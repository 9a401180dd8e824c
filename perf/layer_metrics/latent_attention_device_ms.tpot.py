"""Device time of the ``latent_attention`` kernel (absorbed latent
attention over the block table) inside one execution of the decode
program, summed over its layers, ms, median over executions.  A Pallas
call's event carries the kernel's ``name``; a program without the kernel
reads nothing."""
import numpy as np

from perf import common, program_spans


def kernel_runs(obs):
    """[ns of the kernel] an execution of the decode program inside the
    window; None without a trace, the program or the kernel."""
    arch = common.arch_of(obs["cell"]["config"])
    kernel = getattr(arch, "DECODE_KERNEL", None)
    if not obs.get("trace") or kernel is None or \
            (obs.get("programs") or {}).get("decode") is None:
        return None
    runs = program_spans.per_execution(obs["trace"], "decode",
                                       program_spans.kernel_of)
    runs = [r.get(kernel, 0.0) for r in runs]
    return runs if any(runs) else None


def read(obs):
    runs = kernel_runs(obs)
    return float(np.median(runs) / 1e6) if runs else None
