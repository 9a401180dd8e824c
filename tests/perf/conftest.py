"""What the tests of the benchmark share."""

import pytest

from perf import common, flops, trace_reduce

MS = 1e6


@pytest.fixture()
def on_cpu(monkeypatch):
    import jax
    from paddle_tpu import compile_cache
    monkeypatch.setattr(common, "require_device",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(compile_cache, "enable_persistent_cache",
                        lambda: None)
    monkeypatch.setattr(flops, "peaks", lambda kind: {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    # the profiler runs, but a CPU trace has no device plane: hand-made
    # intervals stand in, through the same reduction
    monkeypatch.setattr(trace_reduce, "load", lambda path: trace_reduce.Trace(
        {"/device:TPU:0": [("fusion.1", 0, 5 * MS), ("fusion.2", 7 * MS,
                                                     MS)]},
        {"/device:TPU:0": [("jit_decode_paged(1)", 0, 5 * MS),
                           ("jit_prefill_chunk(2)", 7 * MS, MS)]},
        [("bench.engine_step", 0, 6 * MS), ("bench.train_step", 6 * MS,
                                            3 * MS)]))
