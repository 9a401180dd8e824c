"""A prefill chunk's attention proper (the walk over the paged context's
tiles, the scope the architecture names ``CHUNK_ATTENTION``) against its
arithmetic's peak, %, over the traced window's prefill-chunk executions:
the operations the pairs each chunk's queries can really see need — a
full layer everything before the query, a sliding-window layer the last
``sliding_window`` positions; the architecture's ``walk_cost`` of the
chunk's start and tokens: the same work whatever implements it, so a walk
that multiplies masked tiles reads lower — over the chip's peak, over the
walk's device time, both summed over the executions.  Which chunk an
execution ran is the program's ``serving.prefill_context`` annotation,
paired as ``latent_prefill_roofline.ttft`` pairs them."""
import os

from perf import common

_pairs = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "latent_prefill_roofline.ttft.py"),
    "perf_chunk_pairs")


def read(obs):
    cfg = obs["cell"]["config"]
    count = getattr(common.arch_of(cfg), "walk_cost", None)
    pairs = _pairs.matched(obs) if count else None
    if not pairs:
        return None
    need = sum(count(cfg, start, tokens) for _, start, tokens in pairs)
    ns = sum(p[0] for p in pairs)
    return 100.0 * need / obs["peaks"]["bf16_flops"] / (ns / 1e9) \
        if ns else None
