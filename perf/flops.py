"""Operations and bytes the algorithm needs, from shapes alone, and the
table of peaks they are held against.

The counts are the architecture's (perf/archs/<name>.py, by the
configuration's ``arch``): minimal-algorithm counts, so that a share of a
peak built on them cannot pass 100 %.  A count may be handed what the
window observed as keywords (live rows, say); an architecture whose count
does not turn on them ignores them.
"""

from __future__ import annotations

import json
import os

from perf import common


def layer_matmul_params(cfg, i=0) -> int:
    return common.arch_of(cfg).layer_matmul_params(cfg, i)


def matmul_params(cfg) -> int:
    """Weights that every token is multiplied by (FLOPs)."""
    return common.arch_of(cfg).matmul_params(cfg)


def total_params(cfg) -> int:
    """Weights held (memory)."""
    return common.arch_of(cfg).total_params(cfg)


def train_flops_per_token(cfg, seq: int, **observed) -> float:
    return common.arch_of(cfg).train_flops_per_token(cfg, seq, **observed)


def kv_bytes_per_token(cfg, itemsize: int = 2, **observed) -> int:
    return common.arch_of(cfg).kv_bytes_per_token(cfg, itemsize, **observed)


def decode_step_bytes(cfg, live_kv_tokens: float, itemsize: int = 2,
                      **observed) -> float:
    return common.arch_of(cfg).decode_step_bytes(cfg, live_kv_tokens,
                                                 itemsize, **observed)


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"perf/peaks.json: add it with its source")
    return table[device_kind]
