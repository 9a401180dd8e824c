"""Operations and bytes the algorithm needs, from shapes alone.

Minimal-algorithm counts: causal attention counts the lower triangle only,
recomputation is never counted, and the embedding lookup is a gather (no
matmul).  So a share of a peak built on them cannot pass 100 %.
"""

from __future__ import annotations

import json
import os


def _dims(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, f, h, kv, hd


def layer_matmul_params(cfg) -> int:
    d, f, h, kv, hd = _dims(cfg)
    return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f


def matmul_params(cfg) -> int:
    """Weights that every token is multiplied by: blocks and the head."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + \
        cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg) -> int:
    d = cfg["hidden_size"]
    return matmul_params(cfg) + cfg["vocab_size"] * d + \
        (2 * cfg["num_hidden_layers"] + 1) * d


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward: 6 per matmul weight, and causal attention's
    QK^T and AV (each 2*(s/2)*h*hd a token forward, times 3)."""
    _, _, h, _, hd = _dims(cfg)
    attn = 3 * 2 * 2 * (seq / 2) * h * hd * cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    _, _, _, kv, hd = _dims(cfg)
    return 2 * kv * hd * itemsize * cfg["num_hidden_layers"]


def decode_step_bytes(cfg, live_kv_tokens: float, itemsize: int = 2) -> float:
    """Bytes one decode step must read: every block weight and the head
    once, and the keys and values of the live contexts."""
    return matmul_params(cfg) * itemsize + \
        live_kv_tokens * kv_bytes_per_token(cfg, itemsize)


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"perf/peaks.json: add it with its source")
    return table[device_kind]
