"""Architecture ``afmoe``: Arcee Trinity (``model_type`` ``afmoe``) — a
decoder of gated grouped-query attention, three layers that see a sliding
window and turn rotary positions to one that sees everything and turns
none, with a norm on every query and key head, four norms a layer
(sandwich), leading dense gated MLPs and then layers of routed gated
experts beside a shared expert, the router taking sigmoid scores with a
choice bias.  The program runs it as ``paddle_tpu.models.HybridForCausalLM``
(layer kind ``attention`` with a per-layer window and rotary x ``dense`` |
``experts``), the window layers' cache in a block group of their own.

``h_0 = E[ids] sqrt(d)`` (``mup_enabled``).  Layer ``i``, ``R`` an RMSNorm
with a learned gain:

    a = R_in(x);  q = R_q(a W_q), k = R_k(a W_k) a head;  v = a W_v;  g = a W_g
    sliding_attention: q, k turned (theta, the whole head, halves); key j
                       seen by query t iff 0 <= t - j < sliding_window
    full_attention:    not turned; causal over everything
    o = softmax(q k^T head_dim^-1/2) v;  x <- x + R_post_attn((o * sigmoid(g)) W_o)
    m = R_pre_mlp(x);  x <- x + R_post_mlp(ffn_i(m))

* ffn: ``W_down(silu(W_gate m) * W_up m)`` for ``i < num_dense_layers``;
  else ``shared(m) + sum_chosen g_e E_e(m)`` with ``s = sigmoid(W_r m)``
  (float32), the k experts the top k of ``s + b``, ``g_e = route_scale
  s_e / sum_chosen s``.

The chip's share (``model-configs`` guide, section 4): ``num_experts``
counts the experts held *here*, ids 0 ... ``num_experts - 1``; the router
keeps the published width (``published.num_experts``) and its
``num_experts_per_tok``; the reference, like the program, sums the held
experts' parts and leaves the absent ones' out.  ``vocab_size`` is the
slice of the vocabulary held.

The plain reference is in this file (section 3): float32 under ``highest``
(set by the caller), a key-value head's six query heads at a time, the
queries in blocks of ``Q_BLOCK`` — against every key under the causal mask
in a full layer, against the ``sliding_window + Q_BLOCK - 1`` keys a block
can see in a window layer, the mask written as the inequality above — so
that three rows of 33,536 positions fit beside the engine; the experts
are ``sarvam_mla``'s (a held expert at a time over the rows that chose
it).  It imports nothing of the program.  Departures from the published
description are listed in the configuration's ``assumed``.  It serves
only: no ``loss``.
"""

from __future__ import annotations

import functools
import math
import os
import statistics

import jax
import jax.numpy as jnp

from perf import common, weights
from perf.archs import sarvam_mla as _sarvam
from perf.archs.sarvam_mla import (CHUNKS, COUNTS, KERNEL_SCOPES,  # noqa: F401
                                   chunk_contexts, dispatch_counts,
                                   window_touched)
from perf.reference.decoder import matmul, rms_norm, rope, rope_tables

SCOPES = ("lm_head_ce", "attn", "moe", "mlp", "embed")   # the readers'
# inside ``attn``: a layer's attention by the kind of the layer (the
# window_* readers pass these, in this order, as their own tuple)
WINDOW_SCOPES = ("attn_window", "attn_full")
# a prefill chunk's attention proper (the walk over the paged context's
# tiles) carries a scope of its own; the decode kernel its name
CHUNK_ATTENTION = "paged_chunk_attention"
DECODE_KERNEL = "paged_attention"
# the program's annotation after a decode dispatch, on the profiler's host
# plane: stats ``rows``, ``tokens`` (the rows' lengths in sum) and
# ``window_tokens`` (their sum with each length cut at the window)
LIVE = "serving.kv_live"
# the reference's query block
Q_BLOCK = 256


def _router_width(cfg):
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def _dense(cfg, i):
    return i < cfg["num_dense_layers"]


def _window(cfg, i):
    """Layer ``i``'s window in positions; 0: it sees everything."""
    return cfg["sliding_window"] \
        if cfg["layer_types"][i] == "sliding_attention" else 0


def _dims(cfg):
    """(query heads, key-value heads, head size)."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def _as_sarvam(cfg):
    """The configuration under the keys sarvam_mla's expert functions
    read; every other key they read is spelt alike."""
    return dict(cfg, routed_scaling_factor=cfg["route_scale"])


# -- 1. the program's model ---------------------------------------------------

def program_config(cfg):
    from paddle_tpu.models import HybridConfig
    n = cfg["num_hidden_layers"]
    heads, kv, hd = _dims(cfg)
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=n, layer_types=("attention",) * n,
        ffn_types=tuple("dense" if _dense(cfg, i) else "experts"
                        for i in range(n)),
        layer_windows=tuple(_window(cfg, i) for i in range(n)),
        layer_rotary=tuple(bool(_window(cfg, i)) for i in range(n)),
        num_attention_heads=heads, num_key_value_heads=kv, head_dim=hd,
        qk_norm=True, attention_gate=True, sandwich_norm=True,
        intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"],
        dense_intermediate_size=cfg["intermediate_size"],
        num_local_experts=_router_width(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=tuple(range(cfg["num_experts"])),
        router_rule="sigmoid_bias",
        routed_scaling_factor=cfg["route_scale"],
        rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        embedding_multiplier=math.sqrt(cfg["hidden_size"])
        if cfg["mup_enabled"] else 1.0,
        attention_multiplier=None, residual_multiplier=1.0,
        logits_scaling=1.0, position_embedding_type="rope",
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"])


def build(cfg, seed, device):
    """The program's model, constructed without device arrays of its own
    (``LazyGuard``) and then given the seed's weights."""
    import paddle_tpu as pp
    from paddle_tpu.models import HybridForCausalLM
    from paddle_tpu.nn import LazyGuard
    pp.seed(common.seed_key(seed))
    with jax.default_device(device):
        with LazyGuard():
            model = HybridForCausalLM(program_config(cfg))
        weights.give(model, cfg, seed)
    return model


# -- 2. the leaves ------------------------------------------------------------

# the router's choice bias: 0.02 n, sarvam-105b.L5's draw for its reasons
# (wider, and the picks that land on the held experts swing from seed to
# seed)
INITS = _sarvam.INITS


def layer_prefix(i):
    return f"model.layers_{i}."


def layer_kind(cfg, i):
    """(``window`` | ``full``) + (``dense`` | ``experts``): layers of one
    kind share a compiled program in the walking reference."""
    return ("window" if _window(cfg, i) else "full") + "+" + \
        ("dense" if _dense(cfg, i) else "experts")


def layer_leaves(cfg, i):
    d = cfg["hidden_size"]
    heads, kv, hd = _dims(cfg)
    p = layer_prefix(i)
    out = [(p + "input_layernorm.weight", (d,), "gain"),
           (p + "self_attn.q_proj.weight", (d, heads * hd), "matrix"),
           (p + "self_attn.k_proj.weight", (d, kv * hd), "matrix"),
           (p + "self_attn.v_proj.weight", (d, kv * hd), "matrix"),
           (p + "self_attn.gate_proj.weight", (d, heads * hd), "matrix"),
           (p + "self_attn.o_proj.weight", (heads * hd, d), "matrix"),
           (p + "self_attn.q_norm.weight", (hd,), "gain"),
           (p + "self_attn.k_norm.weight", (hd,), "gain"),
           (p + "post_attention_layernorm.weight", (d,), "gain"),
           (p + "pre_mlp_layernorm.weight", (d,), "gain"),
           (p + "post_mlp_layernorm.weight", (d,), "gain")]
    if _dense(cfg, i):
        f = cfg["intermediate_size"]
        return out + [(p + "mlp.gate_proj.weight", (d, f), "matrix"),
                      (p + "mlp.up_proj.weight", (d, f), "matrix"),
                      (p + "mlp.down_proj.weight", (f, d), "matrix")]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    fs = cfg["num_shared_experts"] * f
    return out + [
        (p + "block_sparse_moe.router.weight", (d, _router_width(cfg)),
         "matrix"),
        (p + "block_sparse_moe.router_bias", (_router_width(cfg),),
         "choice_bias"),
        (p + "block_sparse_moe.w_in", (held, d, 2 * f), "matrix"),
        (p + "block_sparse_moe.w_out", (held, f, d), "matrix"),
        (p + "shared_mlp.input_linear.weight", (d, 2 * fs), "matrix"),
        (p + "shared_mlp.output_linear.weight", (fs, d), "matrix")]


def embed_leaves(cfg):
    return [("model.embed_tokens.weight",
             (cfg["vocab_size"], cfg["hidden_size"]), "matrix")]


def head_leaves(cfg):
    return [("model.norm.weight", (cfg["hidden_size"],), "gain"),
            ("lm_head.weight", (cfg["hidden_size"], cfg["vocab_size"]),
             "matrix")]


def leaves(cfg):
    """[(name, shape, init)] in a fixed order; a leaf's index is its key."""
    out = embed_leaves(cfg)
    for i in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, i)
    return out + head_leaves(cfg)


# -- 3. the plain reference ---------------------------------------------------

def _attention(y, w, cfg, mm, positions, window):
    """Gated grouped-query attention over ``y`` [b, s, d], a key-value
    head (and its query heads) at a time, the queries a block at a time.
    ``window`` 0: the layer sees everything and turns nothing."""
    b, s, d = y.shape
    heads, kv, hd = _dims(cfg)
    g, eps = heads // kv, cfg["rms_norm_eps"]
    wq = w["self_attn.q_proj.weight"].reshape(d, kv, g * hd)
    wg = w["self_attn.gate_proj.weight"].reshape(d, kv, g * hd)
    wk = w["self_attn.k_proj.weight"].reshape(d, kv, hd)
    wv = w["self_attn.v_proj.weight"].reshape(d, kv, hd)
    cos, sin = rope_tables(cfg, positions)
    qb = math.gcd(s, Q_BLOCK)
    # the keys a block of queries can see: all of them, or the window's
    span = min(window + qb - 1, s) if window else s

    def head(a, j):
        q = rms_norm(mm(y, wq[:, j]).reshape(b, s, g, hd),
                     w["self_attn.q_norm.weight"], eps)
        k = rms_norm(mm(y, wk[:, j]).reshape(b, s, 1, hd),
                     w["self_attn.k_norm.weight"], eps)
        v = mm(y, wv[:, j])
        if window:
            q, k = rope(q, cos, sin), rope(k, cos, sin)
        k = k[:, :, 0]

        def block(_, lo):
            first = jnp.clip(lo - (window - 1), 0, s - span) if window else 0
            kb = jax.lax.dynamic_slice_in_dim(k, first, span, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, first, span, 1)
            t = (lo + jnp.arange(qb))[:, None]
            at = (first + jnp.arange(span))[None]
            seen = (t - at >= 0) & ((t - at < window) if window else True)
            sc = jnp.einsum("bqgd,bkd->bgqk",
                            jax.lax.dynamic_slice_in_dim(q, lo, qb, 1), kb) \
                * hd ** -0.5
            sc = jnp.where(seen[None, None], sc, -jnp.inf)
            return None, jnp.einsum("bgqk,bkd->bqgd",
                                    jax.nn.softmax(sc, -1), vb)

        out = jax.lax.scan(block, None, jnp.arange(0, s, qb))[1]
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, g * hd)
        out = out * jax.nn.sigmoid(mm(y, wg[:, j]))
        return jax.lax.dynamic_update_slice_in_dim(
            a, out, j * g * hd, axis=2), None

    a = jax.lax.scan(head, jnp.zeros((b, s, heads * hd), y.dtype),
                     jnp.arange(kv))[0]
    return mm(a, w["self_attn.o_proj.weight"])


def _ffn(m, w, cfg, i, mm, held=None):
    if _dense(cfg, i):
        return mm(jax.nn.silu(mm(m, w["mlp.gate_proj.weight"]))
                  * mm(m, w["mlp.up_proj.weight"]),
                  w["mlp.down_proj.weight"])
    shared = _sarvam._gated(m, w["shared_mlp.input_linear.weight"],
                            w["shared_mlp.output_linear.weight"], mm)
    return shared + _sarvam._experts(m, w, _as_sarvam(cfg), mm, held)


def layer(x, w, cfg, i, positions, precision="float32"):
    """Block ``i`` over ``x`` [b, s, d]; ``w`` holds the layer's leaves
    under their names less ``layer_prefix(i)``."""
    mm = functools.partial(matmul, precision=precision)
    eps = cfg["rms_norm_eps"]
    a = _attention(rms_norm(x, w["input_layernorm.weight"], eps), w, cfg,
                   mm, positions, _window(cfg, i))
    x = x + rms_norm(a, w["post_attention_layernorm.weight"], eps)
    m = rms_norm(x, w["pre_mlp_layernorm.weight"], eps)
    return x + rms_norm(_ffn(m, w, cfg, i, mm),
                        w["post_mlp_layernorm.weight"], eps)


def embed(w, cfg, ids):
    scale = math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0
    return w["model.embed_tokens.weight"][ids] * scale


def head(h, w, cfg, precision="float32"):
    """Final norm and the untied head over hidden rows ``h`` [n, d]."""
    h = rms_norm(h, w["model.norm.weight"], cfg["rms_norm_eps"])
    return matmul(h, w["lm_head.weight"], precision)


def logits(w, cfg, ids, precision="float32"):
    """One full forward, ``w`` holding every leaf under its full name
    (the tests' reference; the cells walk ``layer`` a layer at a time)."""
    x = embed(w, cfg, ids)
    for i in range(cfg["num_hidden_layers"]):
        p = layer_prefix(i)
        x = layer(x, {n[len(p):]: a for n, a in w.items()
                      if n.startswith(p)}, cfg, i,
                  jnp.arange(ids.shape[1]), precision)
    b, s, d = x.shape
    return head(x.reshape(b * s, d), w, cfg, precision).reshape(b, s, -1)


# -- 4. the counts ------------------------------------------------------------
# Minimal-algorithm counts (the gqa_decoder file's note): only what a step
# must touch, so a share of a peak built on them cannot pass 100 %.

def _expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _attn_params(cfg) -> int:
    """Attention's five projections, its two head norms and the layer's
    four norms."""
    d = cfg["hidden_size"]
    heads, kv, hd = _dims(cfg)
    return d * hd * (3 * heads + 2 * kv) + 2 * hd + 4 * d


def _dense_params(cfg, i) -> int:
    """A layer's parameters outside its routed experts."""
    d = cfg["hidden_size"]
    if _dense(cfg, i):
        return _attn_params(cfg) + 3 * d * cfg["intermediate_size"]
    return _attn_params(cfg) + (d + 1) * _router_width(cfg) \
        + cfg["num_shared_experts"] * _expert_params(cfg)


def _expert_layers(cfg) -> int:
    return sum(not _dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


def _window_layers(cfg) -> int:
    return sum(bool(_window(cfg, i))
               for i in range(cfg["num_hidden_layers"]))


def layer_matmul_params(cfg, i=0) -> float:
    """Weights a token is multiplied by in layer ``i``: the dense part
    and its picks' share of the held experts."""
    if _dense(cfg, i):
        return _dense_params(cfg, i)
    picks = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / _router_width(cfg)
    return _dense_params(cfg, i) + picks * _expert_params(cfg)


def matmul_params(cfg) -> float:
    return sum(layer_matmul_params(cfg, i)
               for i in range(cfg["num_hidden_layers"])) + \
        cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg) -> int:
    """Parameters held: what the weights cost in memory."""
    d = cfg["hidden_size"]
    return sum(_dense_params(cfg, i) + (0 if _dense(cfg, i) else
                                        cfg["num_experts"]
                                        * _expert_params(cfg))
               for i in range(cfg["num_hidden_layers"])) + \
        2 * cfg["vocab_size"] * d + d


def kv_layer_bytes(cfg, itemsize: int = 2) -> int:
    """A token's key and value in one layer."""
    _, kv, hd = _dims(cfg)
    return 2 * kv * hd * itemsize


def kv_bytes_per_token(cfg, itemsize: int = 2, **observed) -> int:
    """What a token leaves in the cache while every layer holds it: a
    key and a value a layer (a window layer lets go of it
    ``sliding_window`` positions later)."""
    return kv_layer_bytes(cfg, itemsize) * cfg["num_hidden_layers"]


def moe_step_bytes(cfg, touched: float, layer_steps: int,
                   itemsize: int = 2) -> float:
    return _sarvam.moe_step_bytes(cfg, touched, layer_steps, itemsize)


@functools.lru_cache(maxsize=2)
def _kv_live(path, _mtime):
    from perf import trace_reduce
    data = jax.profiler.ProfileData.from_file(path)
    lo, hi, found = float("-inf"), float("inf"), []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == LIVE:
                    st = dict(e.stats)
                    found.append((float(e.start_ns), int(st["rows"]),
                                  int(st["tokens"]),
                                  int(st["window_tokens"])))
                elif e.name == trace_reduce.WINDOW_BEGIN:
                    lo = max(lo, float(e.start_ns))
                elif e.name == trace_reduce.WINDOW_END:
                    hi = min(hi, float(e.start_ns))
    return (lo, hi), tuple(sorted(found))


def kv_live():
    """((window begin, end), ((ns, rows, tokens, window tokens), ...)):
    the program's ``serving.kv_live`` annotations of the run's trace in
    time order, one a decode dispatch, beside the benchmark's window
    markers.  None without a trace or where the program writes none."""
    from perf import program_spans
    path = program_spans.find_xplane()
    got = _kv_live(path, os.path.getmtime(path)) if path else None
    return got if got and got[1] else None


def window_live():
    """(tokens, window tokens) of the window's mean decode dispatch: the
    keys its rows attend in a layer that sees everything and in one that
    sees a window.  None where ``kv_live`` finds nothing."""
    got = kv_live()
    if got is None:
        return None
    (lo, hi), found = got
    inside = [f[2:] for f in found if lo <= f[0] <= hi]
    return tuple(statistics.fmean(c) for c in zip(*inside)) \
        if inside else None


def window_decode_bytes(cfg, window_tokens: float, itemsize: int = 2):
    """Bytes the decode kernel must read in the window layers of one
    step: the stored keys and values of ``window_tokens`` row-attended
    positions (each row's length cut at the window), a window layer."""
    return _window_layers(cfg) * window_tokens * kv_layer_bytes(cfg,
                                                                itemsize)


def walk_cost(cfg, start: int, tokens: int) -> float:
    """Operations the visible pairs of one prefill chunk need, every
    layer: ``tokens`` queries from position ``start``, query t seeing
    its ``start + t + 1`` predecessors and itself in a full layer and
    the last ``sliding_window`` of them in a window layer, 4 x heads x
    head size a pair (q.k and p.v).  The same work whatever implements
    it."""
    heads, _, hd = _dims(cfg)
    n, w = cfg["num_hidden_layers"], _window_layers(cfg)
    seen = [start + t + 1 for t in range(tokens)]
    pairs = (n - w) * sum(seen) + \
        w * sum(min(s, cfg["sliding_window"]) for s in seen)
    return pairs * 4 * heads * hd


def dispatch_steps(cfg) -> float:
    """Decode steps the window's median dispatch fused (the engine's
    ``steps_per_sync``), by the program's own count: the expert
    layer-steps a dispatch reports over the expert layers.  1 where
    ``dispatch_counts`` finds nothing."""
    got = dispatch_counts()
    if got is None:
        return 1.0
    (lo, hi), counts = got
    inside = [n for at, _, n in counts if lo <= at <= hi and n]
    return statistics.median(inside) / _expert_layers(cfg) \
        if inside else 1.0


def decode_step_bytes(cfg, live_kv_tokens: float, itemsize: int = 2, *,
                      live_rows=None, **observed) -> float:
    """Bytes the traced window's mean decode **dispatch** must move (the
    reader sets them against one execution's time, and an execution is
    ``dispatch_steps`` steps, none of which re-uses what another read):
    a step's are every weight outside the routed experts and the head
    once (the embedding is a gather of the live rows: not counted), the
    experts that step touched (the program's own count,
    ``window_touched``), and the live contexts' keys and values **as
    the layers need them**: a full layer every live position, a window
    layer each row's last ``sliding_window`` — the program's own sums
    (``window_live``), not the harness's ``live_kv_tokens``, which
    counts what one table would hold and would put four layers of five
    past their windows.  Without the program's sums the window layers
    are left out (the share can then only read low)."""
    d = cfg["hidden_size"]
    n, w = cfg["num_hidden_layers"], _window_layers(cfg)
    dense = sum(_dense_params(cfg, i) for i in range(n)) + \
        d * cfg["vocab_size"] + d
    touched = (window_touched() or 0.0) * _expert_layers(cfg)
    full, windowed = window_live() or (live_kv_tokens, 0.0)
    return dispatch_steps(cfg) * (
        (dense + touched * _expert_params(cfg)) * itemsize
        + kv_layer_bytes(cfg, itemsize) * ((n - w) * full + w * windowed))
