"""Architecture ``sarvam_mla``: Sarvam-105B (``model_type`` ``sarvam_mla``)
— a decoder with latent attention (MLA: a compressed latent and one shared
rotary key are what a token caches), a leading dense gated MLP and then
layers of routed gated experts beside a shared expert, the router taking
sigmoid scores with a choice bias.  The program runs it as
``paddle_tpu.models.HybridForCausalLM`` (layer kinds ``latent_attention``
x ``dense`` | ``experts``).

Every layer (pre-norm, RMSNorm, no biases, untied head):

    x <- x + attn(rms(x));   x <- x + ffn_i(rms(x))

* attention, with ``h = rms(x)``, H heads: ``q = W_q h`` -> [H, nope + rope],
  ``q <- rms_g(q)`` a head; ``[c | k_r] = W_kva h``, ``c <- rms_g(c)``;
  the rope parts of q and the one k_r rotated (rotate halves, YaRN
  frequencies); ``[k_n | v] = W_kvb c`` a head; scores
  ``(q_n k_n + q_r k_r) * scale``, ``scale = (nope + rope)^-1/2 m^2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``; causal softmax; ``W_o``.
* experts: ``s = sigmoid(W_r h)`` (float32); the k experts are the top k
  of ``s + b``; ``g_e = routed_scaling_factor s_e / sum_chosen s``;
  ``y = sum g_e E_e(h) + S(h)``.

The chip's share (``model-configs`` guide, section 4): ``num_experts``
counts the experts held *here*, ids 0 ... ``num_experts - 1``; the router
keeps the published width (``published.num_experts``) and its
``num_experts_per_tok``; the reference, like the program, sums the held
experts' parts and leaves the absent ones' out.  ``vocab_size`` is the
slice of the vocabulary held.

The plain reference is in this file (section 3): float32 under ``highest``
(set by the caller), the **expanded** form only — ``HEAD_GROUP`` heads at
a time, their queries made and their keys and values rebuilt from the
latents, the queries in blocks of ``Q_BLOCK`` against the keys up to the
block's end so that three rows of 33,536 positions fit beside the engine
(a layer's temporaries 2.9 GB as the TPU compiler counts them; all 64
heads' queries at once and 8 heads x 2048 queries of scores were 7.1 GB,
and the chip refused them) —, the experts a held expert at a time
over the rows that chose it.  It imports nothing of the program.  Departures from the published
description are listed in the configuration's ``assumed``.  It serves
only: no ``loss``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perf import common, weights
from perf.archs.granite_moe_hybrid import (COUNTS, KERNEL_SCOPES,  # noqa: F401
                                           dispatch_counts, window_touched)
from perf.reference.decoder import matmul, rms_norm

SCOPES = ("lm_head_ce", "attn", "moe", "mlp", "embed")   # the readers'
# a prefill chunk's attention proper (the walk over the context's tiles,
# under ``attn``) carries a scope of its own; the decode kernel its name
CHUNK_ATTENTION = "latent_chunk_attention"
DECODE_KERNEL = "latent_attention"
# the program's annotation after a prefill chunk's dispatch, on the
# profiler's host plane: stats ``start`` and ``tokens``
CHUNKS = "serving.prefill_context"
# the reference's query block and the heads it attends at once
Q_BLOCK = 1024
HEAD_GROUP = 2


def _router_width(cfg):
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def _held(cfg):
    return tuple(range(cfg["num_experts"]))


def _dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def _dims(cfg):
    """(heads, nope, rope, v, rank)."""
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


# -- 1. the program's model ---------------------------------------------------

def program_config(cfg):
    from paddle_tpu.models import HybridConfig
    n = cfg["num_hidden_layers"]
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=n,
        layer_types=("latent_attention",) * n,
        ffn_types=tuple("dense" if _dense(cfg, i) else "experts"
                        for i in range(n)),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_attention_heads"],
        head_dim=cfg["q_head_dim"],
        intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"],
        dense_intermediate_size=cfg["intermediate_size"],
        num_local_experts=_router_width(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=_held(cfg),
        router_rule="sigmoid_bias",
        routed_scaling_factor=cfg["routed_scaling_factor"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], qk_norm=cfg["use_qk_norm"],
        rope_theta=cfg["rope_theta"], rope_scaling=cfg["rope_scaling"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        embedding_multiplier=1.0, attention_multiplier=None,
        residual_multiplier=1.0, logits_scaling=1.0,
        position_embedding_type="rope",
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

BIAS_STD = 0.02     # below

INITS = {
    # the router's choice bias: 0.02 n.  The scores it is added to are
    # sigmoids of logits of spread ~1.3 (|rms(x)| = 64 against a 0.02 n
    # router), so neighbours in the ranking lie ~0.01 apart at the cut
    # between the 8th and the 9th: a bias of 0.02 n moves about one of a
    # token's eight picks (a test counts them) and leaves every expert
    # reachable.  Wider is not better here: the bias is drawn anew with
    # every seed, and at 0.1 n (this file's first draw) the picks that
    # land on the 16 held experts read 0.64 to 1.43 a token from seed to
    # seed (numpy, 12 draws; a standard deviation of 24 %, 7 % at 0.02 n),
    # which moved a whole run's step times by 7 % between seeds on the
    # chip (PERF.md section 6)
    "choice_bias": lambda key, shape: BIAS_STD * jax.random.normal(
        key, shape, jnp.float32),
}


def layer_prefix(i):
    return f"model.layers_{i}."


def layer_kind(cfg, i):
    """``dense`` or ``experts``: layers of one kind share a compiled
    program in the walking reference."""
    return "dense" if _dense(cfg, i) else "experts"


def layer_leaves(cfg, i):
    d = cfg["hidden_size"]
    heads, nope, rope, vd, rank = _dims(cfg)
    p = layer_prefix(i)
    out = [(p + "input_layernorm.weight", (d,), "gain"),
           (p + "self_attn.q_proj.weight", (d, heads * (nope + rope)),
            "matrix"),
           (p + "self_attn.kv_a_proj_with_mqa.weight", (d, rank + rope),
            "matrix"),
           (p + "self_attn.kv_b_proj.weight", (rank, heads * (nope + vd)),
            "matrix"),
           (p + "self_attn.o_proj.weight", (heads * vd, d), "matrix"),
           (p + "self_attn.q_norm.weight", (nope + rope,), "gain"),
           (p + "self_attn.kv_a_layernorm.weight", (rank,), "gain"),
           (p + "post_attention_layernorm.weight", (d,), "gain")]
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

def yarn_inv_freq(cfg):
    """The rope dims' frequencies in closed form: ``f_j = theta^(-2j/r)``;
    ``corr(t) = r ln(L / 2 pi t) / (2 ln theta)``; ``lo = floor(corr(
    beta_fast))``, ``hi = ceil(corr(beta_slow))``; ``ramp_j = clip((j -
    lo) / (hi - lo), 0, 1)``; ``f'_j = (f_j / factor) ramp_j + f_j (1 -
    ramp_j)``."""
    r, theta, sc = cfg["qk_rope_head_dim"], cfg["rope_theta"], \
        cfg["rope_scaling"]
    corr = lambda turns: r * math.log(
        sc["original_max_position_embeddings"] / (2 * math.pi * turns)) \
        / (2 * math.log(theta))
    lo = max(math.floor(corr(sc["beta_fast"])), 0)
    hi = min(math.ceil(corr(sc["beta_slow"])), r - 1)
    out = []
    for j in range(r // 2):
        f = theta ** (-2.0 * j / r)
        ramp = min(max((j - lo) / (hi - lo), 0.0), 1.0)
        out.append(f / sc["factor"] * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def _m(cfg, a):
    return 0.1 * a * math.log(cfg["rope_scaling"]["factor"]) + 1.0


def score_scale(cfg):
    return cfg["q_head_dim"] ** -0.5 \
        * _m(cfg, cfg["rope_scaling"]["mscale_all_dim"]) ** 2


def _rotate(x, positions, cfg):
    """x [b, s, heads, rope]: pairs (j, j + rope / 2) turned by
    ``positions`` x the frequencies; cos and sin carry
    ``m(mscale) / m(mscale_all_dim)``."""
    sc = cfg["rope_scaling"]
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
    mag = _m(cfg, sc["mscale"]) / _m(cfg, sc["mscale_all_dim"])
    c = (jnp.cos(ang) * mag)[None, :, None, :]
    s = (jnp.sin(ang) * mag)[None, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(y, w, cfg, mm, positions):
    """Expanded latent attention over ``y`` [b, s, d], ``HEAD_GROUP``
    heads at a time: their queries, and their keys and values from the
    latents, then the queries a block at a time against the keys up to
    the block's end.  (All heads' queries at once are 1.65 GB at 33,536
    positions, and as much again once turned: a group's are made when
    the group is attended.)"""
    b, s, _ = y.shape
    heads, nope, rope, vd, rank = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    ckr = mm(y, w["self_attn.kv_a_proj_with_mqa.weight"])
    c = rms_norm(ckr[..., :rank], w["self_attn.kv_a_layernorm.weight"], eps)
    k_r = _rotate(ckr[:, :, None, rank:], positions, cfg)     # [b, s, 1, r]
    group = math.gcd(heads, HEAD_GROUP)
    wq = w["self_attn.q_proj.weight"].reshape(
        -1, heads // group, group * (nope + rope))
    wb = w["self_attn.kv_b_proj.weight"].reshape(
        rank, heads // group, group * (nope + vd))
    scale = score_scale(cfg)

    def heads_of(a, j):
        qh = mm(y, wq[:, j]).reshape(b, s, group, nope + rope)
        if cfg["use_qk_norm"]:
            qh = rms_norm(qh, w["self_attn.q_norm.weight"], eps)
        qh = jnp.concatenate([qh[..., :nope],
                              _rotate(qh[..., nope:], positions, cfg)], -1)
        kv = mm(c, wb[:, j]).reshape(b, s, group, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, group, rope))], -1)
        v = kv[..., nope:]
        outs = []
        for lo in range(0, s, Q_BLOCK):
            hi = min(lo + Q_BLOCK, s)
            sc = jnp.einsum("bqgd,bkgd->bgqk", qh[:, lo:hi], k[:, :hi]) \
                * scale
            seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None]
            sc = jnp.where(seen[None, None], sc, -jnp.inf)
            outs.append(jnp.einsum("bgqk,bkgd->bqgd",
                                   jax.nn.softmax(sc, -1), v[:, :hi]))
        out = jnp.concatenate(outs, 1).reshape(b, s, group * vd)
        return jax.lax.dynamic_update_slice_in_dim(
            a, out, j * group * vd, axis=2), None

    a = jax.lax.scan(heads_of, jnp.zeros((b, s, heads * vd), y.dtype),
                     jnp.arange(heads // group))[0]
    return mm(a, w["self_attn.o_proj.weight"])


def _gated(x, w_in, w_out, mm):
    g, u = jnp.split(mm(x, w_in), 2, axis=-1)
    return mm(jax.nn.silu(g) * u, w_out)


def router_weights(y, w, cfg, mm, bias=True):
    """[b, s, E] float32: the weight the router gives each of its E
    experts at each token (0 where the expert is not chosen)."""
    s = jax.nn.sigmoid(mm(y, w["block_sparse_moe.router.weight"]))
    choice = s + w["block_sparse_moe.router_bias"] if bias else s
    _, topi = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, topi, -1)
    gates = cfg["routed_scaling_factor"] * chosen \
        / jnp.sum(chosen, -1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(topi, s.shape[-1]) * gates[..., None],
                   axis=-2)


def _experts(y, w, cfg, mm, held=None):
    """The held experts' part: each held expert over the rows that chose
    it, times the weight the router gives it there.  An expert's rows
    are gathered (at most a quarter of the positions: four times an even
    share of a router sixteen experts wide or more) and scattered back;
    an expert more rows than that chose goes over every row under its
    weights, which gives the same sum."""
    held = jnp.asarray(_held(cfg) if held is None else held)
    weight = router_weights(y, w, cfg, mm).reshape(-1, _router_width(cfg))
    y2 = y.reshape(-1, y.shape[-1])
    n = y2.shape[0]
    cap = max(n // 4, 1)

    def one(acc, e):
        wt = weight[:, held[e]]
        w_in = w["block_sparse_moe.w_in"][e]
        w_out = w["block_sparse_moe.w_out"][e]

        def few(acc):
            idx = jnp.nonzero(wt > 0, size=cap, fill_value=0)[0]
            real = jnp.arange(cap) < jnp.sum(wt > 0)
            out = _gated(y2[idx], w_in, w_out, mm) \
                * jnp.where(real, wt[idx], 0.0)[:, None]
            return acc.at[idx].add(out)

        def every(acc):
            return acc + _gated(y2, w_in, w_out, mm) * wt[:, None]

        return jax.lax.cond(jnp.sum(wt > 0) <= cap, few, every, acc), None

    out = jax.lax.scan(one, jnp.zeros_like(y2), jnp.arange(len(held)))[0]
    return out.reshape(y.shape)


def layer(x, w, cfg, i, positions, precision="float32"):
    """Block ``i`` over ``x`` [b, s, d]; ``w`` holds the layer's leaves
    under their names less ``layer_prefix(i)``."""
    mm = functools.partial(matmul, precision=precision)
    eps = cfg["rms_norm_eps"]
    x = x + _attention(rms_norm(x, w["input_layernorm.weight"], eps), w,
                       cfg, mm, positions)
    y = rms_norm(x, w["post_attention_layernorm.weight"], eps)
    if _dense(cfg, i):
        return x + mm(jax.nn.silu(mm(y, w["mlp.gate_proj.weight"]))
                      * mm(y, w["mlp.up_proj.weight"]),
                      w["mlp.down_proj.weight"])
    shared = _gated(y, w["shared_mlp.input_linear.weight"],
                    w["shared_mlp.output_linear.weight"], mm)
    return x + _experts(y, w, cfg, mm) + shared


def embed(w, cfg, ids):
    return w["model.embed_tokens.weight"][ids]


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
    d = cfg["hidden_size"]
    heads, nope, rope, vd, rank = _dims(cfg)
    return d * heads * (nope + rope) + d * (rank + rope) \
        + rank * heads * (nope + vd) + heads * vd * d + (nope + rope) + rank


def _dense_params(cfg, i) -> int:
    """A layer's parameters outside its routed experts."""
    d = cfg["hidden_size"]
    if _dense(cfg, i):
        return _attn_params(cfg) + 2 * d + 3 * d * cfg["intermediate_size"]
    return _attn_params(cfg) + 2 * d + (d + 1) * _router_width(cfg) \
        + cfg["num_shared_experts"] * _expert_params(cfg)


def _expert_layers(cfg) -> int:
    return sum(not _dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


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


def latent_row_stored(cfg) -> int:
    """Values a cached token holds a layer: the latent and the rotary
    key, padded to whole 128-value lanes as the pool stores them (the
    chip's tiled layout holds 576 as 640 whatever the shape says)."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def kv_bytes_per_token(cfg, itemsize: int = 2, **observed) -> int:
    """What a token leaves in the cache: one stored row a layer."""
    return latent_row_stored(cfg) * itemsize * cfg["num_hidden_layers"]


def latent_decode_cost(cfg, cached_tokens: float, itemsize: int = 2):
    """(operations, bytes) the decode kernel needs over ``cached_tokens``
    row-attended cached tokens, every layer: absorbed, a cached pair is
    2 H (row + rank) operations and the token's stored row is read once."""
    heads, _, rope, _, rank = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    return (layers * cached_tokens * 2 * heads * (2 * rank + rope),
            cached_tokens * kv_bytes_per_token(cfg, itemsize))


def latent_prefill_cost(cfg, start: int, tokens: int) -> float:
    """Operations the causal pairs of one chunk need, every layer, in
    the cheaper (expanded) form: ``tokens`` queries from position
    ``start``, query t seeing start + t + 1 keys, 2 H (nope + rope + v) a
    pair.  The same work whatever implements it."""
    heads, nope, rope, vd, _ = _dims(cfg)
    pairs = tokens * start + tokens * (tokens + 1) / 2
    return cfg["num_hidden_layers"] * pairs * 2 * heads * (nope + rope + vd)


def moe_step_bytes(cfg, touched: float, layer_steps: int,
                   itemsize: int = 2) -> float:
    """Bytes the ``moe`` scope must read over ``layer_steps`` expert
    layers of decode steps that touched ``touched`` held experts in sum
    (the program's own count): those experts, and a layer's shared
    expert, router, bias and norm each time."""
    d = cfg["hidden_size"]
    per = (d + 1) * _router_width(cfg) + d \
        + cfg["num_shared_experts"] * _expert_params(cfg)
    return (layer_steps * per + touched * _expert_params(cfg)) * itemsize


def decode_step_bytes(cfg, live_kv_tokens: float, itemsize: int = 2, *,
                      live_rows=None, **observed) -> float:
    """Bytes the traced window's median decode step must move: every
    weight outside the routed experts and the head once (the embedding
    is a gather of the live rows: not counted), the experts that step
    touched (the program's own count, ``window_touched``), and the live
    contexts' stored latent rows."""
    d = cfg["hidden_size"]
    dense = sum(_dense_params(cfg, i)
                for i in range(cfg["num_hidden_layers"])) + \
        d * cfg["vocab_size"] + d
    touched = (window_touched() or 0.0) * _expert_layers(cfg)
    return (dense + touched * _expert_params(cfg)) * itemsize + \
        live_kv_tokens * kv_bytes_per_token(cfg, itemsize)


def chunk_contexts():
    """((window begin, end), ((ns, start, tokens), ...)): the program's
    ``serving.prefill_context`` annotations of the run's trace in time
    order — one a prefill chunk's dispatch, written just after it —
    beside the benchmark's window markers.  None without a trace or
    where the program writes no such annotation."""
    import os
    from perf import program_spans
    path = program_spans.find_xplane()
    got = _chunk_contexts(path, os.path.getmtime(path)) if path else None
    return got if got and got[1] else None


@functools.lru_cache(maxsize=2)
def _chunk_contexts(path, _mtime):
    from perf import trace_reduce
    data = jax.profiler.ProfileData.from_file(path)
    lo, hi, found = float("-inf"), float("inf"), []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == CHUNKS:
                    stats = dict(e.stats)
                    found.append((float(e.start_ns), int(stats["start"]),
                                  int(stats["tokens"])))
                elif e.name == trace_reduce.WINDOW_BEGIN:
                    lo = max(lo, float(e.start_ns))
                elif e.name == trace_reduce.WINDOW_END:
                    hi = min(hi, float(e.start_ns))
    return (lo, hi), tuple(sorted(found))
