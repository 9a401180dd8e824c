"""Architecture ``kimi_linear``: Kimi-Linear-48B-A3B (``model_type``
``kimi_linear``) — a decoder whose layers are Kimi Delta Attention (KDA: a
gated delta rule whose decay is per channel, a matrix of state a head and
no cache that grows) three to one of latent attention without rotary
positions (MLA: a compressed latent and one shared key are what a token
caches), a leading dense gated MLP and then layers of routed gated experts
beside a shared expert, the router taking sigmoid scores with a choice
bias.  The program runs it as ``paddle_tpu.models.HybridForCausalLM``
(layer kinds ``kda`` | ``latent_attention`` x ``dense`` | ``experts``).

Every layer (pre-norm, RMSNorm, no biases, no multipliers, untied head):

    x <- x + mixer_i(rms(x));   x <- x + ffn_i(rms(x))

* KDA, with ``h = rms(x)``, H heads of D keys and values, P = H D:
  ``[q~ | k~ | v~ | f | g | b] = W_in h``; ``q, k, v = silu(conv(.))``
  (depthwise, causal, no bias); ``q <- q / |q| D^-1/2``, ``k <- k / |k|``
  a head (1e-6 inside the root); ``a = -exp(A_log) softplus(W_f f +
  dt_bias)`` a channel; ``beta = sigmoid(b)`` a head; then a position at
  a time, a head: ``S' = Diag(e^a) S``, ``S = S' + beta k (v - S'^T
  k)^T``, ``o = S^T q``; ``y = rms_w(o) sigmoid(W_g g)`` a head; ``W_o y``.
* MLA: ``q = W_q h`` -> [H, nope + rope]; ``[c | k_r] = W_kva h``,
  ``c <- rms_g(c)``; ``[k_n | v] = W_kvb c`` a head; scores
  ``(q_n k_n + q_r k_r) (nope + rope)^-1/2``; causal softmax; ``W_o``.
  Nothing is turned (``mla_use_nope``) and the query has no norm.
* experts: ``perf/archs/sarvam_mla.py``'s, at this model's sizes:
  ``s = sigmoid(W_r h)``; the k experts are the top k of ``s + bias``;
  ``g_e = routed_scaling_factor s_e / sum_chosen s``; ``y = sum g_e
  E_e(h) + S(h)``.

The chip's share (``model-configs`` guide, section 4): ``num_experts``
counts the experts held *here*, ids 0 ... ``num_experts - 1``; the router
keeps the published width (``published.num_experts``) and its
``num_experts_per_token``; the reference, like the program, sums the held
experts' parts and leaves the absent ones' out.  ``vocab_size`` is the
slice of the vocabulary held.  Which layers are KDA is
``linear_attn_config.kda_layers`` (1-indexed, as published).

The plain reference is in this file (section 3): float32 under ``highest``
(set by the caller), the recurrence written as the recurrence (a
``lax.scan`` over positions, the state's three sums elementwise), latent
attention expanded a few heads and a block of queries at a time, the
experts sarvam_mla's.  It imports nothing of the program and shares no
chunking with it.  Departures from the published description are listed
in the configuration's ``assumed``.  It serves only: no ``loss``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perf import common, weights
from perf.archs import granite_moe_hybrid as _granite
from perf.archs import sarvam_mla as _sarvam
from perf.archs.sarvam_mla import (CHUNK_ATTENTION, CHUNKS,  # noqa: F401
                                   COUNTS, DECODE_KERNEL, KERNEL_SCOPES,
                                   chunk_contexts, dispatch_counts,
                                   latent_row_stored, moe_step_bytes,
                                   window_touched)
from perf.reference.decoder import matmul, rms_norm

SCOPES = ("lm_head_ce", "ssm", "attn", "moe", "mlp", "embed")  # the readers'
# the recurrence alone, nested in ``ssm``: the kda_* readers' own scope
RECURRENCE = "kda"
# the reference's query block and the heads it attends at once
Q_BLOCK = 1024
HEAD_GROUP = 2


def _router_width(cfg):
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def _dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def _kda(cfg, i):
    return i + 1 in cfg["linear_attn_config"]["kda_layers"]


def _kda_dims(cfg):
    """(heads, head size, heads x head size, taps)."""
    lin = cfg["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"],
            lin["num_heads"] * lin["head_dim"], lin["short_conv_kernel_size"])


def _mla_dims(cfg):
    """(heads, nope, rope, v, rank)."""
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def _layers(cfg, kda):
    return [i for i in range(cfg["num_hidden_layers"])
            if _kda(cfg, i) == kda]


def _as_sarvam(cfg):
    """The configuration under the key sarvam_mla's expert functions read
    (``num_experts_per_tok``); every other key they read is spelt alike."""
    return dict(cfg, num_experts_per_tok=cfg["num_experts_per_token"])


# -- 1. the program's model ---------------------------------------------------

def program_config(cfg):
    from paddle_tpu.models import HybridConfig
    n = cfg["num_hidden_layers"]
    heads, head, _, taps = _kda_dims(cfg)
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=n,
        layer_types=tuple("kda" if _kda(cfg, i) else "latent_attention"
                          for i in range(n)),
        ffn_types=tuple("dense" if _dense(cfg, i) else "experts"
                        for i in range(n)),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"],
        dense_intermediate_size=cfg["intermediate_size"],
        num_local_experts=_router_width(cfg),
        num_experts_per_tok=cfg["num_experts_per_token"],
        held_experts=tuple(range(cfg["num_experts"])),
        router_rule="sigmoid_bias",
        routed_scaling_factor=cfg["routed_scaling_factor"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], qk_norm=False,
        tie_word_embeddings=cfg["tie_word_embeddings"],
        kda_n_heads=heads, kda_head_dim=head, kda_d_conv=taps,
        embedding_multiplier=1.0, attention_multiplier=None,
        residual_multiplier=1.0, logits_scaling=1.0,
        position_embedding_type="nope" if cfg["mla_use_nope"] else "rope",
        max_position_embeddings=cfg["model_max_length"],
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

# ``A_log`` (log U(1, 16) a head), ``dt_bias`` (the inverse softplus of a
# log-uniform step in [1e-3, 1e-1], a channel) and the convolution's taps
# as the published code draws them — Mamba-2's draws, which
# granite_moe_hybrid has; the router's choice bias at sarvam_mla's 0.02 n
INITS = {"a_log": _granite.INITS["a_log"],
         "dt_bias": _granite.INITS["dt_bias"],
         "conv": _granite.INITS["conv"],
         "choice_bias": _sarvam.INITS["choice_bias"]}


def layer_prefix(i):
    return f"model.layers_{i}."


def layer_kind(cfg, i):
    """Mixer and feed-forward: layers of one kind share a compiled
    program in the walking reference."""
    return ("kda" if _kda(cfg, i) else "mla") + \
        ("+dense" if _dense(cfg, i) else "+experts")


def layer_leaves(cfg, i):
    d = cfg["hidden_size"]
    p = layer_prefix(i)
    out = [(p + "input_layernorm.weight", (d,), "gain")]
    if _kda(cfg, i):
        heads, head, inner, taps = _kda_dims(cfg)
        out += [(p + "kda.in_proj.weight",
                 (d, 3 * inner + 2 * head + heads), "matrix"),
                (p + "kda.conv1d.weight", (taps, 3 * inner), "conv"),
                (p + "kda.f_proj.weight", (head, inner), "matrix"),
                (p + "kda.g_proj.weight", (head, inner), "matrix"),
                (p + "kda.dt_bias", (inner,), "dt_bias"),
                (p + "kda.A_log", (heads,), "a_log"),
                (p + "kda.o_norm.weight", (head,), "gain"),
                (p + "kda.o_proj.weight", (inner, d), "matrix")]
    else:
        heads, nope, rope, vd, rank = _mla_dims(cfg)
        out += [(p + "self_attn.q_proj.weight", (d, heads * (nope + rope)),
                 "matrix"),
                (p + "self_attn.kv_a_proj_with_mqa.weight", (d, rank + rope),
                 "matrix"),
                (p + "self_attn.kv_b_proj.weight",
                 (rank, heads * (nope + vd)), "matrix"),
                (p + "self_attn.o_proj.weight", (heads * vd, d), "matrix"),
                (p + "self_attn.kv_a_layernorm.weight", (rank,), "gain")]
    out.append((p + "post_attention_layernorm.weight", (d,), "gain"))
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


embed_leaves = _sarvam.embed_leaves
head_leaves = _sarvam.head_leaves


def leaves(cfg):
    """[(name, shape, init)] in a fixed order; a leaf's index is its key."""
    out = embed_leaves(cfg)
    for i in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, i)
    return out + head_leaves(cfg)


# -- 3. the plain reference ---------------------------------------------------

def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _delta_rule(q, k, v, a, beta):
    """[b, s, H, D] each (beta [b, s, H]) -> o [b, s, H, D]: the
    recurrence a position at a time from a zero state."""
    b, _, heads, head = q.shape

    def position(S, at):
        q_t, k_t, v_t, a_t, b_t = at
        S = S * jnp.exp(a_t)[..., None]
        u = v_t - jnp.sum(S * k_t[..., None], axis=-2)
        S = S + (b_t[..., None] * k_t)[..., None] * u[..., None, :]
        return S, jnp.sum(S * q_t[..., None], axis=-2)

    _, o = jax.lax.scan(position, jnp.zeros((b, heads, head, head), q.dtype),
                        [jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, beta)])
    return jnp.moveaxis(o, 0, 1)


def _kda_mixer(y, w, cfg, mm):
    """KDA over ``y`` [b, s, d]."""
    b, s, _ = y.shape
    heads, head, inner, taps = _kda_dims(cfg)
    # a product each: the columns are independent, and the one
    # [s, 3 inner] float32 result need not exist beside its parts
    cols = jnp.split(w["kda.in_proj.weight"],
                     [inner, 2 * inner, 3 * inner, 3 * inner + head,
                      3 * inner + 2 * head], axis=-1)
    taps_w = jnp.split(w["kda.conv1d.weight"], 3, axis=-1)

    def conv(j):
        past = jnp.pad(mm(y, cols[j]), ((0, 0), (taps - 1, 0), (0, 0)))
        out = sum(past[:, t:t + s] * taps_w[j][t] for t in range(taps))
        return jax.nn.silu(out).reshape(b, s, heads, head)

    q, k, v = _unit(conv(0)) * head ** -0.5, _unit(conv(1)), conv(2)
    a = jax.nn.softplus(mm(mm(y, cols[3]), w["kda.f_proj.weight"])
                        + w["kda.dt_bias"]).reshape(b, s, heads, head)
    a = -jnp.exp(w["kda.A_log"])[:, None] * a
    beta = jax.nn.sigmoid(mm(y, cols[5]))
    o = rms_norm(_delta_rule(q, k, v, a, beta), w["kda.o_norm.weight"],
                 cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(mm(mm(y, cols[4]), w["kda.g_proj.weight"]))
    return mm(o.reshape(b, s, inner) * gate, w["kda.o_proj.weight"])


def _attention(y, w, cfg, mm):
    """Expanded latent attention over ``y`` [b, s, d] with no positions,
    ``HEAD_GROUP`` heads at a time: their queries, and their keys and
    values from the latents, then the queries a block at a time against
    the keys up to the block's end."""
    b, s, _ = y.shape
    heads, nope, rope, vd, rank = _mla_dims(cfg)
    ckr = mm(y, w["self_attn.kv_a_proj_with_mqa.weight"])
    c = rms_norm(ckr[..., :rank], w["self_attn.kv_a_layernorm.weight"],
                 cfg["rms_norm_eps"])
    k_r = ckr[:, :, None, rank:]                              # [b, s, 1, r]
    group = math.gcd(heads, HEAD_GROUP)
    wq = w["self_attn.q_proj.weight"].reshape(
        -1, heads // group, group * (nope + rope))
    wb = w["self_attn.kv_b_proj.weight"].reshape(
        rank, heads // group, group * (nope + vd))
    scale = (nope + rope) ** -0.5

    def heads_of(acc, j):
        qh = mm(y, wq[:, j]).reshape(b, s, group, nope + rope)
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
            acc, out, j * group * vd, axis=2), None

    acc = jax.lax.scan(heads_of, jnp.zeros((b, s, heads * vd), y.dtype),
                       jnp.arange(heads // group))[0]
    return mm(acc, w["self_attn.o_proj.weight"])


def layer(x, w, cfg, i, positions, precision="float32"):
    """Block ``i`` over ``x`` [b, s, d]; ``w`` holds the layer's leaves
    under their names less ``layer_prefix(i)``.  ``positions`` is unused:
    the recurrence and the causal mask carry the order."""
    mm = functools.partial(matmul, precision=precision)
    eps = cfg["rms_norm_eps"]
    y = rms_norm(x, w["input_layernorm.weight"], eps)
    x = x + (_kda_mixer if _kda(cfg, i) else _attention)(y, w, cfg, mm)
    y = rms_norm(x, w["post_attention_layernorm.weight"], eps)
    if _dense(cfg, i):
        return x + mm(jax.nn.silu(mm(y, w["mlp.gate_proj.weight"]))
                      * mm(y, w["mlp.up_proj.weight"]),
                      w["mlp.down_proj.weight"])
    shared = _sarvam._gated(y, w["shared_mlp.input_linear.weight"],
                            w["shared_mlp.output_linear.weight"], mm)
    return x + _sarvam._experts(y, w, _as_sarvam(cfg), mm) + shared


embed = _sarvam.embed
head = _sarvam.head


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

_expert_params = _sarvam._expert_params


def _mixer_params(cfg, i) -> int:
    d = cfg["hidden_size"]
    if _kda(cfg, i):
        heads, head, inner, taps = _kda_dims(cfg)
        return d * (3 * inner + 2 * head + heads) + 2 * head * inner \
            + taps * 3 * inner + inner + heads + head + inner * d
    heads, nope, rope, vd, rank = _mla_dims(cfg)
    return d * heads * (nope + rope) + d * (rank + rope) \
        + rank * heads * (nope + vd) + heads * vd * d + rank


def _dense_params(cfg, i) -> int:
    """A layer's parameters outside its routed experts."""
    d = cfg["hidden_size"]
    if _dense(cfg, i):
        return _mixer_params(cfg, i) + 2 * d + 3 * d * cfg["intermediate_size"]
    return _mixer_params(cfg, i) + 2 * d + (d + 1) * _router_width(cfg) \
        + cfg["num_shared_experts"] * _expert_params(cfg)


def _expert_layers(cfg) -> int:
    return sum(not _dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


def layer_matmul_params(cfg, i=0) -> float:
    """Weights a token is multiplied by in layer ``i``: the dense part
    and its picks' share of the held experts."""
    if _dense(cfg, i):
        return _dense_params(cfg, i)
    picks = cfg["num_experts_per_token"] * cfg["num_experts"] \
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


def kv_bytes_per_token(cfg, itemsize: int = 2, **observed) -> int:
    """What a token leaves in the cache: one stored row a latent layer
    (a KDA layer caches nothing that grows)."""
    return latent_row_stored(cfg) * itemsize * len(_layers(cfg, False))


def state_bytes_per_slot(cfg, itemsize: int = 2) -> int:
    """A request's recurrent state over the KDA layers: the delta rule's
    state in float32 and the convolution's tail in the model's type."""
    heads, head, inner, taps = _kda_dims(cfg)
    return len(_layers(cfg, True)) * (
        heads * head * head * 4 + (taps - 1) * 3 * inner * itemsize)


def _mla_only(cfg):
    """The configuration as sarvam_mla's latent counts read it: they take
    every layer for a latent one, so they are handed the latent layers."""
    return dict(cfg, num_hidden_layers=len(_layers(cfg, False)))


def latent_decode_cost(cfg, cached_tokens: float, itemsize: int = 2):
    return _sarvam.latent_decode_cost(_mla_only(cfg), cached_tokens, itemsize)


def latent_prefill_cost(cfg, start: int, tokens: int) -> float:
    return _sarvam.latent_prefill_cost(_mla_only(cfg), start, tokens)


def ssm_step_bytes(cfg, live_rows: float, itemsize: int = 2) -> float:
    """Bytes the ``ssm`` scope of one decode step must move: the KDA
    layers' weights once, and the live rows' state read and written."""
    return sum(_mixer_params(cfg, i) + cfg["hidden_size"]
               for i in _layers(cfg, True)) * itemsize + \
        2 * live_rows * state_bytes_per_slot(cfg, itemsize)


# the recurrence counted as the recurrence, a state element a position:
# the decay, S'^T k, the update by beta k u^T and the read-out S^T q —
# one multiplication, then three of a multiplication and an addition
_RECURRENCE_OPS = 7


def ssm_scan_cost(cfg, tokens: int, itemsize: int = 2):
    """(operations, bytes) the ``ssm`` scope of one prefill chunk of
    ``tokens`` positions needs at the least: the projections, the
    recurrence counted as the recurrence and the convolution; the weights
    once, one slot's state in and out, the activations in and out."""
    heads, head, inner, taps = _kda_dims(cfg)
    d, kda = cfg["hidden_size"], _layers(cfg, True)
    ops = 2 * d * (3 * inner + 2 * head + heads) + 4 * head * inner \
        + 2 * inner * d + _RECURRENCE_OPS * heads * head * head \
        + 2 * taps * 3 * inner
    moved = (_mixer_params(cfg, kda[0]) + d) * itemsize \
        + 2 * state_bytes_per_slot(cfg, itemsize) / len(kda) \
        + 2 * tokens * d * itemsize
    return len(kda) * tokens * ops, len(kda) * moved


def _recurrence_io(cfg) -> int:
    """float32 values a position hands the recurrence and takes from it,
    a layer: q, k, v and the log-decay in, o out, beta a head."""
    heads, _, inner, _ = _kda_dims(cfg)
    return 5 * inner + heads


def kda_step_bytes(cfg, live_rows: float) -> float:
    """Bytes the ``kda`` scope of one decode step must move: every live
    row's state read and written in float32 and its q, k, v, log-decay
    and beta in and o out, a KDA layer."""
    heads, head, _, _ = _kda_dims(cfg)
    return live_rows * len(_layers(cfg, True)) * 4 * (
        2 * heads * head * head + _recurrence_io(cfg))


def kda_scan_cost(cfg, tokens: int):
    """(operations, bytes) the ``kda`` scope of one prefill chunk of
    ``tokens`` positions needs at the least: the recurrence counted as
    the recurrence; one slot's state in and out, the positions' inputs
    and outputs, all float32."""
    heads, head, _, _ = _kda_dims(cfg)
    layers = len(_layers(cfg, True))
    return (layers * tokens * _RECURRENCE_OPS * heads * head * head,
            layers * 4 * (2 * heads * head * head
                          + tokens * _recurrence_io(cfg)))


def decode_step_bytes(cfg, live_kv_tokens: float, itemsize: int = 2, *,
                      live_rows=None, **observed) -> float:
    """Bytes the traced window's median decode step must move: every
    weight outside the routed experts and the head once (the embedding
    is a gather of the live rows: not counted), the experts that step
    touched (the program's own count, ``window_touched``), the live rows'
    recurrent state read and written, and the live contexts' stored
    latent rows."""
    d = cfg["hidden_size"]
    dense = sum(_dense_params(cfg, i)
                for i in range(cfg["num_hidden_layers"])) + \
        d * cfg["vocab_size"] + d
    touched = (window_touched() or 0.0) * _expert_layers(cfg)
    return (dense + touched * _expert_params(cfg)) * itemsize + \
        2 * (live_rows or 0.0) * state_bytes_per_slot(cfg, itemsize) + \
        live_kv_tokens * kv_bytes_per_token(cfg, itemsize)
