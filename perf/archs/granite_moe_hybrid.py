"""Architecture ``granite_moe_hybrid``: IBM Granite 4.0-H
(``model_type`` ``granitemoehybrid``) — a decoder whose layers are Mamba-2
mixers or grouped-query attention without rotary positions, each followed
by a router over sparse gated experts beside a shared gated expert.  The
program runs it as ``paddle_tpu.models.HybridForCausalLM``.

Every layer, with ``r = residual_multiplier``:

    x <- x + r * mixer(rms(x))                       Mamba-2 or attention
    x <- x + r * (experts(rms(x)) + shared(rms(x)))

Embedding output x ``embedding_multiplier``; tied head; logits /
``logits_scaling``; attention scores x ``attention_multiplier``; router:
top-k of the logits, softmax over the chosen.

The chip's share (``model-configs`` guide, section 4): the configuration's
``num_local_experts`` counts the experts held *here*, ids 0 ...
``num_local_experts - 1``; the router keeps the
published width (``published.num_local_experts``) and its
``num_experts_per_tok``; the reference below, like the program, sums the
held experts' parts and leaves the absent ones' out.  ``vocab_size`` is
the slice of the vocabulary held.

An architecture file answers what the kinds, the references and the
metrics ask (perf/archs/gqa_decoder.py's docstring has the list).  The
plain reference is in this file (section 3): float32 under ``highest``
(set by the caller), the recurrence written as the recurrence (a
``lax.scan`` over positions), attention as a masked softmax a head at a
time, the experts as a dense sum over the held ids under the top-k mask;
it imports nothing of the program and shares no chunking with it.  It
serves only: no ``loss`` (the scan has no backward in the program).
"""

from __future__ import annotations

import functools
import os
import statistics

import jax
import jax.numpy as jnp

from perf import common, weights
from perf.reference.decoder import matmul, rms_norm

SCOPES = ("lm_head_ce", "ssm", "attn", "moe", "embed")   # the readers'
# the program's annotation after a decode dispatch, on the profiler's host
# plane: stats ``touched`` (held experts with a row, summed over the
# dispatch's expert layers and steps) and ``layer_steps`` (how many)
COUNTS = "serving.moe_counts"
# the TPU compiler lowers ``lax.ragged_dot`` to a grouped-matmul kernel of
# its own, whose instruction keeps no ``op_name`` path (seen in the compiled
# text: ``metadata={op_name="ragged-dot-none"}``); only the expert layers
# call it, so the readers count it under ``moe`` by its name
KERNEL_SCOPES = {"ragged-dot-none": "moe", "ragged-dot-metadata": "moe"}


def _router_width(cfg):
    return cfg.get("published", {}).get("num_local_experts",
                                        cfg["num_local_experts"])


def _held(cfg):
    return tuple(range(cfg["num_local_experts"]))


def _mamba_dims(cfg):
    heads, p, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"])
    inner = heads * p
    return heads, p, n, inner, inner + 2 * cfg["mamba_n_groups"] * n


# -- 1. the program's model ---------------------------------------------------

def program_config(cfg):
    from paddle_tpu.models import HybridConfig
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        shared_intermediate_size=cfg["shared_intermediate_size"],
        num_local_experts=_router_width(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=_held(cfg),
        mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        mamba_conv_bias=cfg["mamba_conv_bias"],
        embedding_multiplier=cfg["embedding_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        position_embedding_type=cfg["position_embedding_type"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"])


def build(cfg, seed, device):
    """The program's model, constructed without device arrays of its own
    (``LazyGuard``) and then given the seed's weights: the peak is the
    weights and one leaf."""
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

def _dt_bias(key, shape):
    """Inverse softplus of a log-uniform step in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


EMBED_STD = 0.002   # below

INITS = {
    # the tied embedding: 0.002 n, a tenth of the other matrices'.  At
    # 0.02 n the input token's own row, scaled by embedding_multiplier
    # 12 and met again by the tied head, is the largest logit by a factor
    # of three whatever the layers do (|12 e|^2 against 12 e . e'), every
    # served token repeats the last, and the comparison that decides
    # ``correct`` would read 0 from a program with no layers at all
    "embedding": lambda key, shape: EMBED_STD * jax.random.normal(
        key, shape, jnp.float32),
    "a_log": lambda key, shape: jnp.log(
        jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)),
    "dt_bias": _dt_bias,
    "ones": lambda key, shape: jnp.ones(shape, jnp.float32),
    # a depthwise convolution's taps: U(+-1/sqrt(taps)), the framework
    # default the published model was initialised with
    "conv": lambda key, shape: jax.random.uniform(
        key, shape, jnp.float32, -1.0, 1.0) / jnp.sqrt(shape[0] * 1.0),
}


def layer_prefix(i):
    return f"model.layers_{i}."


def layer_kind(cfg, i):
    """``mamba`` or ``attention``: layers of one kind share a compiled
    program in the walking reference."""
    return cfg["layer_types"][i]


def layer_leaves(cfg, i):
    d = cfg["hidden_size"]
    f, fs = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    p = layer_prefix(i)
    out = [(p + "input_layernorm.weight", (d,), "gain")]
    if layer_kind(cfg, i) == "mamba":
        heads, _, _, inner, conv = _mamba_dims(cfg)
        out += [(p + "mamba.in_proj.weight", (d, inner + conv + heads),
                 "matrix"),
                (p + "mamba.conv1d.weight", (cfg["mamba_d_conv"], conv),
                 "conv"),
                (p + "mamba.conv1d.bias", (conv,), "vector"),
                (p + "mamba.dt_bias", (heads,), "dt_bias"),
                (p + "mamba.A_log", (heads,), "a_log"),
                (p + "mamba.D", (heads,), "ones"),
                (p + "mamba.norm.weight", (inner,), "gain"),
                (p + "mamba.out_proj.weight", (inner, d), "matrix")]
    else:
        hd = d // cfg["num_attention_heads"]
        dq = cfg["num_attention_heads"] * hd
        dkv = cfg["num_key_value_heads"] * hd
        out += [(p + "self_attn.q_proj.weight", (d, dq), "matrix"),
                (p + "self_attn.k_proj.weight", (d, dkv), "matrix"),
                (p + "self_attn.v_proj.weight", (d, dkv), "matrix"),
                (p + "self_attn.o_proj.weight", (dq, d), "matrix")]
    held = cfg["num_local_experts"]
    return out + [
        (p + "post_attention_layernorm.weight", (d,), "gain"),
        (p + "block_sparse_moe.router.weight", (d, _router_width(cfg)),
         "matrix"),
        (p + "block_sparse_moe.w_in", (held, d, 2 * f), "matrix"),
        (p + "block_sparse_moe.w_out", (held, f, d), "matrix"),
        (p + "shared_mlp.input_linear.weight", (d, 2 * fs), "matrix"),
        (p + "shared_mlp.output_linear.weight", (fs, d), "matrix")]


def embed_leaves(cfg):
    return [("model.embed_tokens.weight",
             (cfg["vocab_size"], cfg["hidden_size"]), "embedding")]


def head_leaves(cfg):
    """Tied: the head reads the embedding's leaf."""
    return [("model.norm.weight", (cfg["hidden_size"],), "gain"),
            embed_leaves(cfg)[0]]


def leaves(cfg):
    """[(name, shape, init)] in a fixed order; a leaf's index is its key
    (the head's second leaf is the first of this list, so it is listed
    once)."""
    out = embed_leaves(cfg)
    for i in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, i)
    return out + head_leaves(cfg)[:1]


# -- 3. the plain reference ---------------------------------------------------

def embed(w, cfg, ids):
    return w["model.embed_tokens.weight"][ids] * cfg["embedding_multiplier"]


def _mamba(y, w, cfg, mm):
    """The Mamba-2 mixer over ``y`` [b, s, d], the recurrence a position
    at a time from a zero state."""
    b, s, _ = y.shape
    heads, p, n, inner, conv = _mamba_dims(cfg)
    # [z | x B C | dt], a product each: the columns are independent, and
    # the one [s, 16768] float32 result need not exist beside its parts
    z, xbc, dt = (mm(y, cols) for cols in jnp.split(
        w["mamba.in_proj.weight"], [inner, inner + conv], axis=-1))
    taps = cfg["mamba_d_conv"]
    past = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = sum(past[:, k:k + s] * w["mamba.conv1d.weight"][k]
              for k in range(taps))
    if cfg["mamba_conv_bias"]:
        xbc = xbc + w["mamba.conv1d.bias"]
    x, B, C = jnp.split(jax.nn.silu(xbc), [inner, inner + n], axis=-1)
    x = x.reshape(b, s, heads, p)
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])           # [b, s, heads]
    A = -jnp.exp(w["mamba.A_log"])

    def position(h, at):
        x_t, dt_t, B_t, C_t = at
        h = h * jnp.exp(dt_t * A)[..., None, None] + \
            (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, C_t)

    _, ys = jax.lax.scan(position, jnp.zeros((b, heads, p, n), y.dtype),
                         [jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)])
    out = jnp.moveaxis(ys, 0, 1) + w["mamba.D"][:, None] * x
    out = out.reshape(b, s, inner) * jax.nn.silu(z)         # gate, then norm
    out = rms_norm(out, w["mamba.norm.weight"], cfg["rms_norm_eps"])
    return mm(out, w["mamba.out_proj.weight"])


def _attention(y, w, cfg, mm):
    """Causal softmax attention without positions, a query head at a
    time (the [s, s] scores of one head are what has to fit)."""
    b, s, _ = y.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = mm(y, w["self_attn.q_proj.weight"]).reshape(b, s, h, -1)
    k = mm(y, w["self_attn.k_proj.weight"]).reshape(b, s, kv, -1)
    v = mm(y, w["self_attn.v_proj.weight"]).reshape(b, s, kv, -1)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None]

    def head(j):
        sc = jnp.einsum("bqd,bkd->bqk", q[:, :, j], k[:, :, j // (h // kv)])
        sc = jnp.where(mask[None], sc * cfg["attention_multiplier"],
                       -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, -1),
                          v[:, :, j // (h // kv)])

    a = jax.lax.map(head, jnp.arange(h))                    # [h, b, s, hd]
    return mm(jnp.moveaxis(a, 0, 2).reshape(b, s, -1),
              w["self_attn.o_proj.weight"])


def _gated(x, w_in, w_out, mm):
    g, u = jnp.split(mm(x, w_in), 2, axis=-1)
    return mm(jax.nn.silu(g) * u, w_out)


def _experts(y, w, cfg, mm):
    """The held experts' part: each held expert over every token, times
    the weight the router gives it there (0 where it is not among the
    token's top k)."""
    scores = mm(y, w["block_sparse_moe.router.weight"])     # [b, s, E]
    topv, topi = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    gates = jax.nn.softmax(topv, -1)                        # over the chosen
    weight = jnp.sum(jax.nn.one_hot(topi, scores.shape[-1])
                     * gates[..., None], axis=-2)           # [b, s, E]
    held = jnp.asarray(_held(cfg))

    def one(acc, e):
        out = _gated(y, w["block_sparse_moe.w_in"][e],
                     w["block_sparse_moe.w_out"][e], mm)
        return acc + out * weight[..., held[e], None], None

    return jax.lax.scan(one, jnp.zeros_like(y),
                        jnp.arange(len(_held(cfg))))[0]


def layer(x, w, cfg, i, positions, precision="float32"):
    """Block ``i`` over ``x`` [b, s, d]; ``w`` holds the layer's leaves
    under their names less ``layer_prefix(i)``.  ``positions`` is unused:
    the recurrence and the causal mask carry the order."""
    mm = functools.partial(matmul, precision=precision)
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    y = rms_norm(x, w["input_layernorm.weight"], eps)
    mixer = _mamba if layer_kind(cfg, i) == "mamba" else _attention
    x = x + r * mixer(y, w, cfg, mm)
    y = rms_norm(x, w["post_attention_layernorm.weight"], eps)
    shared = _gated(y, w["shared_mlp.input_linear.weight"],
                    w["shared_mlp.output_linear.weight"], mm)
    return x + r * (_experts(y, w, cfg, mm) + shared)


def head(h, w, cfg, precision="float32"):
    """Final norm and the tied head over hidden rows ``h`` [n, d]."""
    h = rms_norm(h, w["model.norm.weight"], cfg["rms_norm_eps"])
    return matmul(h, w["model.embed_tokens.weight"].T, precision) \
        / cfg["logits_scaling"]


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
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _mixer_params(cfg, i) -> int:
    d = cfg["hidden_size"]
    if layer_kind(cfg, i) == "mamba":
        heads, _, _, inner, conv = _mamba_dims(cfg)
        return d * (inner + conv + heads) + inner * d + \
            (cfg["mamba_d_conv"] + 1) * conv + 3 * heads + inner
    hd = d // cfg["num_attention_heads"]
    return 2 * d * cfg["num_attention_heads"] * hd + \
        2 * d * cfg["num_key_value_heads"] * hd


def _dense_params(cfg, i) -> int:
    """A layer's parameters outside its routed experts: the mixer, the
    shared expert, the router and the two norms."""
    d = cfg["hidden_size"]
    return _mixer_params(cfg, i) + 2 * d + d * _router_width(cfg) + \
        3 * d * cfg["shared_intermediate_size"]


def _kinds(cfg, kind):
    return [i for i in range(cfg["num_hidden_layers"])
            if layer_kind(cfg, i) == kind]


def layer_matmul_params(cfg, i=0) -> float:
    """Weights a token is multiplied by in layer ``i``: the dense part
    and its picks' share of the held experts (k x held / width of them
    on average)."""
    picks = cfg["num_experts_per_tok"] * cfg["num_local_experts"] \
        / _router_width(cfg)
    return _dense_params(cfg, i) + picks * _expert_params(cfg)


def matmul_params(cfg) -> float:
    return sum(layer_matmul_params(cfg, i)
               for i in range(cfg["num_hidden_layers"])) + \
        cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg) -> int:
    """Parameters held: what the weights cost in memory (the tied
    embedding once)."""
    d = cfg["hidden_size"]
    return sum(_dense_params(cfg, i) + cfg["num_local_experts"]
               * _expert_params(cfg)
               for i in range(cfg["num_hidden_layers"])) + \
        cfg["vocab_size"] * d + d


def kv_bytes_per_token(cfg, itemsize: int = 2, **observed) -> int:
    """Keys and values a token leaves: the attention layers only."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * hd * itemsize * \
        len(_kinds(cfg, "attention"))


def state_bytes_per_slot(cfg, itemsize: int = 2) -> int:
    """A request's recurrent state over the Mamba layers: the SSM state
    in float32 and the convolution tail in the model's type."""
    heads, p, n, _, conv = _mamba_dims(cfg)
    return len(_kinds(cfg, "mamba")) * (
        heads * p * n * 4 + (cfg["mamba_d_conv"] - 1) * conv * itemsize)


@functools.lru_cache(maxsize=2)
def _dispatch_counts(path, _mtime):
    from perf import trace_reduce
    data = jax.profiler.ProfileData.from_file(path)
    lo, hi, found = float("-inf"), float("inf"), []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == COUNTS:
                    stats = dict(e.stats)
                    found.append((float(e.start_ns), int(stats["touched"]),
                                  int(stats["layer_steps"])))
                elif e.name == trace_reduce.WINDOW_BEGIN:
                    lo = max(lo, float(e.start_ns))
                elif e.name == trace_reduce.WINDOW_END:
                    hi = min(hi, float(e.start_ns))
    return (lo, hi), tuple(sorted(found))


def dispatch_counts():
    """((window begin, end), ((ns, touched, layer_steps), ...)): what
    the program's expert layers counted in each decode dispatch of the
    run's trace, by the time the count reached the host (just after the
    dispatch's execution ended), beside the benchmark's window markers;
    nanoseconds on the trace's clock.  None without a trace or where
    the program writes no such annotation."""
    from perf import program_spans
    path = program_spans.find_xplane()
    got = _dispatch_counts(path, os.path.getmtime(path)) if path else None
    return got if got and got[1] else None


def window_touched():
    """Held experts a layer of the window's median decode dispatch
    touched; None where ``dispatch_counts`` finds nothing."""
    got = dispatch_counts()
    if got is None:
        return None
    (lo, hi), counts = got
    inside = [t / n for at, t, n in counts if lo <= at <= hi and n]
    return statistics.median(inside) if inside else None


def moe_step_bytes(cfg, touched: float, layer_steps: int,
                   itemsize: int = 2) -> float:
    """Bytes the ``moe`` scope must read over ``layer_steps`` expert
    layers of decode steps that touched ``touched`` held experts in sum
    (the program's own count): those experts, and a layer's shared
    expert, router and norm each time."""
    d = cfg["hidden_size"]
    per = d * _router_width(cfg) + 3 * d * cfg["shared_intermediate_size"] \
        + d
    return (layer_steps * per + touched * _expert_params(cfg)) * itemsize


def ssm_step_bytes(cfg, live_rows: float, itemsize: int = 2) -> float:
    """Bytes the ``ssm`` scope of one decode step must move: the Mamba
    layers' weights once, and the live rows' state read and written."""
    return sum(_mixer_params(cfg, i) + cfg["hidden_size"]
               for i in _kinds(cfg, "mamba")) * itemsize + \
        2 * live_rows * state_bytes_per_slot(cfg, itemsize)


def ssm_scan_cost(cfg, tokens: int, itemsize: int = 2):
    """(operations, bytes) the ``ssm`` scope of one prefill chunk of
    ``tokens`` positions needs at the least: the two projections, the
    recurrence counted as the recurrence (decay, outer product, update
    and read-out: 5 operations a state element a position) and the
    convolution; the weights once, one slot's state in and out, the
    activations in and out."""
    heads, p, n, inner, conv = _mamba_dims(cfg)
    d, layers = cfg["hidden_size"], len(_kinds(cfg, "mamba"))
    ops = 2 * d * (inner + conv + heads) + 2 * inner * d + \
        5 * heads * p * n + 2 * cfg["mamba_d_conv"] * conv
    moved = (_mixer_params(cfg, _kinds(cfg, "mamba")[0]) + d) * itemsize \
        + 2 * state_bytes_per_slot(cfg, itemsize) / layers \
        + 2 * tokens * d * itemsize
    return layers * tokens * ops, layers * moved


def decode_step_bytes(cfg, live_kv_tokens: float, itemsize: int = 2, *,
                      live_rows=None, **observed) -> float:
    """Bytes the traced window's median decode step must move: every
    weight outside the routed experts and the head once, the experts
    that step touched (the program's own count of the window's
    dispatches, ``window_touched``: what the window did, not what a row
    count would lead one to expect; none where there is no such count),
    the live rows' recurrent state read and written, and the live
    contexts' keys and values."""
    d = cfg["hidden_size"]
    dense = sum(_dense_params(cfg, i)
                for i in range(cfg["num_hidden_layers"])) + \
        d * cfg["vocab_size"] + d
    touched = (window_touched() or 0.0) * cfg["num_hidden_layers"]
    return (dense + touched * _expert_params(cfg)) * itemsize + \
        2 * (live_rows or 0.0) * state_bytes_per_slot(cfg, itemsize) + \
        live_kv_tokens * kv_bytes_per_token(cfg, itemsize)
