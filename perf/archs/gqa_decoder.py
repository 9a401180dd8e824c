"""Architecture ``gqa_decoder``: a pre-norm decoder of one layer kind —
RMSNorm, rotary positions, grouped-query causal attention, SwiGLU, untied
head.  The equations of MistralForCausalLM and InternLM2ForCausalLM, which
the program runs as ``LlamaForCausalLM``.  A configuration file without an
``arch`` key names this file.

An architecture file answers what the kinds, the references and the metrics
ask, and nothing else in ``perf/`` knows a model:

1. the program's model: ``build``, and ``partition_specs`` for a mesh;
2. the leaves: ``leaves`` / ``layer_leaves`` — ``[(name, shape, init)]`` in a
   fixed order, a leaf's index in ``leaves(cfg)`` being its random key —
   with ``init`` one of ``perf/weights.py``'s initialisers (``gain``,
   ``matrix``, ``vector``); ``layer_prefix`` and ``layer_kind`` tell the
   walking references which layers share a compiled program;
3. the plain reference (``perf/reference/decoder.py``): ``embed``, ``layer``,
   ``head`` and ``loss``, float32 under ``highest`` (set by the caller),
   importing nothing of the program;
4. the counts: parameters held, parameters a token is multiplied by, and the
   operations and bytes that ``perf/flops.py`` hands to the layer metrics.
   A count may take what the window observed as keywords; this file's
   ignore them.
"""

from __future__ import annotations

from perf import common, weights
from perf.reference import decoder


# -- 1. the program's model ---------------------------------------------------

def program_config(cfg):
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"])


def build(cfg, seed, device):
    """The program's decoder at the configuration's sizes, holding the
    seed's weights (made by the benchmark, in the type they are served
    or trained in) in place of its initialiser's."""
    import jax
    import paddle_tpu as pp
    from paddle_tpu.models import LlamaForCausalLM
    pp.seed(common.seed_key(seed))
    with jax.default_device(device):
        model = LlamaForCausalLM(program_config(cfg))
        weights.give(model, cfg, seed)
    return model


def partition_specs(model, tp_axis, fsdp_axis):
    """{state-dict name: PartitionSpec} for a meshed train cell."""
    from paddle_tpu.models import LlamaForCausalLM
    rules = LlamaForCausalLM.partition_specs(
        model.config, tp_axis=tp_axis, fsdp_axis=fsdp_axis)
    return {n: LlamaForCausalLM.spec_for(n, rules)
            for n in model.state_dict(keep_vars=True)}


# -- 2. the leaves ------------------------------------------------------------

def layer_prefix(i):
    return f"model.layers_{i}."


def layer_kind(cfg, i):
    """Layers of one kind share a compiled program in the walking
    references; ``layer`` depends on ``i`` through its kind alone."""
    return "decoder"


def layer_leaves(cfg, i):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    dq = cfg["num_attention_heads"] * hd
    dkv = cfg["num_key_value_heads"] * hd
    p = layer_prefix(i)
    return [(p + "input_layernorm.weight", (d,), "gain"),
            (p + "self_attn.q_proj.weight", (d, dq), "matrix"),
            (p + "self_attn.k_proj.weight", (d, dkv), "matrix"),
            (p + "self_attn.v_proj.weight", (d, dkv), "matrix"),
            (p + "self_attn.o_proj.weight", (dq, d), "matrix"),
            (p + "post_attention_layernorm.weight", (d,), "gain"),
            (p + "mlp.gate_proj.weight", (d, f), "matrix"),
            (p + "mlp.up_proj.weight", (d, f), "matrix"),
            (p + "mlp.down_proj.weight", (f, d), "matrix")]


def embed_leaves(cfg):
    return [("model.embed_tokens.weight",
             (cfg["vocab_size"], cfg["hidden_size"]), "matrix")]


def head_leaves(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return [("model.norm.weight", (d,), "gain"),
            ("lm_head.weight", (d, v), "matrix")]


def leaves(cfg):
    """[(name, shape, init)] in a fixed order; a leaf's index is its key."""
    out = embed_leaves(cfg)
    for i in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, i)
    return out + head_leaves(cfg)


# -- 3. the plain reference ---------------------------------------------------

def embed(w, cfg, ids):
    return w["model.embed_tokens.weight"][ids]


def layer(x, w, cfg, i, positions, precision="float32"):
    """Block ``i`` over ``x`` [b, s, d]; ``w`` holds the layer's leaves
    under their names less ``layer_prefix(i)``."""
    cos, sin = decoder.rope_tables(cfg, positions)
    return decoder.layer(x, w, cfg, cos, sin, "", precision)


def head(h, w, cfg, precision="float32"):
    """Final norm and head over hidden rows ``h`` [n, d]: logits."""
    h = decoder.rms_norm(h, w["model.norm.weight"], cfg["rms_norm_eps"])
    return decoder.matmul(h, w["lm_head.weight"], precision)


def loss(w, cfg, ids, labels, precision="float32"):
    """Mean next-token cross-entropy over every position; ``w`` holds
    every leaf under its full name."""
    return decoder.loss(w, cfg, ids, labels, precision)


# -- 4. the counts ------------------------------------------------------------
# Minimal-algorithm counts: causal attention counts the lower triangle
# only, recomputation is never counted, and the embedding lookup is a
# gather (no matmul).  So a share of a peak built on them cannot pass 100 %.

def _dims(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, f, h, kv, hd


def layer_matmul_params(cfg, i=0) -> int:
    d, f, h, kv, hd = _dims(cfg)
    return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f


def matmul_params(cfg) -> int:
    """Weights that every token is multiplied by: blocks and the head."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + \
        cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg) -> int:
    """Parameters held: what the weights cost in memory."""
    d = cfg["hidden_size"]
    return matmul_params(cfg) + cfg["vocab_size"] * d + \
        (2 * cfg["num_hidden_layers"] + 1) * d


def train_flops_per_token(cfg, seq: int, **observed) -> float:
    """Forward + backward: 6 per matmul weight, and causal attention's
    QK^T and AV (each 2*(s/2)*h*hd a token forward, times 3)."""
    _, _, h, _, hd = _dims(cfg)
    attn = 3 * 2 * 2 * (seq / 2) * h * hd * cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg, itemsize: int = 2, **observed) -> int:
    _, _, _, kv, hd = _dims(cfg)
    return 2 * kv * hd * itemsize * cfg["num_hidden_layers"]


def decode_step_bytes(cfg, live_kv_tokens: float, itemsize: int = 2,
                      **observed) -> float:
    """Bytes one decode step must read: every block weight and the head
    once, and the keys and values of the live contexts."""
    return matmul_params(cfg) * itemsize + \
        live_kv_tokens * kv_bytes_per_token(cfg, itemsize)
