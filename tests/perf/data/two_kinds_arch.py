"""A toy architecture for tests/perf: ``gqa_decoder`` whose odd layers
end in a learned shift of the residual stream, ``x + shift`` — a second
kind of layer, carrying a 1-D leaf that is no norm gain, drawn by an
initialiser of this file's own (``INITS``) and added under a scope of
this file's own (``shift``; its decode count turns on the live rows).
The tests copy this file into perf/archs/ of a scratch checkout; nothing
in perf/ knows it.  It serves only (no ``loss``).
"""

import jax
import jax.numpy as jnp

from perf import common, weights

_base = common.arch_of({})      # gqa_decoder, by the default

layer_prefix = _base.layer_prefix
embed_leaves, head_leaves = _base.embed_leaves, _base.head_leaves
embed, head = _base.embed, _base.head
layer_matmul_params, matmul_params = \
    _base.layer_matmul_params, _base.matmul_params
train_flops_per_token = _base.train_flops_per_token
kv_bytes_per_token = _base.kv_bytes_per_token

# 0.02 log U(1, 16): no normal draw gives it, and weights.py has no such
INITS = {"log_uniform": lambda key, shape: weights.MATRIX_STD * jnp.log(
    jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))}


def build(cfg, seed, device):
    import paddle_tpu as pp
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaDecoderLayer

    class ShiftedLayer(LlamaDecoderLayer):
        def __init__(self, config):
            super().__init__(config)
            self.shift = self.create_parameter([config.hidden_size],
                                               is_bias=True)

        def forward(self, x, *args, **kwargs):
            y = super().forward(x, *args, **kwargs)
            with jax.named_scope("shift"):
                if isinstance(y, tuple):    # (hidden, the layer's cache)
                    return y[0] + self.shift, y[1]
                return y + self.shift

    mcfg = _base.program_config(cfg)
    pp.seed(common.seed_key(seed))
    with jax.default_device(device):
        model = LlamaForCausalLM(mcfg)
        for i in range(1, cfg["num_hidden_layers"], 2):
            layer = ShiftedLayer(mcfg)
            model.model.add_sublayer(f"layers_{i}", layer)
            model.model.layers[i] = layer
        weights.give(model, cfg, seed)
    return model


def layer_kind(cfg, i):
    return "shifted" if i % 2 else "plain"


def layer_leaves(cfg, i):
    out = _base.layer_leaves(cfg, i)
    if i % 2:
        out.append((layer_prefix(i) + "shift", (cfg["hidden_size"],),
                    "log_uniform"))
    return out


def leaves(cfg):
    out = embed_leaves(cfg)
    for i in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, i)
    return out + head_leaves(cfg)


def layer(x, w, cfg, i, positions, precision="float32"):
    y = _base.layer(x, w, cfg, i, positions, precision)
    return y + w["shift"] if i % 2 else y


def total_params(cfg):
    return _base.total_params(cfg) + \
        cfg["num_hidden_layers"] // 2 * cfg["hidden_size"]


def decode_step_bytes(cfg, live_kv_tokens, itemsize=2, *, live_rows,
                      **observed):
    """The base's, and each live row's hidden state read and written
    once by every shifted layer."""
    return _base.decode_step_bytes(cfg, live_kv_tokens, itemsize) + \
        live_rows * 2 * itemsize * \
        cfg["num_hidden_layers"] // 2 * cfg["hidden_size"]
