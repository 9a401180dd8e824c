"""A toy architecture for tests/perf: ``gqa_decoder`` whose odd layers
end in a learned shift of the residual stream, ``x + shift`` — a second
kind of layer, carrying a 1-D leaf that is no norm gain.  The test copies
this file to perf/archs/two_kinds.py of a scratch checkout; nothing in
perf/ knows it.  It serves only (no ``loss``).
"""

from perf import common, weights

_base = common.arch_of({})      # gqa_decoder, by the default

layer_prefix = _base.layer_prefix
embed_leaves, head_leaves = _base.embed_leaves, _base.head_leaves
embed, head = _base.embed, _base.head
layer_matmul_params, matmul_params = \
    _base.layer_matmul_params, _base.matmul_params
train_flops_per_token = _base.train_flops_per_token
kv_bytes_per_token = _base.kv_bytes_per_token
decode_step_bytes = _base.decode_step_bytes


def build(cfg, seed, device):
    import jax
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
            if isinstance(y, tuple):        # (hidden, the layer's cache)
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
                    "vector"))
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
