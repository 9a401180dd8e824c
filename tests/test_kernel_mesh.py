"""ops/pallas/mesh.py — how a Pallas kernel meets a sharded step's mesh:
flash runs per shard of batch and heads under shard_map, every other
kernel is routed to the XLA path by its gate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.nn.functional import attention as A
from paddle_tpu.ops.pallas import mesh as KM


def _mesh():
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))


def _qkv(h=4, hk=2, b=4, s=128, d=128):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (b, s, h, d), jnp.float32) * 0.5,
            jax.random.normal(ks[1], (b, s, hk, d), jnp.float32) * 0.5,
            jax.random.normal(ks[2], (b, s, hk, d), jnp.float32) * 0.5)


def _routes():
    from paddle_tpu.observability import default_registry
    m = default_registry().get("paddle_tpu_kernel_mesh_route_total")
    return {k: c.value() for k, c in m.series()} if m is not None else {}


def test_no_mesh_declared_outside_a_sharded_step():
    assert KM.current() is None
    with KM.step_mesh(None):
        assert KM.current() is None
    with KM.step_mesh(Mesh(np.array(jax.devices()[:1]), ("dp",))):
        assert KM.current() is None      # one device shards nothing
    with KM.step_mesh(_mesh(), ("fsdp",)):
        assert KM.current()[1] == ("fsdp",)
    assert KM.current() is None


@pytest.mark.parametrize("h,hk,head_sharded", [(4, 2, True), (3, 3, False)])
def test_flash_runs_per_shard_of_batch_and_heads(monkeypatch, h, hk,
                                                 head_sharded):
    """With the flash gate on, sdpa inside a sharded step matches the
    dense reference in value and gradient; heads that the mesh does not
    divide stay whole."""
    monkeypatch.setattr(A, "_use_pallas", lambda *a: True)
    mesh = _mesh()
    q, k, v = _qkv(h=h, hk=hk)
    sh = NamedSharding(mesh, P("fsdp", None, "tp" if head_sharded else None,
                               None))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    def sharded(q, k, v):
        with KM.step_mesh(mesh, ("fsdp",)):
            return A.scaled_dot_product_attention(q, k, v, is_causal=True)

    before = _routes().get(("flash", "shard_map"), 0)
    jaxpr = str(jax.make_jaxpr(sharded)(q, k, v))
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr
    assert _routes()[("flash", "shard_map")] == before + 1
    got, ggot = jax.jit(jax.value_and_grad(loss(sharded), (0, 1, 2)))(q, k, v)
    want, gwant = jax.value_and_grad(
        loss(lambda q, k, v: A._sdpa_reference(q, k, v, is_causal=True)),
        (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    for a, b in zip(ggot, gwant):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_weight_kernels_route_to_xla_inside_a_sharded_step(monkeypatch):
    """On a TPU backend the fused-block and fused-CE gates are on — and
    off, by the gate, while a sharded step is traced."""
    from paddle_tpu.ops.pallas import cross_entropy as CE
    from paddle_tpu.ops.pallas import fused_block as FB
    monkeypatch.delenv("PADDLE_TPU_FUSED_BLOCK", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FUSED_CE", raising=False)
    assert FB.fused_block_tier() == "off" and not CE.fused_ce_enabled()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert FB.fused_block_tier() == "fused" and CE.fused_ce_enabled()
    before = _routes()
    with KM.step_mesh(_mesh(), ("fsdp",)):
        assert FB.fused_block_tier() == "off"
        assert not CE.fused_ce_enabled()
    after = _routes()
    for kernel in ("fused_block", "fused_ce"):
        assert after[(kernel, "xla")] == before.get((kernel, "xla"), 0) + 1
    assert FB.fused_block_tier() == "fused"


def test_train_step_declares_its_batch_axes():
    import paddle_tpu as pp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    opt = pp.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    rules = LlamaForCausalLM.partition_specs(cfg, fsdp_axis="fsdp")
    specs = {n: LlamaForCausalLM.spec_for(n, rules)
             for n in model.state_dict(keep_vars=True)}
    seen = []
    orig = TrainStep._step_body

    def spy(self, *a):
        seen.append(KM.current())
        return orig(self, *a)

    step = TrainStep(model, opt, mesh=_mesh(), param_specs=specs,
                     batch_spec=P("fsdp"))
    assert step._batch_axes == ("fsdp",)
    TrainStep._step_body = spy
    try:
        ids = np.zeros((4, 17), np.int32)
        step({"input_ids": ids[:, :-1], "labels": ids[:, 1:]})
    finally:
        TrainStep._step_body = orig
    assert seen and seen[0][0] is step.mesh and seen[0][1] == ("fsdp",)
    assert TrainStep(model, opt)._batch_axes == ()
