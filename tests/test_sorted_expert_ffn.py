"""The served expert layer's sorted kernel (ISSUE 38): the held experts'
two grouped products as one Pallas call over expert-sorted rows on tile
boundaries (``ops/pallas/grouped_matmul.py: sorted_gated_ffn``), against
the layer's ``lax.ragged_dot`` path and against a dense per-expert sum;
the shape rule that picks one or the other; the tile plan.

Interpret mode on the CPU (conftest pins JAX_PLATFORMS).  The compile for
the chip at the published widths is in ``test_flash_attention_tpu.py``.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.distributed.moe import gated_experts_forward  # noqa: E402
from paddle_tpu.ops.pallas import grouped_matmul as GM  # noqa: E402

E, HELD, D, F = 8, (2, 3, 4, 5), 32, 48
H = len(HELD)
LOCAL = np.full(E, H, np.int32)
LOCAL[list(HELD)] = np.arange(H)

# case: (tokens, top_k, router columns raised so that every token picks
# them, rows that are tokens).  With H = 4 the shape rule's tile is 32
# rows at 80 picks and 16 at 26.
CASES = {
    "spread": (40, 2, (), None),
    "group_larger_than_a_tile": (40, 2, (2,), None),
    "empty_groups": (40, 2, (2, 5), None),           # groups 1, 2 empty
    "every_pick_on_one_expert": (40, 1, (4,), None),
    "one_held_one_elsewhere": (40, 2, (3, 7), None),
    "all_held_elsewhere": (40, 2, (0, 7), None),     # no tile is used
    "padded_tail": (40, 2, (), 29),
    "rows_no_multiple_of_the_tile": (37, 2, (), None),
    "few_rows": (13, 2, (), 11),
}


def _layer(case, dtype):
    T, k, raised, live = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    x = rng.normal(size=(T, D))
    x[:, 0] = 1.0                       # the feature the raised columns read
    router = rng.normal(size=(D, E)) * 0.3
    for e in raised:
        router[0, e] += 40.0
    w_in = rng.normal(size=(H, D, 2 * F)) * 0.2
    w_out = rng.normal(size=(H, F, D)) * 0.2
    valid = None if live is None else jnp.arange(T) < live
    cast = lambda a: jnp.asarray(a, dtype)
    return (cast(x), cast(router), cast(w_in), cast(w_out)), k, valid


def _forward(args, k, valid, path, monkeypatch):
    """The layer with its product forced to ``path``: the rule's own
    threshold moved under or over every shape."""
    monkeypatch.setattr(GM, "_SORTED_MIN_GROUP_ROWS",
                        0 if path == "sorted_kernel" else 1 << 30)
    return gated_experts_forward(*args, top_k=k, local_of=LOCAL,
                                 row_valid=valid)


def _dense(args, k, valid):
    """Every held expert over every token, then the gates: no sort, no
    group.  float32 whatever the operands' type."""
    x, router, w_in, w_out = (jnp.asarray(a, jnp.float32) for a in args)
    topv, topi = jax.lax.top_k(x @ router, k)
    gates = jax.nn.softmax(topv, axis=-1)
    g, u = jnp.split(jnp.einsum("td,hdf->thf", x, w_in), 2, axis=-1)
    y = jnp.einsum("thf,hfd->thd", jax.nn.silu(g) * u, w_out)
    share = jnp.sum(gates[..., None] * jax.nn.one_hot(
        jnp.asarray(LOCAL)[topi], H + 1)[..., :H], axis=1)       # [T, H]
    if valid is not None:
        share = jnp.where(valid[:, None], share, 0.0)
    return jnp.einsum("th,thd->td", share, y)


@pytest.mark.parametrize("case", sorted(CASES))
def test_sorted_kernel_is_the_ragged_path_and_the_dense_sum_float32(
        case, monkeypatch):
    args, k, valid = _layer(case, jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, counts = _forward(args, k, valid, "sorted_kernel", monkeypatch)
        ref, ref_counts = _forward(args, k, valid, "ragged_dot", monkeypatch)
        dense = _dense(args, k, valid)
    assert out.dtype == jnp.float32 and out.shape == args[0].shape
    assert [int(c) for c in counts] == [int(c) for c in ref_counts]
    scale = max(float(jnp.abs(dense).max()), 1e-30)
    assert float(jnp.abs(out - ref).max()) <= 5e-6 * scale
    assert float(jnp.abs(out - dense).max()) <= 5e-6 * scale
    if case == "all_held_elsewhere":
        assert int(counts[1]) == 0 and float(jnp.abs(out).max()) == 0.0
    else:
        assert float(jnp.abs(dense).max()) > 0.1      # the case has rows


@pytest.mark.parametrize("case", sorted(CASES))
def test_sorted_kernel_in_bfloat16_to_blocked_accumulation_noise(
        case, monkeypatch):
    """bf16 operands, float32 accumulation, the activation in float32 and
    one cast before the second product: within bf16's rounding of the
    ragged path (which rounds ``gu`` once more) and of the float32 dense
    sum over the same rounded operands."""
    args, k, valid = _layer(case, jnp.bfloat16)
    out, counts = _forward(args, k, valid, "sorted_kernel", monkeypatch)
    ref, ref_counts = _forward(args, k, valid, "ragged_dot", monkeypatch)
    dense = _dense(args, k, valid)
    assert out.dtype == jnp.float32
    assert [int(c) for c in counts] == [int(c) for c in ref_counts]
    scale = max(float(jnp.abs(dense).max()), 1e-30)
    assert float(jnp.abs(out - ref).max()) <= 2e-2 * scale
    assert float(jnp.abs(out - dense).max()) <= 2e-2 * scale
    # no step is rounded lower than the ragged path's
    assert float(jnp.abs(out - dense).max()) <= \
        1.5 * float(jnp.abs(ref - dense).max()) + 1e-3 * scale


@pytest.mark.parametrize("case", ["padded_tail", "one_held_one_elsewhere",
                                  "all_held_elsewhere", "few_rows"])
def test_rows_of_no_held_expert_come_back_exactly_zero(case, monkeypatch):
    args, k, valid = _layer(case, jnp.float32)
    out, _ = _forward(args, k, valid, "sorted_kernel", monkeypatch)
    topi = jax.lax.top_k(jnp.asarray(args[0] @ args[1]), k)[1]
    here = np.asarray((jnp.asarray(LOCAL)[topi] < H).any(axis=1))
    if valid is not None:
        here = here & np.asarray(valid)
    assert (~here).any() or case == "one_held_one_elsewhere"
    assert float(jnp.abs(out[np.flatnonzero(~here)]).max(initial=0.0)) == 0.0
    if here.any():
        assert float(jnp.abs(out[np.flatnonzero(here)]).min(axis=-1).max()) > 0


def _picks(sizes, T, k, seed):
    """loc [T, k] with ``sizes[e]`` picks of group e, a token picking a
    group at most once, the rest H."""
    H = len(sizes)
    rng = np.random.default_rng(seed)
    loc = np.full((T, k), H, np.int32)
    free = np.full(T, k)
    for e, n in enumerate(sizes):
        tok = rng.choice(np.flatnonzero(free > 0), size=n, replace=False)
        loc[tok, k - free[tok]] = e
        free[tok] -= 1
    return loc


@pytest.mark.parametrize("sizes,T,k,tile", [
    ((71, 0, 130, 5), 256, 2, 128), ((0, 0, 0, 0), 32, 2, 16),
    ((1, 1, 1, 29), 32, 2, 16), ((16, 16, 16, 16), 32, 4, 16),
    ((3, 0, 37, 0), 37, 2, 32), ((40, 40, 40, 40), 40, 4, 32)])
def test_tile_plan_puts_every_group_on_a_tile_boundary(sizes, T, k, tile):
    sizes = np.asarray(sizes, np.int32)
    H = len(sizes)
    loc = _picks(sizes, T, k, seed=int(sizes.sum()))
    te, used, dest = (np.asarray(a) for a in GM.sorted_tile_plan(
        jnp.asarray(loc), jnp.asarray(sizes), tile))
    tiles = -(-sizes // tile)
    assert te.shape[0] == -(-(T * k) // tile) + H - 1
    assert int(used[0]) == tiles.sum() <= te.shape[0]
    assert list(te[:used[0]]) == list(np.repeat(np.arange(H), tiles))
    # a skipped tile names the last used one's expert: no new weight block
    assert (te[used[0]:] == (te[used[0] - 1] if used[0] else te[0])).all()
    assert ((dest >= 0) == (loc < H)).all()
    first = (np.cumsum(tiles) - tiles) * tile
    for e, n in enumerate(sizes):       # a group's picks: its first tile's
        at = dest[loc == e]             # first row on, in token order
        assert list(at) == list(first[e] + np.arange(n))
        assert (te[at // tile] == e).all()


@pytest.mark.parametrize("block_f", [128, 256])
def test_hidden_blocks_fold_into_one_output_tile(block_f):
    """The kernel alone: the hidden width walked in blocks gives what one
    block gives, every pick's row is its token's, and a tile past the
    used count is not computed."""
    rng = np.random.default_rng(3)
    d, f, tile, T, k = 64, 256, 16, 24, 2
    sizes = np.asarray([20, 0, 7], np.int32)
    loc = _picks(sizes, T, k, seed=5)
    te, used, dest = GM.sorted_tile_plan(jnp.asarray(loc),
                                         jnp.asarray(sizes), tile)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(3, d, 2 * f)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(3, f, d)) * 0.1, jnp.float32)
    tok, col = np.nonzero(loc < 3)
    group = loc[tok, col]
    with jax.default_matmul_precision("highest"):
        ys = GM.sorted_gated_ffn(x, dest, w_in, w_out, te, used,
                                 block_rows=tile, block_f=block_f)
        g, u = jnp.split(jnp.einsum("nd,ndf->nf", x[tok], w_in[group]), 2,
                         axis=-1)
        want = jnp.einsum("nf,nfd->nd", jax.nn.silu(g) * u, w_out[group])
    got = ys[np.asarray(dest)[tok, col]]
    assert float(jnp.abs(got - want).max()) <= 5e-6 * float(
        jnp.abs(want).max())
    assert ys.shape == (te.shape[0] * tile, d) and int(used[0]) == 3
    # the rows of a used tile that no pick has are zero rows' product
    assert float(jnp.abs(ys[20:32]).max()) == 0.0


def _path_counts():
    from paddle_tpu.observability import default_registry
    m = default_registry().get("paddle_tpu_grouped_moe_path_total")
    return {"/".join(k): c.value() for k, c in m.series()} if m else {}


@pytest.mark.parametrize("rows,path", [(512, "sorted_kernel"),
                                       (24, "ragged_dot")])
def test_the_shape_rule_sends_a_chunk_to_the_kernel_and_a_step_to_ragged_dot(
        rows, path):
    """No knob: a prefill chunk's rows (every held expert could fill a
    128-row tile) trace the Pallas call, a decode step's the compiler's
    ``ragged_dot`` — read from the path counter and from the jaxpr."""
    k = 2
    blocks = GM.sorted_ffn_blocks(rows, k, H, D, F, jnp.float32)
    assert (blocks is not None) == (path == "sorted_kernel")
    rng = np.random.default_rng(rows)
    args = (jnp.asarray(rng.normal(size=(rows, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(D, E)), jnp.float32),
            jnp.asarray(rng.normal(size=(H, D, 2 * F)) * 0.2, jnp.float32),
            jnp.asarray(rng.normal(size=(H, F, D)) * 0.2, jnp.float32))
    before = _path_counts()
    text = str(jax.make_jaxpr(lambda *a: gated_experts_forward(
        *a, top_k=k, local_of=LOCAL))(*args))
    after = _path_counts()
    other = "ragged_dot" if path == "sorted_kernel" else "sorted_kernel"
    assert after.get(path, 0) == before.get(path, 0) + 1
    assert after.get(other, 0) == before.get(other, 0)
    assert ("pallas_call" in text) == (path == "sorted_kernel")
    assert ("ragged_dot" in text) == (path == "ragged_dot")
    if path == "sorted_kernel":
        assert "sorted_gated_ffn" in text
        assert blocks[0] == 128
        with jax.default_matmul_precision("highest"):
            out, _ = gated_experts_forward(*args, top_k=k, local_of=LOCAL)
            dense = _dense(args, k, None)
        assert float(jnp.abs(out - dense).max()) <= 5e-6 * float(
            jnp.abs(dense).max())


def test_tile_and_hidden_block_come_from_the_shape():
    """serve-rag's chunk: a 128-row tile and a hidden block that divides
    f in lanes, inside the budget; fewer rows an expert, a smaller tile;
    a decode step's rows, and rows that do not fit VMEM whole, are
    ``ragged_dot``'s."""
    bf16 = jnp.bfloat16
    rows, bf = GM.sorted_ffn_blocks(512, 10, 36, 4096, 768, bf16)
    assert rows == 128 and 768 % bf == 0 and bf % 128 == 0
    assert GM.sorted_ffn_vmem_bytes(rows, bf, 512, 10, 4096, 2) \
        <= GM._SORTED_VMEM_BUDGET < GM._SORTED_VMEM_LIMIT
    assert GM.sorted_ffn_blocks(128, 10, 36, 4096, 768, bf16)[0] == 64
    assert GM.sorted_ffn_blocks(24, 10, 36, 4096, 768, bf16) is None
    assert GM.sorted_ffn_blocks(8192, 10, 36, 4096, 768, bf16) is None


def test_static_verification_at_serve_rags_chunk():
    """The catalog holds the kernel at serve-rag's shape and finds it
    clean under the scope it asks for; a hidden block whose tiles do not
    fit that scope is an error, and the rule never offers it."""
    from paddle_tpu.analysis import kernel_verify as kv
    assert GM.verify_static_sorted(512, 10, 36, 4096, 768) == []
    over = GM.verify_static_sorted(512, 10, 36, 4096, 768, block_f=768)
    assert [d.message.split(":")[0] for d in over] == [kv.VMEM_EXCEEDED]
    rows = [r for r in kv.catalog_report()
            if r["kernel"] == "sorted_gated_ffn"]
    assert len(rows) == 1 and rows[0]["verdict"] == "OK"
    assert rows[0]["config"] == "br{} bf{}".format(*GM.sorted_ffn_blocks(
        512, 10, 36, 4096, 768, jnp.bfloat16))


def test_the_engine_prefills_through_the_kernel_and_decodes_by_ragged_dot():
    """The hybrid decoder served at a test's size with a prefill chunk of
    64 tokens (2 picks each over 4 held experts: the rule's 32 rows an
    expert) and 3 decode rows: the chunk's program traces the kernel, the
    step's ``ragged_dot``, and every served token is the float32
    reference's to the tolerance of tests/test_hybrid_serving.py."""
    import test_hybrid_serving as HS
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    arch = HS.common.arch_of(HS.CFG)
    model = arch.build(HS.CFG, HS.SEED, jax.devices()[0])
    leaves = HS.weights.make_all(HS.CFG, HS.SEED, jnp.float32)
    before = _path_counts()
    eng = ContinuousBatchingEngine(model, **dict(
        HS.ENGINE, max_len=160, prefill_chunk=64, prefill_buckets=(64,)))
    prompts = HS._prompts((64, 90, 23), seed=38)   # whole chunks and a tail
    with jax.default_matmul_precision("highest"):
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        res = eng.run()
    after = _path_counts()
    assert after.get("sorted_kernel", 0) > before.get("sorted_kernel", 0)
    assert after.get("ragged_dot", 0) > before.get("ragged_dot", 0)
    for rid, p in zip(rids, prompts):
        assert len(res[rid][1]) == 6
        assert HS._served_gap(arch, leaves, p, res[rid][1]) <= HS.TOL
