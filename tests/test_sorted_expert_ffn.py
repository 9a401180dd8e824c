"""The served expert layer's sorted kernel (ISSUE 38, 48): the held
experts' two grouped products, the gates and the sum over a token's
picks as one Pallas call over expert-sorted rows on tile boundaries
(``ops/pallas/grouped_matmul.py: sorted_gated_ffn``), against the layer's
``lax.ragged_dot`` path, against a dense per-expert sum and against the
un-fused form (padded rows, ``ys[dest]``, ``einsum`` under the gates:
kept here as the reference); the shape rule that picks one or the other;
the tile plan.

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
    gates = np.random.default_rng(T).uniform(0.1, 1.0, (T, k)).astype(
        np.float32)
    te, used, dest, src, row_gate = (np.asarray(a) for a in
                                     GM.sorted_tile_plan(
        jnp.asarray(loc), jnp.asarray(sizes), tile, jnp.asarray(gates)))
    tiles = -(-sizes // tile)
    assert te.shape[0] == -(-(T * k) // tile) + H - 1
    assert src.shape == row_gate.shape == (te.shape[0] * tile,)
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
    # a padded row knows its token and its gate; a row no pick has, neither
    tok, col = np.nonzero(loc < H)
    assert (src[dest[tok, col]] == tok).all()
    assert (row_gate[dest[tok, col]] == gates[tok, col]).all()
    assert (src >= 0).sum() == sizes.sum()
    assert (src[used[0] * tile:] == -1).all()
    assert (row_gate[src < 0] == 0.0).all()


def _unfused(x, loc, gates, w_in, w_out, te, dest, tile):
    """What the layer did before the kernel combined, in plain jnp: the
    padded rows (each tile its expert's product over its rows, float32
    out), ``ys[dest]`` back to the picks, the sum under the gates.  Also
    returns the padded rows."""
    T, k = loc.shape
    H = w_in.shape[0]
    tok, col = np.nonzero(np.asarray(loc) < H)
    at = np.asarray(dest)[tok, col]
    xp = jnp.zeros((te.shape[0] * tile, x.shape[1]), x.dtype).at[at].set(
        x[tok])
    e = jnp.repeat(jnp.asarray(te), tile)
    g, u = jnp.split(jnp.einsum("nd,ndf->nf", xp, w_in[e],
                                preferred_element_type=jnp.float32), 2,
                     axis=-1)
    ys = jnp.einsum("nf,nfd->nd", (jax.nn.silu(g) * u).astype(x.dtype),
                    w_out[e], preferred_element_type=jnp.float32)
    picked = jnp.where((dest >= 0)[..., None], ys[jnp.maximum(dest, 0)], 0.0)
    return jnp.einsum("tk,tkd->td", gates, picked), ys


# the four expert cells' expert layers at toy widths: (tokens, top k,
# routed, held, tile rows, rows that are tokens).  The tile is the one the
# rule gives the cell's chunk; held / routed is the cell's (a half, a
# quarter, an eighth, a sixteenth of the picks land here); d 128, f 256
# in two hidden blocks; column 1 raised so that eight tokens in nine pick
# expert 1: a group larger than a tile, and tokens with picks in several
# tiles; the ninth's picks may all land elsewhere.
CELLS = {
    "serve-rag": (160, 4, 8, 4, 128, 149),
    "serve-reason": (96, 4, 16, 4, 64, 96),
    "serve-longctx": (160, 4, 16, 2, 128, 155),
    "serve-mixed": (192, 2, 32, 2, 128, 192),
}


def _cell(name, dtype):
    T, k, routed, held, tile, live = CELLS[name]
    d, f = 128, 256
    rng = np.random.default_rng(sorted(CELLS).index(name))
    x = rng.normal(size=(T, d))
    x[:, 0] = 1.0
    x[::9, 0] = 0.0
    router = rng.normal(size=(d, routed)) * 0.3
    router[0, 1] += 40.0
    logits = jnp.asarray(x @ router, jnp.float32)
    topv, topi = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(topv, axis=-1)
    loc = jnp.where(topi < held, topi, held).astype(jnp.int32)
    loc = jnp.where((jnp.arange(T) < live)[:, None], loc, held)
    sizes = jnp.sum(loc.reshape(-1)[:, None] == jnp.arange(held)[None],
                    axis=0, dtype=jnp.int32)
    w_in = jnp.asarray(rng.normal(size=(held, d, 2 * f)) * 0.2, dtype)
    w_out = jnp.asarray(rng.normal(size=(held, f, d)) * 0.2, dtype)
    return jnp.asarray(x, dtype), loc, sizes, gates, w_in, w_out, tile


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_kernel_gives_the_unfused_forms_sum_at_each_cells_shape(name, dtype):
    """The kernel's ``[T, d]`` is the padded rows gathered back and summed
    under the gates: to float32 round-off of a reordered k-term sum in
    float32, to blocked-accumulation noise (two hidden blocks folded in
    float32 against one product) in bf16.  A padded tail row and a token
    whose picks all land elsewhere read exactly zero."""
    x, loc, sizes, gates, w_in, w_out, tile = _cell(name, jnp.dtype(dtype))
    held = w_in.shape[0]
    te, used, dest, src, row_gate = GM.sorted_tile_plan(loc, sizes, tile,
                                                        gates)
    with jax.default_matmul_precision("highest"):
        out = GM.sorted_gated_ffn(x, dest, src, row_gate, w_in, w_out, te,
                                  used, block_rows=tile, block_f=128)
        want, _ = _unfused(x, loc, gates, w_in, w_out, te, dest, tile)
    assert out.shape == want.shape and out.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert scale > 0.1
    tol = 2e-6 if dtype == "float32" else 1e-3
    assert float(jnp.abs(out - want).max()) <= tol * scale
    here = np.asarray((loc < held).any(axis=1))
    assert (~here).any() and here.any()
    assert float(jnp.abs(out[np.flatnonzero(~here)]).max()) == 0.0
    # picks land elsewhere, a group is larger than a tile and some
    # token's picks are in different tiles
    assert 0 < int(sizes.sum()) < int((jnp.arange(x.shape[0])
                                       < CELLS[name][5]).sum()) * loc.shape[1]
    assert int(sizes.max()) > tile
    spread = np.asarray(jnp.where(dest >= 0, dest // tile, -1))
    assert any(len(set(r[r >= 0])) > 1 for r in spread)


def test_tiles_past_the_used_count_do_nothing():
    """Told one tile fewer than the plan used, the kernel leaves that
    tile's rows out of the sum and touches nothing else: a step past
    ``num_used`` computes nothing and adds nothing."""
    x, loc, sizes, gates, w_in, w_out, tile = _cell("serve-rag",
                                                    jnp.float32)
    te, used, dest, src, row_gate = GM.sorted_tile_plan(loc, sizes, tile,
                                                        gates)
    assert int(used[0]) >= 3 and te.shape[0] > int(used[0])
    with jax.default_matmul_precision("highest"):
        out = GM.sorted_gated_ffn(x, dest, src, row_gate, w_in, w_out, te,
                                  used - 1, block_rows=tile, block_f=128)
        last = dest // tile == used[0] - 1
        want, _ = _unfused(x, loc, jnp.where(last, 0.0, gates), w_in, w_out,
                           te, dest, tile)
    assert bool(last.any())
    assert float(jnp.abs(out - want).max()) <= 2e-6 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("block_f", [128, 256])
def test_hidden_blocks_fold_into_one_output_tile(block_f):
    """The kernel alone: the hidden width walked in blocks gives what one
    block gives, every pick's row is its token's and carries its gate,
    and a token's picks in different groups add up."""
    rng = np.random.default_rng(3)
    d, f, tile, T, k = 64, 256, 16, 24, 2
    sizes = np.asarray([20, 0, 7], np.int32)
    loc = _picks(sizes, T, k, seed=5)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, (T, k)), jnp.float32)
    te, used, dest, src, row_gate = GM.sorted_tile_plan(
        jnp.asarray(loc), jnp.asarray(sizes), tile, gates)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(3, d, 2 * f)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(3, f, d)) * 0.1, jnp.float32)
    tok, col = np.nonzero(loc < 3)
    group = loc[tok, col]
    with jax.default_matmul_precision("highest"):
        out = GM.sorted_gated_ffn(x, dest, src, row_gate, w_in, w_out, te,
                                  used, block_rows=tile, block_f=block_f)
        g, u = jnp.split(jnp.einsum("nd,ndf->nf", x[tok], w_in[group]), 2,
                         axis=-1)
        y = jnp.einsum("nf,nfd->nd", jax.nn.silu(g) * u, w_out[group])
        want = jnp.zeros((T, d), jnp.float32).at[tok].add(
            gates[tok, col][:, None] * y)
        unfused, ys = _unfused(x, jnp.asarray(loc), gates, w_in, w_out, te,
                               dest, tile)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(out - want).max()) <= 5e-6 * scale
    assert float(jnp.abs(out - unfused).max()) <= 5e-6 * scale
    assert out.shape == (T, d) and int(used[0]) == 3
    # the reference's padded rows: a used tile's rows that no pick has
    # are zero rows' product
    assert ys.shape == (te.shape[0] * tile, d)
    assert float(jnp.abs(ys[20:32]).max()) == 0.0
    assert len(set(tok)) < len(tok)           # a token with two held picks


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
    assert text.count("pallas_call") == (1 if path == "sorted_kernel" else 0)
    assert text.count("= ragged_dot") == (2 if path == "ragged_dot" else 0)
    # the picks' rows, float32 [T, k, d]: the decode step's un-sort and
    # sum have them, the chunk's path holds none (nor the padded rows)
    assert (f"f32[{rows},{k},{D}]" in text) == (path == "ragged_dot")
    if path == "sorted_kernel":
        assert text.count("sorted_gated_ffn") == 1
        assert blocks[0] == 128
        tiles = -(-(rows * k) // 128) + H - 1
        assert f"f32[{tiles * 128},{D}]" not in text
        with jax.default_matmul_precision("highest"):
            out, _ = gated_experts_forward(*args, top_k=k, local_of=LOCAL)
            dense = _dense(args, k, None)
        assert float(jnp.abs(out - dense).max()) <= 5e-6 * float(
            jnp.abs(dense).max())


# (tokens, top k, held, d, f) of a prefill chunk's expert layer in the four
# cells that hold experts (perf/configs/, perf/traffic/: chunk 512)
PUBLISHED = {
    "serve-rag": (512, 10, 36, 4096, 768),
    "serve-longctx": (512, 8, 16, 4096, 2048),
    "serve-reason": (512, 8, 64, 2304, 1024),
    "serve-mixed": (512, 4, 16, 3072, 3072),
}


@pytest.mark.parametrize("cell", sorted(PUBLISHED))
def test_tile_and_hidden_block_come_from_the_shape(cell):
    """A cell's chunk: the MXU's 128-row tile (64 where a held expert's
    mean rows are fewer) and a hidden block that divides f in lanes, what
    the call holds inside the budget with the float32 output resident;
    fewer rows an expert, a smaller tile; a decode step's rows, and a
    step whose resident output and rows do not fit VMEM, are
    ``ragged_dot``'s."""
    bf16 = jnp.bfloat16
    T, k, H_, d, f = PUBLISHED[cell]
    rows, bf = GM.sorted_ffn_blocks(T, k, H_, d, f, bf16)
    assert rows == (64 if cell == "serve-reason" else 128)
    assert f % bf == 0 and bf % 128 == 0
    held = GM.sorted_ffn_vmem_bytes(rows, bf, T, k, d, 2)
    assert T * d * 4 < held <= GM._SORTED_VMEM_BUDGET < GM._SORTED_VMEM_LIMIT
    fewer = GM.sorted_ffn_blocks(128, k, H_, d, f, bf16)
    assert fewer is None or fewer[0] < rows
    assert GM.sorted_ffn_blocks(24, k, H_, d, f, bf16) is None
    for big in (2048, 8192):
        # the resident output and the rows leave no room for a weight tile
        assert GM.sorted_ffn_vmem_bytes(128, 128, big, k, d, 2) \
            > GM._SORTED_VMEM_BUDGET
        assert GM.sorted_ffn_blocks(big, k, H_, d, f, bf16) is None


@pytest.mark.parametrize("cell", sorted(PUBLISHED))
def test_static_verification_at_each_cells_chunk(cell):
    """The catalog holds the kernel at each cell's shape and finds it
    clean under the scope it asks for (the resident output, the gates'
    scalar operands and both scratch tiles in the count); a hidden block
    whose tiles do not fit that scope is an error, and the rule never
    offers it."""
    from paddle_tpu.analysis import kernel_verify as kv
    T, k, H_, d, f = PUBLISHED[cell]
    assert GM.verify_static_sorted(T, k, H_, d, f) == []
    over = GM.verify_static_sorted(T, k, H_, d, f, block_f=f)
    assert [d_.message.split(":")[0] for d_ in over] == [kv.VMEM_EXCEEDED]
    rows = [r for r in kv.catalog_report()
            if r["kernel"] == "sorted_gated_ffn"
            and r["shape"].startswith(f"t{T} k{k} h{H_} d{d} f{f} ")]
    assert len(rows) == 1 and rows[0]["verdict"] == "OK"
    assert rows[0]["config"] == "br{} bf{}".format(*GM.sorted_ffn_blocks(
        T, k, H_, d, f, jnp.bfloat16))


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
