"""Grouped expert-matmul — all E experts' FFNs in one Pallas call.

The MoE tentpole (ROADMAP item 1): every dispatch path in
``distributed/moe.py`` funnels expert compute through a stacked-weight
FFN over ``[G, C, d]`` capacity-grouped token blocks (G groups, each
bound to expert ``g // (G // E)``; the einsum/index paths have G == E,
the all_to_all paths G == E_loc * n_shards source chunks).  Upstream
Paddle loops experts through gather/scatter collectives; the dense
einsum pair here already beats that, but it still spends full
``[E, C, d]`` HBM traffic on padding rows and re-reads activations
between the up- and down-projection.  This kernel runs the whole
grouped FFN as ONE ``pallas_call``:

* grid ``(G, C/block_c, h/block_f)`` with the hidden (f) axis innermost
  — only a ``[block_c, block_f]`` tile of the hidden activations ever
  exists, folded into an fp32 VMEM accumulator (the fused-MLP
  discipline, fused_block.py);
* per-group valid-row counts ride along as a ``[G, 1, 1]`` int32
  operand and ``pl.when`` skips capacity blocks with no routed tokens —
  under GShard capacity factors most tail blocks are empty, so skipped
  blocks cost neither MXU flops nor the w1/w2 HBM reads their grid
  steps would re-issue;
* rows past a group's count are zeroed (their combine weights are zero
  in every dispatch path, so MoE outputs are unchanged), which makes
  the kernel's semantics block-size independent and gives the jnp
  reference an exact contract to oracle against;
* custom VJP: backward is the plain-JAX masked einsum chain, left to
  XLA, with a ``float0`` cotangent for counts.

Beside it, ``sorted_gated_ffn``: the served (dropless, gated, bias-free)
expert layer's two products over expert-sorted rows and their sum under
the gates at a prefill chunk's row count, chosen by ``sorted_ffn_blocks``
from the static shapes alone (its own section below).

Routing of the capacity-grouped kernel is trace-time and OFF by
default: ``PADDLE_TPU_GROUPED_MOE=1`` flips ``_expert_ffn`` to it
(interpret mode off-TPU); unset or 0 keeps the dense einsum pair with a
byte-identical jaxpr (regression-tested).  Block sizes are one more
autotune-v2 axis (``autotune.grouped_block_sizes``) and the static
Mosaic-legality specs are in the kernel-verify catalog via
:func:`verify_static` and :func:`verify_static_sorted`.
"""

from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_expert_ffn", "grouped_expert_ffn_pallas",
           "grouped_expert_ffn_reference", "grouped_moe_enabled",
           "grouped_ffn_eligible", "record_path", "sorted_gated_ffn",
           "sorted_tile_plan", "sorted_ffn_blocks"]


def grouped_moe_enabled() -> bool:
    """``PADDLE_TPU_GROUPED_MOE=1`` routes stacked-expert FFNs through
    the grouped Pallas kernel; unset/0 keeps the dense einsum pair (and
    its exact jaxpr)."""
    raw = os.environ.get("PADDLE_TPU_GROUPED_MOE")
    return raw is not None and raw.strip().lower() in ("1", "true", "yes",
                                                       "on")


def grouped_ffn_eligible(G: int, C: int, d: int, h: int, E: int) -> bool:
    """Structural + (on TPU) alignment gate for the grouped kernel.
    Off-TPU the kernel runs in interpret mode, where Mosaic tiling does
    not constrain shapes."""
    if E <= 0 or G % E:
        return False
    if jax.default_backend() != "tpu":
        return True
    return d % 128 == 0 and h % 128 == 0 and C >= 8


def record_path(path: str):
    """Trace-time implementation counter — the grouped-MoE analog of the
    quant/fused-block path counters."""
    try:
        from paddle_tpu.observability import default_registry
        default_registry().counter(
            "paddle_tpu_grouped_moe_path_total",
            "grouped expert-FFN implementation chosen at trace time",
            labelnames=("path",)).labels(path=path).inc()
    except Exception:  # pragma: no cover - telemetry must never trace-fail
        pass


def _default_grouped_blocks(C: int, d: int, h: int, dtype):
    """Heuristic (block_c, block_f) when the autotune cache is cold:
    widest hidden block, then the tallest capacity block whose working
    set (x/y/acc + double-buffered w1/w2 tiles) stays under ~10 MB of
    VMEM.  Degenerate dims fall back to spanning blocks (always
    Mosaic-legal: a block equal to the array dim needs no tiling)."""
    s = str(dtype)
    itemsize = 2 if ("bfloat16" in s or "float16" in s) else 4
    quantum = 16 if itemsize == 2 else 8
    bcs = [c for c in (512, 256, 128, 64, 32, 16, 8)
           if c % quantum == 0 and C % c == 0 and C >= c]
    if not bcs:
        bcs = [C]                       # spanning block — no sublane tiling
    bfs = [f for f in (512, 256, 128) if h % f == 0]
    if not bfs:
        bfs = [h]
    for bf in bfs:
        for bc in bcs:
            vmem = (2 * bc * d * itemsize        # x, double-buffered
                    + bc * d * 4                 # fp32 accumulator
                    + 2 * bc * d * itemsize      # y, double-buffered
                    + 4 * d * bf * itemsize)     # w1 + w2 tiles, 2x
            if vmem < 10 * (1 << 20):
                return bc, bf
    return bcs[-1], bfs[-1]


def _grouped_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, cnt_ref, o_ref,
                    acc_ref, *, act, block_c):
    """One (group, capacity, hidden) tile.  The hidden axis is the
    innermost (sequential) grid dim; the fp32 accumulator in VMEM folds
    each ``[block_c, block_f]`` hidden tile into the down-projection.
    Capacity blocks past the group's routed-token count are skipped
    entirely (no MXU work, zeros written at finalize)."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    nf = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    cnt = cnt_ref[0, 0, 0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_c, 1), 0) \
        + i * block_c
    valid = rows < cnt

    @pl.when(i * block_c < cnt)
    def _compute():
        xb = x_ref[0]                                    # [bc, d]
        u = jax.lax.dot_general(
            xb, w1_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bc, bf]
        u = u + b1_ref[0].astype(jnp.float32)
        hb = jnp.where(valid, act(u), 0.0)               # mask pad rows
        acc_ref[:] += jax.lax.dot_general(
            hb.astype(x_ref.dtype), w2_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bc, d]

    @pl.when(j == nf - 1)
    def _finalize():
        out = acc_ref[:] + b2_ref[0].astype(jnp.float32)
        o_ref[0] = jnp.where(valid, out, 0.0).astype(o_ref.dtype)


def grouped_expert_ffn_pallas(x, w1, b1, w2, b2, counts, *, act,
                              block_c, block_f, interpret):
    """``[G, C, d] -> [G, C, d]`` grouped FFN via the Pallas kernel.
    ``counts [G]`` int32 bounds each group's valid-row prefix; rows past
    it come back exactly zero."""
    G, C, d = x.shape
    E, _, h = w1.shape
    rep = G // E
    nc = C // block_c
    nf = h // block_f

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_grouped_kernel, act=act, block_c=block_c),
        grid=(G, nc, nf),
        in_specs=[
            pl.BlockSpec((1, block_c, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, d, block_f), lambda g, i, j: (g // rep, 0, j)),
            pl.BlockSpec((1, 1, block_f), lambda g, i, j: (g // rep, 0, j)),
            pl.BlockSpec((1, block_f, d), lambda g, i, j: (g // rep, j, 0)),
            pl.BlockSpec((1, 1, d), lambda g, i, j: (g // rep, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, i, j: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((G, C, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, d), jnp.float32)],
        name="grouped_matmul",
        interpret=interpret,
        **params,
    )(x, w1, b1.reshape(E, 1, h), w2, b2.reshape(E, 1, d),
      counts.reshape(G, 1, 1))


def grouped_expert_ffn_reference(x, w1, b1, w2, b2, counts=None, *,
                                 act=None):
    """The jnp oracle: same op order as the kernel (fp32 MXU
    accumulation, activation in fp32, one cast between the projections)
    with rows past ``counts`` zeroed — block-size independent, so the
    kernel must match it to blocked-accumulation noise."""
    act = act or jax.nn.gelu
    G, C, d = x.shape
    E, _, h = w1.shape
    rep = G // E
    xr = x.reshape(E, rep * C, d)
    u = jnp.einsum("ecd,edh->ech", xr, w1,
                   preferred_element_type=jnp.float32) + b1[:, None, :]
    hb = act(u).astype(x.dtype)
    y = jnp.einsum("ech,ehd->ecd", hb, w2,
                   preferred_element_type=jnp.float32) + b2[:, None, :]
    y = y.astype(x.dtype).reshape(G, C, d)
    if counts is not None:
        rows = jax.lax.broadcasted_iota(jnp.int32, (G, C), 1)
        y = jnp.where((rows < counts[:, None])[..., None], y, 0)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _grouped_core(x, w1, b1, w2, b2, counts, act, block_c, block_f,
                  interpret):
    return _grouped_fwd(x, w1, b1, w2, b2, counts, act, block_c, block_f,
                        interpret)[0]


def _grouped_fwd(x, w1, b1, w2, b2, counts, act, block_c, block_f,
                 interpret):
    y = grouped_expert_ffn_pallas(x, w1, b1, w2, b2, counts, act=act,
                                  block_c=block_c, block_f=block_f,
                                  interpret=interpret)
    return y, (x, w1, b1, w2, b2, counts)


def _grouped_bwd(act, block_c, block_f, interpret, res, dy):
    # recompute the masked einsum chain in plain JAX: rows past counts
    # carry zero cotangent and zero input, so padded slots contribute
    # nothing to any grad
    x, w1, b1, w2, b2, counts = res
    G, C, d = x.shape
    E = w1.shape[0]
    rep = G // E
    rows = jax.lax.broadcasted_iota(jnp.int32, (G, C), 1)
    valid = (rows < counts[:, None])[..., None]
    xm = jnp.where(valid, x, 0).reshape(E, rep * C, d)
    gy = jnp.where(valid, dy, 0).reshape(E, rep * C, d)
    u = jnp.einsum("ecd,edh->ech", xm, w1,
                   preferred_element_type=jnp.float32) + b1[:, None, :]
    s, act_vjp = jax.vjp(act, u)
    dh = jnp.einsum("ecd,ehd->ech", gy, w2,
                    preferred_element_type=jnp.float32)
    dw2 = jnp.einsum("ech,ecd->ehd", s.astype(x.dtype), gy,
                     preferred_element_type=jnp.float32).astype(w2.dtype)
    db2 = gy.astype(jnp.float32).sum(axis=1).astype(b2.dtype)
    du = act_vjp(dh)[0]
    dw1 = jnp.einsum("ecd,ech->edh", xm, du.astype(x.dtype),
                     preferred_element_type=jnp.float32).astype(w1.dtype)
    db1 = du.sum(axis=1).astype(b1.dtype)
    dx = jnp.einsum("ech,edh->ecd", du.astype(x.dtype), w1,
                    preferred_element_type=jnp.float32)
    dx = dx.reshape(G, C, d).astype(x.dtype)
    dcounts = np.zeros(counts.shape, dtype=jax.dtypes.float0)
    return dx, dw1, db1, dw2, db2, dcounts


_grouped_core.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_expert_ffn(x, w1, b1, w2, b2, *, counts=None, act=None,
                       block_c=None, block_f=None, interpret=None,
                       autotune=True):
    """Grouped expert FFN with trace-time block selection.

    ``x``: ``[G, C, d]`` capacity-grouped tokens (group ``g`` belongs
    to expert ``g // (G // E)``); ``w1/b1/w2/b2``: stacked
    ``[E, d, h] / [E, h] / [E, h, d] / [E, d]`` expert weights;
    ``counts``: optional ``[G]`` int32 valid-row prefix per group (rows
    past it return exactly zero — their combine weights are zero in
    every MoE dispatch path).  Differentiable in x and the weights.
    """
    act = act or jax.nn.gelu
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    G, C, d = x.shape
    E, _, h = w1.shape
    if G % E:
        raise ValueError(f"group count {G} not divisible by experts {E}")
    if block_c is None or block_f is None:
        if autotune and not interpret:
            from paddle_tpu.ops.pallas.autotune import grouped_block_sizes
            bc, bf = grouped_block_sizes(G, C, d, h, str(x.dtype))
        else:
            bc, bf = _default_grouped_blocks(C, d, h, str(x.dtype))
        block_c = block_c or bc
        block_f = block_f or bf
    if C % block_c or h % block_f:
        block_c, block_f = _default_grouped_blocks(C, d, h, str(x.dtype))
    if counts is None:
        counts = jnp.full((G,), C, jnp.int32)
    return _grouped_core(x, w1, b1, w2, b2, counts.astype(jnp.int32),
                         act, int(block_c), int(block_f), bool(interpret))


# ---------------------------------------------------------------------------
# the served expert layer's product: gated experts over expert-sorted rows
#
# ``distributed/moe.py: gated_experts_forward`` routes a step's tokens over
# the held experts.  At a prefill chunk's rows a group is about a hundred
# rows, and the compiler's own lowering of ``lax.ragged_dot`` multiplies a
# 512-row tile for each.  Here every group starts on a tile boundary of a
# padded row buffer, a tile belongs to one expert (the tile -> expert map
# is scalar prefetch), and both products run in one call with the hidden
# tile in VMEM.  The padded buffer is never in HBM, on the way in or out:
# the chunk's rows stay whole in VMEM and a tile takes its own by a
# one-hot product (exact: one term a row), so no sorted copy of the rows
# is made or read; and the call's output is the step's ``[T, d]`` float32,
# resident in VMEM over the grid, into which each tile's rows are added
# under their gates (a padded row's token and gate are scalar prefetch
# too), so no padded result is written, gathered back to the picks or
# summed in HBM.

_SORTED_TILE_ROWS = 128       # the MXU's height: the most rows a tile has
# A held expert's mean rows from which the kernel is taken.  Measured on
# the chip at serve-rag's widths (benchmarks/served_experts_bench.py,
# PERF.md section 6, PR 38): the kernel wins at 142 / 71 / 36 rows an
# expert (512 / 256 / 128 tokens); at a decode step's 6.7 the two are
# within a tenth and the step keeps the compiler's product.
_SORTED_MIN_GROUP_ROWS = 32
# The compiler's own scope is 16 MiB of a v5e core's 128: the kernel asks
# for this much, and the buffers it holds take no more than the budget.
# The rest is the compiler's own (a tile's float32 product before it is
# folded: under 2 MiB at 128 rows x d 4096, compiled for a v5e).
_SORTED_VMEM_LIMIT = 32 * (1 << 20)
_SORTED_VMEM_BUDGET = 28 * (1 << 20)


def sorted_ffn_vmem_bytes(block_rows, block_f, T, top_k, d, itemsize):
    """What the call holds: the streamed weight tiles twice; once each
    (a whole array at a constant index has one buffer) the float32
    output, resident over the grid, the step's rows and their places;
    the gathered tile and its float32 rows."""
    return (2 * 3 * d * block_f * itemsize         # gate, up, down
            + T * d * 4 + T * d * itemsize + top_k * T * 4
            + block_rows * d * (itemsize + 4))


def sorted_ffn_blocks(T: int, top_k: int, H: int, d: int, f: int, dtype):
    """The one place that says which product the served expert layer
    runs: ``(block_rows, block_f)`` of the sorted kernel, or None for
    ``lax.ragged_dot``.

    The kernel where the step's picks, all landing here, would give a
    held expert ``_SORTED_MIN_GROUP_ROWS`` rows or more (a prefill chunk:
    512 tokens x 10 picks over 36 experts is 142) and the step's float32
    output and its rows fit in VMEM beside the weight tiles;
    ``ragged_dot`` below that (a decode step: 24 x 10 over 36 is 7, which
    the compiler tiles by 16) and above it (thousands of rows a group
    fill the compiler's 512-row tile).  On the TPU the widths must tile
    by lanes.

    The tile is the MXU's height, or the mean group's rows rounded up to
    a power of two where that is less (never under the dtype's packed
    tile); the hidden block the widest lane-aligned divisor of the hidden
    width that fits the budget."""
    n_rows = T * top_k
    if n_rows < H * _SORTED_MIN_GROUP_ROWS:
        return None
    if jax.default_backend() == "tpu" and (d % 128 or f % 128 or T % 128):
        return None
    itemsize = jnp.dtype(dtype).itemsize
    quantum = 32 // itemsize              # sublanes a packed tile holds
    mean = -(-n_rows // H)
    block_rows = min(_SORTED_TILE_ROWS,
                     max(quantum, 1 << (mean - 1).bit_length()))
    for n in range(1, max(f // 128, 1) + 1):
        block_f = f // n
        if f % n or (n > 1 and block_f % 128):
            continue
        if sorted_ffn_vmem_bytes(block_rows, block_f, T, top_k, d,
                                 itemsize) <= _SORTED_VMEM_BUDGET:
            return block_rows, block_f
    return None


@functools.partial(jax.jit, static_argnames=("block_rows",))
def sorted_tile_plan(loc, sizes, block_rows: int, gates):
    """Where a step's picks go in the padded row buffer, and whose each
    padded row is.

    ``loc`` [T, k] int32: the group of each pick, H for none (a token
    picks a group at most once); ``sizes`` [H] int32: picks of each
    group; ``gates`` [T, k] float32: each pick's weight.  Group ``e``
    gets ``cdiv(sizes[e], block_rows)`` tiles, its picks in token order
    from its first tile's first row, so at most
    ``cdiv(T * k, block_rows) + H - 1`` tiles are used.  No sort and no
    scatter (one update at a time on the TPU): a pick's place in its
    group is the count of tokens above it that chose the group, and a
    padded row's token is the one of its tile's group with that many
    above it.  Returns

    * ``tile_expert`` [tiles] int32 -- the group a tile belongs to (the
      last used tile's past the used count, so a skipped step asks for
      no new weight block),
    * ``num_used`` [1] int32 -- tiles that hold rows,
    * ``dest`` [T, k] int32 -- the padded row of each pick, -1 for the
      picks of no group,
    * ``src`` [tiles * block_rows] int32 -- the token of each padded
      row, -1 for a row no pick has (a tile's rows fill from its first),
    * ``row_gate`` [tiles * block_rows] float32 -- that pick's gate, 0
      for a row no pick has.

    Behind one jit, as the call is: a program's layers lower one plan."""
    T, k = loc.shape
    H = sizes.shape[0]
    tm = block_rows
    nt = -(-(T * k) // tm) + H - 1
    hot = loc[:, :, None] == jnp.arange(H, dtype=jnp.int32)    # [T, k, H]
    chose = jnp.sum(hot, axis=1, dtype=jnp.int32)              # [T, H] 0/1
    above = jnp.cumsum(chose, axis=0) - chose
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    first = tile_end - tiles
    place = (first * tm)[None] + above                         # [T, H]
    dest = jnp.sum(jnp.where(hot, place[:, None], 0), axis=-1)
    dest = jnp.where(loc < H, dest, -1)
    num_used = tile_end[-1]
    t = jnp.arange(nt, dtype=jnp.int32)
    live = jnp.minimum(t, jnp.maximum(num_used - 1, 0))
    te = jnp.minimum(jnp.sum(live[:, None] >= tile_end[None], axis=1,
                             dtype=jnp.int32), H - 1)
    # row r of used tile t is its group's pick with (t - first) * tm + r
    # of the group's picks above it; the columns of a tile's group by a
    # one-hot sum (a gather of columns is a loop on the TPU)
    mine = (te[:, None] == jnp.arange(H, dtype=jnp.int32)) \
        & (t < num_used)[:, None]                              # [tiles, H]
    pick = lambda a: jnp.sum(jnp.where(mine[:, None], a[None], 0), axis=-1)
    rank = ((t - first[te]) * tm)[:, None] \
        + jnp.arange(tm, dtype=jnp.int32)                      # [tiles, tm]
    # above + 1 where the token chose the group, 0 where not: one compare
    is_row = pick(jnp.where(chose > 0, above + 1, 0))[:, None, :] \
        == rank[:, :, None] + 1                                # [tiles, tm, T]
    weight = jnp.sum(jnp.where(hot, gates.astype(jnp.float32)[:, :, None],
                               0.0), axis=1)
    tok = jnp.arange(T, dtype=jnp.int32)
    src = jnp.max(jnp.where(is_row, tok, -1), axis=-1)
    row_gate = jnp.sum(jnp.where(is_row, pick(weight)[:, None, :], 0.0),
                       axis=-1)
    return (te, num_used.reshape(1), dest, src.reshape(-1),
            row_gate.reshape(-1))


def _whole(*_):
    """A whole array at a constant index: fetched (or written) once, and
    one buffer."""
    return 0, 0


def _sorted_maps(nf: int):
    """Index maps of the sorted kernel's weights (grid indices, then the
    scalar-prefetch operands, of which the tile -> expert map and the
    used count are read).  A step past the used tiles is sent to the last
    used step's blocks, so it moves nothing."""
    def live(t, j, te, nu):
        used = t < nu[0]
        t = jnp.where(used, t, jnp.maximum(nu[0] - 1, 0))
        return jnp.where(used, j, nf - 1), te[t]

    def gate(t, j, te, nu, *_):
        j, e = live(t, j, te, nu)
        return e, 0, j

    def up(t, j, te, nu, *_):   # w_in is [gate | up]: the second half
        j, e = live(t, j, te, nu)
        return e, 0, nf + j

    def down(t, j, te, nu, *_):
        j, e = live(t, j, te, nu)
        return e, j, 0
    return gate, up, down


def _sorted_gated_kernel(te_ref, nu_ref, src_ref, rg_ref, dest_ref, x_ref,
                         wg_ref, wu_ref, wo_ref, o_ref, xs_ref, ys_ref):
    """One (row tile, hidden block) step.  The output, the step's whole
    ``[T, d]`` float32, stays in VMEM over the grid: zeroed at the first
    step, written to HBM once after the last.  At a tile's first hidden
    block its rows are taken from the step's: row ``r`` of tile ``t`` is
    the token with a pick whose place is ``t * rows + r`` (a one-hot
    product; a row no pick has is zero).  Then ``[rows, block_f]`` gate
    and up tiles, ``silu(g) * u`` in float32, one cast, folded into the
    tile's float32 rows in VMEM.  At the last hidden block each row a
    pick has is multiplied by its gate and added to its token's row of
    the output: a tile is one expert's and a token picks an expert once,
    so a tile's tokens are distinct, and tiles run in order."""
    t, j = pl.program_id(0), pl.program_id(1)
    nf = pl.num_programs(1)
    tm = xs_ref.shape[0]
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    @pl.when((t == 0) & (j == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(t < nu_ref[0])
    def _compute():
        @pl.when(j == 0)
        def _gather():
            T = x_ref.shape[0]
            row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, T), 0)
            hit = jnp.zeros((tm, T), jnp.float32)
            for k in range(dest_ref.shape[0]):
                hit += jnp.where(dest_ref[k:k + 1, :] == row, 1.0, 0.0)
            xs_ref[...] = dot(hit.astype(x_ref.dtype),
                              x_ref[...]).astype(xs_ref.dtype)

        x = xs_ref[...]
        g = dot(x, wg_ref[0])
        u = dot(x, wu_ref[0])
        y = dot((jax.nn.silu(g) * u).astype(x.dtype), wo_ref[0])

        @pl.when(j == 0)
        def _first():
            ys_ref[...] = y

        @pl.when(j > 0)
        def _fold():
            ys_ref[...] += y

        @pl.when(j == nf - 1)
        def _combine():
            def add_row(r):
                tok = src_ref[t * tm + r]
                o_ref[pl.ds(tok, 1), :] += \
                    rg_ref[t * tm + r] * ys_ref[pl.ds(r, 1), :]
                return r + 1

            # a tile's rows fill from its first: stop at the first empty
            jax.lax.while_loop(
                lambda r: (r < tm)
                & (src_ref[t * tm + jnp.minimum(r, tm - 1)] >= 0),
                add_row, jnp.int32(0))


def sorted_gated_ffn(x, dest, src, row_gate, w_in, w_out, tile_expert,
                     num_used, *, block_rows: int, block_f: int,
                     interpret=None):
    """The held experts' part of the layer's result, float32 [T, d]: each
    token's row the sum over its picks with a place of
    ``gate * W_out[e] (silu(g) * u)``, ``[g | u] = x[token] W_in[e]``.

    ``x`` [T, d] the step's rows; ``dest`` [T, k] the padded row of each
    pick (-1: none), ``src`` / ``row_gate`` [tiles * block_rows] the
    token and the gate of each padded row, and tile ``t`` with the
    weights of group ``tile_expert[t]`` (all ``sorted_tile_plan``'s);
    ``w_in`` [H, d, 2f] as ``[gate | up]`` and ``w_out`` [H, f, d] as the
    layer holds them.  The padded rows never leave VMEM, and the gates'
    sum is in float32 in the order of the tiles.  Tiles from
    ``num_used`` on do no product and read no weight; a token with no
    pick here reads exactly zero."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _sorted_gated_call(x, dest, src, row_gate, w_in, w_out,
                              tile_expert, num_used,
                              block_rows=int(block_rows),
                              block_f=int(block_f),
                              interpret=bool(interpret))


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_f", "interpret"))
def _sorted_gated_call(x, dest, src, row_gate, w_in, w_out, tile_expert,
                       num_used, *, block_rows, block_f, interpret):
    """The ``pallas_call`` behind ONE jit (as ``paged_attention``'s): a
    program whose layers call it at identical shapes lowers one kernel
    body, and the Pallas -> Mosaic lowering is paid on every start."""
    T, d = x.shape
    H, f, _ = w_out.shape
    nt, nf = tile_expert.shape[0], f // block_f
    gate, up, down = _sorted_maps(nf)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_SORTED_VMEM_LIMIT)
    return pl.pallas_call(
        _sorted_gated_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nt, nf),
            in_specs=[pl.BlockSpec((dest.shape[1], T), _whole),
                      pl.BlockSpec((T, d), _whole),
                      pl.BlockSpec((1, d, block_f), gate),
                      pl.BlockSpec((1, d, block_f), up),
                      pl.BlockSpec((1, block_f, d), down)],
            out_specs=pl.BlockSpec((T, d), _whole),
            scratch_shapes=[pltpu.VMEM((block_rows, d), x.dtype),
                            pltpu.VMEM((block_rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        name="sorted_gated_ffn",
        interpret=interpret,
        **params,
    )(tile_expert, num_used, src, row_gate, dest.T, x, w_in, w_in, w_out)


# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify)


def verify_static(G, C, d, h, E=None, dtype="bfloat16", block_c=None,
                  block_f=None):
    """Static Mosaic-legality findings for the grouped expert-matmul at
    this shape/config — the counts operand travels as ``[G, 1, 1]`` with
    ``(1, 1, 1)`` blocks (trailing dims span the array, so no sublane
    tiling applies; the flash-lse layout trick)."""
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    E = int(E or G)
    rep = max(1, G // E)
    if block_c is None or block_f is None:
        bc_d, bf_d = _default_grouped_blocks(C, d, h, dtype)
        block_c = block_c or bc_d
        block_f = block_f or bf_d
    bc, bf = int(block_c), int(block_f)
    spec = kv.KernelSpec(
        name="grouped_matmul",
        grid=(G, C // bc if bc else 0, h // bf if bf else 0),
        args=[
            kv.ArgSpec("x", (G, C, d), (1, bc, d),
                       lambda g, i, j: (g, i, 0), dtype),
            kv.ArgSpec("w1", (E, d, h), (1, d, bf),
                       lambda g, i, j: (g // rep, 0, j), dtype,
                       dma_once=True),
            kv.ArgSpec("b1", (E, 1, h), (1, 1, bf),
                       lambda g, i, j: (g // rep, 0, j), dtype),
            kv.ArgSpec("w2", (E, h, d), (1, bf, d),
                       lambda g, i, j: (g // rep, j, 0), dtype,
                       dma_once=True),
            kv.ArgSpec("b2", (E, 1, d), (1, 1, d),
                       lambda g, i, j: (g // rep, 0, 0), dtype),
            kv.ArgSpec("counts", (G, 1, 1), (1, 1, 1),
                       lambda g, i, j: (g, 0, 0), "int32"),
            kv.ArgSpec("o", (G, C, d), (1, bc, d),
                       lambda g, i, j: (g, i, 0), dtype, is_output=True),
        ],
        scratch=[kv.ScratchSpec("acc", (bc, d), "float32")],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        needs_fp32_acc=True,
        where=f"grouped_matmul[G={G} C={C} d={d} h={h} E={E} "
              f"bc={bc} bf={bf} {dtype}]")
    return kv.verify_kernel(spec)


def verify_static_sorted(T, top_k, H, d, f, dtype="bfloat16",
                         block_rows=None, block_f=None):
    """Static Mosaic-legality findings for the sorted gated kernel at
    this step's shape: the step's rows, their places and the float32
    ``[T, d]`` output each one whole block at a constant index (one
    buffer; the output revisited along both sequential axes, the
    accumulator pattern), the weight tiles streamed, the gathered tile
    and its float32 rows as scratch, the plan's four scalar operands.
    The tile -> expert map is checked on the plan that uses every tile
    (all groups but the last one row, the last the rest: the bound
    ``cdiv(T * k, rows) + H - 1`` reached)."""
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    if block_rows is None or block_f is None:
        rule = sorted_ffn_blocks(T, top_k, H, d, f, dtype)
        block_rows, block_f = block_rows or rule[0], block_f or rule[1]
    tm, bf = int(block_rows), int(block_f)
    nt, nf = -(-(T * top_k) // tm) + H - 1, f // bf
    te = np.minimum(np.arange(nt), H - 1).astype(np.int32)
    gate, up, down = _sorted_maps(nf)
    spec = kv.KernelSpec(
        name="sorted_gated_ffn",
        grid=(nt, nf),
        args=[
            kv.ArgSpec("dest", (top_k, T), (top_k, T), _whole, "int32"),
            kv.ArgSpec("x", (T, d), (T, d), _whole, dtype),
            kv.ArgSpec("w_gate", (H, d, 2 * f), (1, d, bf), gate, dtype,
                       dma_once=True),
            kv.ArgSpec("w_up", (H, d, 2 * f), (1, d, bf), up, dtype,
                       dma_once=True),
            kv.ArgSpec("w_out", (H, f, d), (1, bf, d), down, dtype,
                       dma_once=True),
            kv.ArgSpec("o", (T, d), (T, d), _whole, "float32",
                       is_output=True),
        ],
        scratch=[kv.ScratchSpec("xs", (tm, d), dtype),
                 kv.ScratchSpec("ys", (tm, d), "float32")],
        dimension_semantics=("arbitrary", "arbitrary"),
        scalar_prefetch=(te, np.asarray([nt], np.int32),
                         np.zeros(nt * tm, np.int32),
                         np.zeros(nt * tm, np.float32)),
        vmem_budget=_SORTED_VMEM_BUDGET, vmem_limit=_SORTED_VMEM_LIMIT,
        # both products accumulate in float32 registers and fold into the
        # tile's float32 scratch rows; the gates' sum is float32 too
        needs_fp32_acc=True, acc_inline=True,
        where=f"sorted_gated_ffn[T={T} k={top_k} H={H} d={d} f={f} "
              f"rows={tm} bf={bf} {dtype}]")
    return kv.verify_kernel(spec)
