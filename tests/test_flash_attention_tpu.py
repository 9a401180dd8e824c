"""The kernels of the main path, compiled by the chip's own compiler.

Interpret-mode parity says a kernel is correct; only Mosaic says whether it
lowers (round 1 shipped an illegal lse BlockSpec that every interpret-mode
test passed).  The TPU compiler is installed here and compiles for a chip
that is *described*, not attached, so these tests ask it — at the widths
the chip runs: bench.py's on-chip model (d2048, ffn 7168, vocab 32000,
16Q/8KV·128) and Llama-3-8B (d4096, ffn 14336, vocab 128256, 32Q/8KV·128),
b4·s2048, default block sizes, forward and backward.  A compile that passes
is not a chip run; `chip_smoke.py` is.

The topology is described inside a module-scoped fixture, after a test of
this file has started — never at import, in a `skipif`, or in `conftest.py`:
one process at a time may load the TPU library, every xdist worker imports
every test file, and only the worker that runs this file may load it.  So
these tests live in this one file and compile in the test's own process.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

BF16 = jnp.bfloat16
# (hidden, ffn, vocab, q heads, kv heads, head_dim)
WIDTHS = {
    "bench": (2048, 7168, 32000, 16, 8, 128),
    "llama3_8b": (4096, 14336, 128256, 32, 8, 128),
}
BATCH, SEQ = 4, 2048
TOKENS = BATCH * SEQ


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip can be written to the persistent
    # cache but never read back: keep the cache off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Compile for the described chip; the Mosaic kernel must be in the
    program."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sumsq(*xs):
    return sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in xs)


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_flash_fwd_bwd_compiles(one_chip, widths):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    _, _, _, h, hk, d = WIDTHS[widths]

    def step(q, k, v):
        loss = lambda q, k, v: _sumsq(flash_attention(
            q, k, v, causal=True, autotune=False, interpret=False))
        return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

    S = lambda heads: jax.ShapeDtypeStruct((BATCH, SEQ, heads, d), BF16,
                                           sharding=one_chip)
    _compile(step, S(h), S(hk), S(hk))


# (batch, seq, q heads, kv heads, dtype, causal): the backward's two
# kernels at the shapes that reach them — train-1chip's own
# (internlm2: 16Q/8KV) and mistral's 32Q/8KV at the cell's b4·s4096, MHA,
# the encoder families' non-causal call, float32, a sequence of one tile
BWD_SHAPES = {
    "train_1chip_16q8kv": (4, 4096, 16, 8, BF16, True),
    "mistral_32q8kv": (4, 4096, 32, 8, BF16, True),
    "mha_16q16kv": (4, 2048, 16, 16, BF16, True),
    "non_causal": (4, 2048, 16, 8, BF16, False),
    "float32": (2, 4096, 16, 8, jnp.float32, True),
    "one_tile_s384": (8, 384, 8, 4, BF16, True),
}


@pytest.mark.parametrize("shape", sorted(BWD_SHAPES))
def test_flash_backward_kernels_compile(one_chip, shape):
    """The whole backward is the two kernels: both are in the program by
    name, and no float32 score tensor ([..., s, tile]) lives outside
    them (the compiled backward holds no temporary of that size)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    b, s, h, hk, dt, causal = BWD_SHAPES[shape]
    d = 128

    def step(q, k, v):
        loss = lambda q, k, v: _sumsq(flash_attention(
            q, k, v, causal=causal, autotune=False, interpret=False))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    S = lambda heads: jax.ShapeDtypeStruct((b, s, heads, d), dt,
                                           sharding=one_chip)
    compiled = _compile(step, S(h), S(hk), S(hk))
    text = compiled.as_text()
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text
    score_bytes = b * h * s * 128 * 4        # one [b, h, s, 128] float32
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * score_bytes


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_fused_rmsnorm_qkv_fwd_bwd_compiles(one_chip, widths):
    from paddle_tpu.ops.pallas.fused_block import fused_rmsnorm_qkv
    dm, _, _, h, hk, d = WIDTHS[widths]

    def step(*args):
        loss = lambda *a: _sumsq(*fused_rmsnorm_qkv(
            *a, autotune=False, interpret=False))
        return jax.value_and_grad(loss, (0, 1, 2, 3, 4))(*args)

    S = lambda *shape: jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    _compile(step, S(TOKENS, dm), S(dm), S(dm, h * d), S(dm, hk * d),
             S(dm, hk * d))


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_fused_mlp_fwd_bwd_compiles(one_chip, widths):
    from paddle_tpu.ops.pallas.fused_block import fused_mlp
    dm, ffn = WIDTHS[widths][:2]

    def step(*args):
        loss = lambda *a: _sumsq(fused_mlp(*a, autotune=False,
                                           interpret=False))
        return jax.value_and_grad(loss, (0, 1, 2, 3))(*args)

    S = lambda *shape: jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    _compile(step, S(TOKENS, dm), S(dm, ffn), S(dm, ffn), S(ffn, dm))


@pytest.mark.parametrize("rows,ffn", [(256, 14336), (512, 16384)])
def test_fused_block_prefill_chunk_compiles_at_one_pass(one_chip, rows,
                                                        ffn):
    """A prefill chunk's rows at d = 4096 (mistral-7b's 256, sarvam's
    dense layer's and granite's attention layer's 512) as ONE token block:
    the weights are read once, inside the scope the calls declare — the
    compiler's own 16 MiB refuses the QKV kernel at either."""
    from paddle_tpu.ops.pallas import fused_block as FB
    dm, h, hk, d = 4096, 32, 8, 128
    assert FB._default_mlp_blocks(rows, dm, ffn, "bfloat16")[0] == rows
    assert FB._default_qkv_blocks(rows, dm, h * d, hk * d, hk * d,
                                  "bfloat16")[0] == rows
    S = lambda *shape: jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    mlp = _compile(lambda *a: FB.fused_mlp(*a, autotune=False,
                                           interpret=False),
                   S(rows, dm), S(dm, ffn), S(dm, ffn), S(ffn, dm))
    qkv = _compile(lambda *a: FB.fused_rmsnorm_qkv(*a, autotune=False,
                                                   interpret=False),
                   S(rows, dm), S(dm), S(dm, h * d), S(dm, hk * d),
                   S(dm, hk * d))
    for compiled in (mlp, qkv):
        assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_fused_cross_entropy_fwd_bwd_compiles(one_chip, widths):
    from paddle_tpu.ops.pallas.cross_entropy import \
        fused_softmax_cross_entropy
    vocab = WIDTHS[widths][2]

    def step(logits, labels):
        loss = lambda x: jnp.sum(fused_softmax_cross_entropy(
            x, labels, autotune=False, interpret=False))
        return jax.value_and_grad(loss)(logits)

    _compile(step,
             jax.ShapeDtypeStruct((TOKENS, vocab), BF16, sharding=one_chip),
             jax.ShapeDtypeStruct((TOKENS,), jnp.int32, sharding=one_chip))


def test_paged_decode_compiles(one_chip):
    """The serve phase's decode attention: 4 slots over a 2048-token
    table of 16-token blocks, Llama-3-8B heads."""
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention
    _, _, _, h, hk, d = WIDTHS["llama3_8b"]
    slots, block, max_len = 4, 16, 2048
    blocks = 1 + slots * max_len // block

    def decode(q, kp, vp, bt, lengths):
        return paged_decode_attention(q, kp, vp, bt, lengths,
                                      interpret=False)

    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    _compile(decode, S((slots, h, d)), S((blocks, block, hk, d)),
             S((blocks, block, hk, d)), S((slots, max_len // block),
                                          jnp.int32), S((slots,), jnp.int32))


def test_paged_decode_lowers_one_kernel_body_for_twelve_layers(one_chip):
    """serve-chat's decode attention (32 slots, a 2576-token table, 3073
    blocks of 16, Mistral-7B heads) called by twelve layers at identical
    shapes: the kernel sits behind one jit, so the program lowers one
    kernel body that the layers share (the Pallas -> Mosaic lowering is
    paid on every start) and still compiles to twelve custom calls."""
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention
    layers, slots, block, table, blocks = 12, 32, 16, 161, 3073
    h, hk, d = 32, 8, 128

    def decode(q, pools, bt, lengths):
        for kp, vp in pools:
            q = paged_decode_attention(q, kp, vp, bt, lengths,
                                       interpret=False)
        return q

    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    pool = S((blocks, block, hk, d))
    lowered = jax.jit(decode).lower(
        S((slots, h, d)), [(pool, pool)] * layers,
        S((slots, table), jnp.int32), S((slots,), jnp.int32))
    assert lowered.as_text().count("@tpu_custom_call") == 1
    assert lowered.compile().as_text().count(
        'custom_call_target="tpu_custom_call"') == layers


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_paged_decode_compiles_other_pools(one_chip, dtype):
    """The same walk over a float32 pool and over an int8 pool whose
    scales ride the block DMAs, at serve-chat's shapes."""
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention
    slots, block, table, blocks, h, hk, d = 32, 16, 161, 3073, 32, 8, 128
    quant = dtype == "int8"
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def decode(q, kp, vp, bt, lengths, ks=None, vs=None):
        return paged_decode_attention(q, kp, vp, bt, lengths,
                                      interpret=False, k_scale=ks,
                                      v_scale=vs)

    pool = S((blocks, block, hk, d), dtype)
    scales = (S((blocks, block, hk), jnp.float32),) * 2 if quant else ()
    _compile(decode, S((slots, h, d), BF16 if quant else jnp.float32),
             pool, pool, S((slots, table), jnp.int32),
             S((slots,), jnp.int32), *scales)



# trinity-large-preview's widths (serve-mixed): 48 query heads over 8
# key-value heads x 128, 32 slots, a 33,552-token table of 16-token blocks,
# a window of 4096; the window layers' pools hold 6,144 blocks
@pytest.mark.parametrize("window", [4096, None])
def test_paged_decode_compiles_under_a_window_at_48_heads(one_chip, window):
    """The decode kernel with the window's lower bound (a static count:
    the walk starts at the chunk of ``len - window``) and without it, at
    the cell's shapes; two kernel bodies in a program that has layers of
    both kinds, one a kind."""
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention
    slots, block, table, h, hk, d = 32, 16, 2097, 48, 8, 128
    blocks = 6144 if window else 16384
    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)

    def decode(q, kp, vp, bt, lengths):
        more = {"window": window} if window else {}
        q = paged_decode_attention(q, kp, vp, bt, lengths, interpret=False,
                                   **more)
        return paged_decode_attention(q, kp, vp, bt, lengths,
                                      interpret=False, **more)

    pool = S((blocks, block, hk, d))
    lowered = jax.jit(decode).lower(
        S((slots, h, d)), pool, pool, S((slots, table), jnp.int32),
        S((slots,), jnp.int32))
    assert lowered.as_text().count("@tpu_custom_call") == 1
    assert lowered.compile().as_text().count(
        'custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("window", [4096, None])
def test_paged_chunk_walk_compiles_with_run_time_trip_counts(one_chip,
                                                             window):
    """A 512-token prefill chunk's grouped-query attention over the paged
    context: one while loop whose bounds are read from the positions (no
    constant bound of 66 tiles), under the scope the reader looks for,
    its temporaries a few score tiles ([48, 512, 512] float32: 50 MB) and
    never the ``[48, 512, 33552]`` scores of a gathered table (3.3 GB)."""
    from paddle_tpu.ops.pallas.paged_attention import paged_chunk_attention

    def chunk(q, kp, vp, bt, qpos):
        return paged_chunk_attention(q, kp, vp, bt, qpos, window=window)

    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    pool = S((6144 if window else 16384, 16, 8, 128))
    compiled = jax.jit(chunk).lower(
        S((1, 512, 48, 128)), pool, pool, S((1, 2097), jnp.int32),
        S((1, 512), jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_chunk_attention" in text and " while(" in text
    assert 'known_trip_count' not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


# granite-4.0-h-small's widths (serve-rag): 36 held experts of 72 at
# d4096 / f768, top 10; Mamba-2 128 heads x 64, state 128, chunk 256
@pytest.mark.parametrize("rows", [512, 24])
def test_served_expert_layer_compiles_to_grouped_matmuls(one_chip, rows,
                                                         monkeypatch):
    """A prefill chunk's rows go through the repo's own kernel over the
    sorted rows (one Mosaic call under its name whose output is the gated
    ``[512, 4096]`` sum, no ``ragged-dot`` of the compiler's, and neither
    the ``[5120, 1536]`` gate-and-up product nor the padded or gathered
    float32 rows ever a temporary); a decode step's keep ``lax.ragged_dot``, the compiler's
    grouped matmul (two custom calls and their metadata call).  Neither
    is a dense product over every expert — the temporary a dense
    [rows * 10, 36, 1536] product would need is not there."""
    import numpy as np
    from paddle_tpu.distributed.moe import gated_experts_forward
    # this process's backend is the CPU; the compile is for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    local = np.full(72, 36, np.int32)
    local[:36] = np.arange(36)

    def step(x, router, w_in, w_out, valid):
        return gated_experts_forward(x, router, w_in, w_out, top_k=10,
                                     local_of=local, row_valid=valid)

    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
    compiled = _compile(step, S((rows, 4096)), S((4096, 72)),
                        S((36, 4096, 1536)), S((36, 768, 4096)),
                        S((rows,), jnp.bool_))
    text = compiled.as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    if rows == 512:
        assert calls == 1 and "sorted_gated_ffn" in text
        assert "ragged-dot" not in text
        assert "[5120,1536]" not in text
        # the kernel's output is the step's [512, 4096]: no padded rows
        # (75 tiles of 128), no picks' rows gathered back to be summed
        assert "f32[9600,4096]" not in text
        assert "f32[512,10,4096]" not in text and "f32[5120,4096]" not in text
    else:
        assert calls == 3 and "ragged-dot" in text
        assert "sorted_gated_ffn" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < \
        rows * 10 * 36 * 1536 * 2


def test_mamba2_scan_and_step_compile(one_chip):
    """The chunked scan over a 512-token prefill chunk carrying a state,
    and the one-token update over 24 slots, at the published widths; the
    update is one pass over the state (its temporaries stay under one
    copy of it)."""
    from paddle_tpu.ops import mamba2
    f32 = jnp.float32
    S = lambda *shape: jax.ShapeDtypeStruct(shape, f32, sharding=one_chip)
    scan = jax.jit(lambda x, dt, A, B, C, h: mamba2.ssd_scan(
        x, dt, A, B, C, h, 256)).lower(
        S(1, 512, 128, 64), S(1, 512, 128), S(128), S(1, 512, 128),
        S(1, 512, 128), S(1, 128, 64, 128)).compile()
    assert scan.memory_analysis().temp_size_in_bytes < 1 << 30
    step = jax.jit(mamba2.ssm_step, donate_argnums=(5,)).lower(
        S(24, 128, 64), S(24, 128), S(128), S(24, 128), S(24, 128),
        S(24, 128, 64, 128)).compile()
    assert step.memory_analysis().temp_size_in_bytes < 24 * 128 * 64 * 128 * 4


# sarvam-105b's widths (serve-longctx): 64 heads, latent 512 + rotary key
# 64 stored as 640 lanes, q.k 192 / v 128; 24 slots over a 33,552-token
# table of 16-token blocks, 16,384 blocks
def test_latent_decode_kernel_compiles(one_chip):
    """The absorbed decode kernel over the latent pool's lane-padded rows
    compiles under its own name; a pool 576 wide does not (the chip's
    tiled layout holds it as 640 and Mosaic refuses the 576-wide DMA),
    which is why the pool pads the row."""
    from paddle_tpu.ops.pallas.latent_attention import \
        latent_decode_attention
    slots, block, blocks, table = 24, 16, 16384, 2097

    def decode(q, pool, bt, lengths):
        return latent_decode_attention(q, pool, bt, lengths, 512,
                                       interpret=False)

    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    compiled = _compile(decode, S((slots, 64, 640)),
                        S((blocks, block, 640)),
                        S((slots, table), jnp.int32), S((slots,), jnp.int32))
    assert "latent_attention" in compiled.as_text()
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(decode, S((slots, 64, 576)), S((blocks, block, 576)),
                 S((slots, table), jnp.int32), S((slots,), jnp.int32))


@pytest.mark.parametrize("table", [2097, 1072])
def test_latent_chunk_walk_compiles_with_a_run_time_trip_count(one_chip,
                                                                table):
    """A 512-token prefill chunk's attention over the pool: one while
    loop whose trip count is read from the positions (no constant bound
    of 66 tiles), under the scope the reader looks for, its temporaries a
    few score tiles and not the table's worth of keys — at the cell's
    table (33,552 positions: not a whole number of 512-token tiles, so
    the walk pads it) and at half of it."""
    from paddle_tpu.ops.pallas.latent_attention import \
        latent_chunk_attention

    def chunk(q, pool, bt, qpos, w):
        return latent_chunk_attention(q, pool, bt, qpos, w, rank=512,
                                      nope=128, scale=0.135)

    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    compiled = jax.jit(chunk).lower(
        S((1, 512, 64, 192)), S((16384, 16, 640)), S((1, table), jnp.int32),
        S((1, 512), jnp.int32), S((512, 64 * 256))).compile()
    text = compiled.as_text()
    assert "latent_chunk_attention" in text and " while(" in text
    assert 'known_trip_count' not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# kimi-linear-48b-a3b's widths (serve-reason): KDA 32 heads x 128, a
# 512-token prefill chunk in chunks of 64, 48 slots; latent attention at
# 32 heads over the same 640-lane rows, a 17,424-token table
def test_kda_scan_and_step_compile(one_chip):
    """The chunked delta rule over a 512-token prefill chunk carrying a
    state, and the one-token update over 48 slots, at the published
    widths: the [t, s, channel] decays of a chunk (0.5 GB a layer) are
    never a temporary, and the update's temporaries stay under one copy
    of the state."""
    from paddle_tpu.ops import kda
    f32 = jnp.float32
    S = lambda *shape: jax.ShapeDtypeStruct(shape, f32, sharding=one_chip)
    scan = jax.jit(lambda q, k, v, a, beta, S0: kda.kda_scan(
        q, k, v, a, beta, S0, 64)).lower(
        S(1, 512, 32, 128), S(1, 512, 32, 128), S(1, 512, 32, 128),
        S(1, 512, 32, 128), S(1, 512, 32), S(1, 32, 128, 128)).compile()
    assert scan.memory_analysis().temp_size_in_bytes < 256 << 20
    entry = scan.as_text().split("ENTRY", 1)[1]
    assert "64,64,128]" not in entry
    step = jax.jit(kda.kda_step, donate_argnums=(5,)).lower(
        S(48, 32, 128), S(48, 32, 128), S(48, 32, 128), S(48, 32, 128),
        S(48, 32), S(48, 32, 128, 128)).compile()
    assert step.memory_analysis().temp_size_in_bytes < 48 * 32 * 128 * 128 * 4


def test_a_decode_steps_convolution_tail_is_no_loop_over_the_slots(one_chip):
    """A decode step's new tail over 48 slots x 12,288 channels is one
    select: a batched ``dynamic_slice`` there is a gather, which the TPU
    compiler runs as a ``while`` over the rows — 2200 of the 3600 device
    operations of a serve-reason decode step (PERF.md section 6, PR 43)."""
    from paddle_tpu.ops import mamba2
    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    text = jax.jit(lambda x, t, w, v: mamba2.causal_conv(
        x, t, w, None, v)).lower(
        S((48, 1, 12288)), S((48, 3, 12288)), S((4, 12288)),
        S((48,), jnp.int32)).compile().as_text()
    assert " while(" not in text and "gather" not in text


def test_latent_decode_kernel_compiles_at_32_heads(one_chip):
    from paddle_tpu.ops.pallas.latent_attention import \
        latent_decode_attention

    def decode(q, pool, bt, lengths):
        return latent_decode_attention(q, pool, bt, lengths, 512,
                                       interpret=False)

    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    compiled = _compile(decode, S((48, 32, 640)), S((32768, 16, 640)),
                        S((48, 1089), jnp.int32), S((48,), jnp.int32))
    assert "latent_attention" in compiled.as_text()


def test_paged_engine_warms_the_targets_it_always_has(monkeypatch):
    """The walk's trip count is a run-time scalar read from the lengths:
    a paged engine whose decode step runs the kernel (interpret mode
    here) acquires one decode program, not one per live length, and
    serves what the model generates."""
    import paddle_tpu as pp
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.ops.pallas import paged_attention as PA
    monkeypatch.setattr(PA, "paged_decode_eligible", lambda *a, **k: True)
    pp.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128))
    eng = ContinuousBatchingEngine(
        model, slots=2, max_len=64, prefill_buckets=(16, 32),
        kv_block_size=4, prefill_chunk=8)
    assert sorted(eng.aot_warmup()) == ["serving.decode",
                                        "serving.prefill_chunk[8]"]
    prompts = [list(range(3, 24)), [7, 9, 11]]
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    done = eng.run()
    for rid, p in zip(rids, prompts):
        ref = model.generate(np.asarray(p, np.int32)[None],
                             max_new_tokens=6, do_sample=False)
        assert done[rid][1] == list(np.asarray(ref)[0, len(p):])


def test_flash_meets_the_four_chip_mesh_under_shard_map(topo, monkeypatch):
    """XLA cannot partition a Mosaic kernel: on the 2x2 mesh flash compiles
    because ops/pallas/mesh.py runs it per shard of batch (fsdp) and heads
    (tp) — with shard_map's checker on, as on the chip — and fails
    without."""
    from paddle_tpu.ops.pallas import mesh as KM
    # this process's backend is the CPU; the compile is for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    _, _, _, h, hk, d = WIDTHS["llama3_8b"]
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("fsdp", "tp"))
    sh = NamedSharding(mesh, P("fsdp", None, "tp", None))
    S = lambda heads: jax.ShapeDtypeStruct((BATCH, SEQ, heads, d), BF16,
                                           sharding=sh)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=True, autotune=False, interpret=False)

    def step(q, k, v):
        def loss(q, k, v):
            with KM.step_mesh(mesh, ("fsdp",)):
                return _sumsq(KM.over_batch_and_heads(attn, q, k, v))
        return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

    compiled = _compile(step, S(h), S(hk), S(hk))
    assert compiled.memory_analysis() is not None
    with pytest.raises(Exception, match="shard_map"):
        jax.jit(lambda q, k, v: attn(q, k, v)).lower(
            S(h), S(hk), S(hk)).compile()
