"""Pallas/Mosaic kernel static verifier (analysis/kernel_verify).

Covers: the catalog-wide clean sweep at bench shapes (incl. the two
named megakernel Mosaic risks surfacing as WARNINGs), adversarial
KernelSpec fixtures that each trip exactly the intended finding code,
the shared VMEM footprint model backing the megakernel eligibility
gate, autotune candidate pruning (the sub-quantum quant row-block class
is provably rejected before benchmarking), the odd-vocab CE block
clamp, the registered ``kernel-verify`` pass over a traced pallas_call
program, and the ``lint --kernels`` CLI verdict table.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.analysis import kernel_verify as kv
from paddle_tpu.analysis.diagnostics import Severity
from paddle_tpu.ops.pallas import fused_block as FB


def codes_of(diags):
    return sorted({d.message.split(":", 1)[0] for d in diags})


def error_codes_of(diags):
    return sorted({d.message.split(":", 1)[0] for d in diags
                   if d.severity >= Severity.ERROR})


# ---------------------------------------------------------------------------
# catalog: every shipped kernel x bench shape


class TestCatalog:
    @pytest.fixture(scope="class")
    def rows(self):
        return kv.catalog_report()

    def test_covers_all_seven_kernel_modules(self, rows):
        kernels = {r["kernel"] for r in rows}
        assert kernels >= {"flash_fwd", "flash_bwd", "fused_ce",
                           "rmsnorm", "fused_qkv", "fused_mlp",
                           "fused_decoder", "quant_matmul",
                           "paged_decode"}

    def test_catalog_has_zero_errors(self, rows):
        bad = [(r["kernel"], r["shape"], r["codes"]) for r in rows
               if r["errors"]]
        assert not bad, bad

    def test_decoder_named_risks_surface_as_distinct_warnings(self, rows):
        """Acceptance: the megakernel's lane-axis RoPE concat and the
        seq-scaling K/V scratch are each a distinct WARNING carrying the
        offending shape."""
        dec = [r for r in rows if r["kernel"] == "fused_decoder"]
        assert dec
        for r in dec:
            assert r["verdict"] == "WARNING", r
            assert set(r["codes"]) == {"LANE_CONCAT", "SEQ_SCRATCH"}, r
            seq = [d for d in r["diags"]
                   if d.message.startswith(kv.SEQ_SCRATCH)]
            # one finding per sequence-wide scratch buffer (K and V),
            # each naming the offending [s, dkv] shape
            assert len(seq) == 2
            assert any("(512, 512)" in d.message or
                       "(128, 1024)" in d.message for d in seq), \
                [d.message for d in seq]
            lane = [d for d in r["diags"]
                    if d.message.startswith(kv.LANE_CONCAT)]
            assert len(lane) == 1
            assert "lane" in lane[0].message

    def test_non_decoder_rows_are_clean(self, rows):
        for r in rows:
            if r["kernel"] != "fused_decoder":
                assert r["verdict"] == "OK", r

    def test_render_table_mentions_every_kernel(self, rows):
        table = kv.render_catalog_table(rows)
        for name in ("flash_fwd", "fused_decoder", "paged_decode"):
            assert name in table
        assert "0 error(s)" in table


# ---------------------------------------------------------------------------
# adversarial fixtures: each trips exactly the intended finding


def _spec(name="adv", grid=(4,), args=None, **kw):
    return kv.KernelSpec(name=name, grid=grid, args=args or [], **kw)


class TestAdversarialFixtures:
    def test_overlapping_output_index_map_is_write_race(self):
        # two parallel grid points write each output block
        spec = _spec(grid=(4,), args=[
            kv.ArgSpec("o", (256, 128), (128, 128),
                       lambda i: (i // 2, 0), "float32", is_output=True),
        ], dimension_semantics=("parallel",))
        diags = kv.verify_kernel(spec, record_metric=False)
        assert error_codes_of(diags) == [kv.WRITE_RACE], codes_of(diags)

    def test_sequential_revisit_is_not_a_race(self):
        # the same overlap along an "arbitrary" axis is the legal
        # accumulator pattern (flash dq, fused-MLP y) — no finding
        spec = _spec(grid=(4,), args=[
            kv.ArgSpec("o", (512, 128), (128, 128),
                       lambda i: (i // 2, 0), "float32", is_output=True),
        ], dimension_semantics=("arbitrary",))
        diags = kv.verify_kernel(spec, record_metric=False)
        assert kv.WRITE_RACE not in codes_of(diags)
        # ...but full coverage is still required, and i//2 covers only
        # blocks 0..1 of 4
        assert kv.OUTPUT_UNCOVERED in error_codes_of(diags)

    def test_misaligned_lane_dim(self):
        spec = _spec(grid=(2,), args=[
            kv.ArgSpec("x", (16, 200), (16, 100), lambda i: (0, i),
                       "float32"),
        ])
        diags = kv.verify_kernel(spec, record_metric=False)
        assert kv.LANE_MISALIGNED in error_codes_of(diags)

    def test_vmem_exceeding_block(self):
        spec = _spec(grid=(2,), args=[
            kv.ArgSpec("x", (16384, 1024), (8192, 1024), lambda i: (i, 0),
                       "float32"),
        ])
        diags = kv.verify_kernel(spec, record_metric=False)
        assert kv.VMEM_EXCEEDED in error_codes_of(diags)

    def test_hand_dma_operand_is_walked_and_takes_no_vmem(self):
        # a pool left in HBM: its copies are walked over grid x dma_grid
        # (the table sends one past the pool), its VMEM is the scratch
        table = np.arange(8, dtype=np.int32).reshape(2, 4) + 1
        spec = _spec(grid=(2,), args=[
            kv.ArgSpec("pool", (8, 8192, 1024), (1, 8192, 1024),
                       lambda b, j, bt: (bt[b, j], 0, 0), "float32",
                       dma_grid=(4,)),
        ], scratch=[kv.ScratchSpec("chunk", (8, 128), "float32")],
            scalar_prefetch=(table,))
        assert kv.footprint_bytes(spec) == 8 * 128 * 4 + table.nbytes
        diags = kv.verify_kernel(spec, record_metric=False)
        assert error_codes_of(diags) == [kv.OOB_BLOCK], codes_of(diags)

    def test_uncovered_output_block(self):
        spec = _spec(grid=(4,), args=[
            kv.ArgSpec("o", (512, 128), (128, 128), lambda i: (0, 0),
                       "float32", is_output=True),
        ], dimension_semantics=("arbitrary",))
        diags = kv.verify_kernel(spec, record_metric=False)
        assert kv.OUTPUT_UNCOVERED in error_codes_of(diags)

    def test_oob_block_read(self):
        spec = _spec(grid=(4,), args=[
            kv.ArgSpec("x", (512, 128), (128, 128), lambda i: (i + 1, 0),
                       "float32"),
        ])
        diags = kv.verify_kernel(spec, record_metric=False)
        assert error_codes_of(diags) == [kv.OOB_BLOCK], codes_of(diags)

    def test_redundant_dma_on_dma_once_arg(self):
        # the inner sweep leaves weight block 0 and comes back (j % 2):
        # Pallas must re-DMA it — exactly what the fused-block clamped
        # maps exist to avoid
        spec = _spec(grid=(1, 4), args=[
            kv.ArgSpec("w", (256, 128), (128, 128),
                       lambda i, j: (j % 2, 0), "float32", dma_once=True),
            kv.ArgSpec("o", (128, 128), (128, 128),
                       lambda i, j: (i, 0), "float32", is_output=True),
        ], dimension_semantics=("parallel", "arbitrary"))
        diags = kv.verify_kernel(spec, record_metric=False)
        assert kv.REDUNDANT_DMA in codes_of(diags)
        assert not error_codes_of(diags)

    def test_clamped_map_passes_dma_once(self):
        # the fused-qkv wq map: resident for the first half of the inner
        # sweep, clamped after — each block DMAs exactly once per sweep
        spec = _spec(grid=(2, 4), args=[
            kv.ArgSpec("w", (256, 256), (256, 128),
                       FB._clamped(0, 2), "float32", dma_once=True),
            kv.ArgSpec("o", (256, 128), (128, 128),
                       lambda i, j: (i, 0), "float32", is_output=True),
        ], dimension_semantics=("parallel", "arbitrary"))
        diags = kv.verify_kernel(spec, record_metric=False)
        assert kv.REDUNDANT_DMA not in codes_of(diags)

    def test_block_indivisible(self):
        spec = _spec(grid=(2,), args=[
            kv.ArgSpec("x", (300, 128), (128, 128), lambda i: (i, 0),
                       "float32"),
        ])
        diags = kv.verify_kernel(spec, record_metric=False)
        assert kv.BLOCK_INDIVISIBLE in error_codes_of(diags)

    def test_missing_fp32_accumulator_warns(self):
        spec = _spec(grid=(2,), args=[
            kv.ArgSpec("x", (256, 128), (128, 128), lambda i: (i, 0),
                       "bfloat16"),
        ], needs_fp32_acc=True)
        diags = kv.verify_kernel(spec, record_metric=False)
        assert kv.ACC_DTYPE in codes_of(diags)

    def test_quant_scale_shape_mismatch(self):
        from paddle_tpu.ops.pallas import quant_matmul as qm
        diags = qm.verify_static(256, 1024, 1024, block_t=128,
                                 block_n=256)
        assert not error_codes_of(diags)
        # break the agreement: scale lanes frozen at 128 vs qw's 256
        spec = _spec(grid=(2, 4), args=[
            kv.ArgSpec("qw", (256, 1024), (256, 256),
                       lambda i, j: (0, j), "int8"),
            kv.ArgSpec("scale", (1, 1024), (1, 128),
                       lambda i, j: (0, j), "float32"),
        ], scale_pairs=[("scale", "qw")])
        diags = kv.verify_kernel(spec, record_metric=False)
        assert kv.SCALE_SHAPE in error_codes_of(diags)


# ---------------------------------------------------------------------------
# the shared VMEM footprint model (satellite: megakernel gate unification)


class TestSharedVmemModel:
    def test_decoder_budget_is_the_verifier_budget(self):
        assert FB._DECODER_VMEM_BUDGET == kv.VMEM_BUDGET_BYTES

    def test_decoder_vmem_bytes_delegates_to_footprint_model(self):
        a = (512, 1024, 1024, 512, 128, 3584, 64, 128, 128, "bfloat16")
        spec = FB._decoder_verify_spec(1, *a)
        assert FB.decoder_vmem_bytes(*a) == kv.footprint_bytes(spec)

    def test_footprint_monotone_in_seq(self):
        lo = FB.decoder_vmem_bytes(128, 1024, 1024, 512, 128, 3584,
                                   16, 128, 128, "bfloat16")
        hi = FB.decoder_vmem_bytes(4096, 1024, 1024, 512, 128, 3584,
                                   16, 128, 128, "bfloat16")
        assert hi > lo

    def test_eligibility_gate_and_lint_verdict_agree(self):
        """The gate admits a shape iff verify_static finds no
        VMEM ERROR for it (they share the same footprint + budget)."""
        for shape in [(4, 512, 1024, 1024, 512, 128, 3584),
                      (4, 2048, 2048, 2048, 1024, 128, 7168)]:
            b, s, d, dq, dkv, hd, f = shape
            eligible = FB.fused_decoder_eligible(b, s, d, dq, dkv, hd, f,
                                                 "bfloat16")
            diags = FB.verify_static_decoder(b, s, d, dq, dkv, hd, f,
                                             dtype="bfloat16")
            vmem_err = any(
                d.severity >= Severity.ERROR
                and d.message.startswith((kv.VMEM_EXCEEDED,))
                for d in diags)
            assert eligible == (not vmem_err), (shape, diags)

    def test_resident_args_count_single_buffered(self):
        spec = kv.KernelSpec(name="t", grid=(2,), args=[
            kv.ArgSpec("a", (256, 128), (128, 128), lambda i: (i, 0),
                       "float32"),
            kv.ArgSpec("w", (1, 128), (1, 128), lambda i: (0, 0),
                       "float32", resident=True),
        ])
        # a double-buffers (2x), resident w does not (1x)
        assert kv.footprint_bytes(spec) == \
            2 * 128 * 128 * 4 + 1 * 128 * 4


# the serving shapes of the two per-segment kernels (PR 42): mistral-7b's
# prefill chunk and decode step, sarvam's dense layer and granite's
# attention layer at a chunk of 512 rows
SERVING_MLP = [(256, 4096, 14336), (32, 4096, 14336), (512, 4096, 16384)]
SERVING_QKV = [(256, 4096, 4096, 1024, 1024), (32, 4096, 4096, 1024, 1024),
               (512, 4096, 4096, 1024, 1024)]


class TestFusedBlockDeclaredScope:
    """The per-segment fused kernels ask the compiler for more VMEM than
    its own scope and tell the verifier the same number."""

    @pytest.mark.parametrize("shape", SERVING_MLP)
    def test_mlp_passes_at_the_serving_shapes(self, shape):
        diags = FB.verify_static_mlp(*shape, dtype="bfloat16")
        assert not [d for d in diags if d.severity >= Severity.WARNING], \
            diags

    @pytest.mark.parametrize("shape", SERVING_QKV)
    @pytest.mark.parametrize("residuals", [False, True])
    def test_qkv_passes_at_the_serving_shapes(self, shape, residuals):
        diags = FB.verify_static_qkv(*shape, dtype="bfloat16",
                                     residuals=residuals)
        assert not [d for d in diags if d.severity >= Severity.WARNING], \
            diags

    def test_the_rules_blocks_would_not_pass_the_compilers_scope(self):
        # the check reads the declared scope: the same spec under the
        # verifier's default limit is the error it was
        spec = FB._mlp_verify_spec(512, 4096, 16384, 512, 128, "bfloat16")
        assert FB._VMEM_LIMIT >= spec.vmem_limit == \
            FB._vmem_limit("mlp", 512, 128, 4096, 2) > kv.VMEM_LIMIT_BYTES
        assert not error_codes_of(kv.verify_kernel(spec,
                                                   record_metric=False))
        spec.vmem_limit = kv.VMEM_LIMIT_BYTES
        assert kv.VMEM_EXCEEDED in error_codes_of(
            kv.verify_kernel(spec, record_metric=False))

    @pytest.mark.parametrize("kernel", ["mlp", "qkv"])
    def test_blocks_over_the_declared_scope_are_still_reported(self,
                                                               kernel):
        if kernel == "mlp":
            diags = FB.verify_static_mlp(2048, 4096, 14336, "bfloat16",
                                         block_t=2048, block_f=128)
        else:
            diags = FB.verify_static_qkv(2048, 4096, 4096, 1024, 1024,
                                         "bfloat16", block_t=2048,
                                         block_o=128)
        assert kv.VMEM_EXCEEDED in error_codes_of(diags)

    def test_blocks_over_the_budget_warn(self):
        # 1024 rows at d = 4096: inside the declared scope, over what the
        # rule lets a choice hold
        diags = FB.verify_static_mlp(2048, 4096, 14336, "bfloat16",
                                     block_t=1024, block_f=128)
        assert not error_codes_of(diags)
        assert kv.VMEM_OVER_BUDGET in codes_of(diags)

    def test_the_traced_call_is_checked_against_what_it_asks_for(self):
        import paddle_tpu.analysis as analysis
        S = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
        report = analysis.check(
            lambda *a: FB.fused_mlp(*a, interpret=False, autotune=False),
            S(512, 4096), S(4096, 2048), S(4096, 2048), S(2048, 4096),
            passes=["kernel-verify"])
        assert report.by_pass("kernel-verify"), report.format()
        assert not report.errors(), report.format()


# ---------------------------------------------------------------------------
# autotune pruning (satellite: verify-before-bench)


class TestAutotunePruning:
    def test_quant_sub_quantum_row_blocks_are_pruned(self):
        """Acceptance: >= 1 illegal config class provably pruned — bf16
        activations at block_t=8 (sublane quantum is 16) never reach a
        benchmark."""
        from paddle_tpu.ops.pallas import autotune as at
        shape = (16, 1024, 1024, "int8", "bfloat16")
        cands = at._quant_candidates(*shape)
        assert any(bt == 8 for bt, _ in cands)    # the class exists...
        kept, n_pruned = kv.prune_candidates("quant_matmul", shape, cands)
        assert n_pruned == sum(bt == 8 for bt, _ in cands) > 0
        assert all(bt != 8 for bt, _ in kept)     # ...and is gone
        assert kept                                # but the set survives

    def test_prune_never_returns_empty(self):
        shape = (16, 1024, 1024, "int8", "bfloat16")
        only_bad = [(8, 128), (8, 256)]
        kept, n_pruned = kv.prune_candidates("quant_matmul", shape,
                                             only_bad)
        assert n_pruned == 2
        assert kept == only_bad    # wrongly-strict flag, not a crash

    def test_block_sizes_skip_pruned_candidates(self, monkeypatch,
                                                tmp_path):
        from paddle_tpu.ops.pallas import autotune as at
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "c.json"))
        at.reload()
        benched = []

        def fake(op_name, key, candidates, bench, default):
            benched.extend(candidates)
            return candidates[0]

        monkeypatch.setattr(at, "autotune", fake)
        at.quant_block_sizes(16, 1024, 1024, "int8", "bfloat16")
        assert benched and all(bt != 8 for bt, _ in benched)

    def test_ce_candidates_divide_odd_vocab(self):
        """Regression (satellite bugfix): enumerators must never emit a
        vocab block that does not divide V."""
        from paddle_tpu.ops.pallas import autotune as at
        from paddle_tpu.ops.pallas.cross_entropy import _default_blocks
        for t, v in [(64, 1000), (128, 4000), (64, 32000)]:
            for bt, bv in at._ce_candidates(t, v, "float32"):
                assert v % bv == 0, (t, v, bt, bv)
            assert v % _default_blocks(t, v)[1] == 0, (t, v)

    def test_default_quant_blocks_respect_sublane_quantum(self):
        from paddle_tpu.ops.pallas.quant_matmul import \
            _default_quant_blocks
        assert _default_quant_blocks(256, 1024, "bfloat16")[0] % 16 == 0
        # degenerate t keeps the old always-valid fallback
        assert _default_quant_blocks(8, 1024, "bfloat16") == (8, 512)

    def test_verify_only_sweep_exits_zero(self, capsys):
        from paddle_tpu.ops.pallas import autotune as at
        rc = at.main(["--sweep", "--verify-only", "--ops",
                      "quant_matmul,fused_ce"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "pruned_invalid=3" in out
        assert "0 timed" in out


# ---------------------------------------------------------------------------
# the registered pass over a traced program


class TestKernelVerifyPass:
    def test_registered_but_not_default(self):
        from paddle_tpu.analysis.passes import DEFAULT_PASSES, all_passes
        assert "kernel-verify" in all_passes()
        assert "kernel-verify" not in DEFAULT_PASSES
        assert len(DEFAULT_PASSES) == 5

    def test_traced_pallas_call_is_verified(self):
        import paddle_tpu.analysis as analysis
        from jax.experimental import pallas as pl

        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:] * 2.0

        def f(x):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32),
                interpret=True,
            )(x)

        report = analysis.check(
            f, jax.ShapeDtypeStruct((256, 128), jnp.float32),
            passes=["kernel-verify"])
        found = report.by_pass("kernel-verify")
        assert found, report.format()
        assert not report.errors(), report.format()

    def test_traced_bad_index_map_is_flagged(self):
        import paddle_tpu.analysis as analysis
        from jax.experimental import pallas as pl

        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def f(x):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((64, 128), lambda i: (i + 1, 0))],
                out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32),
                interpret=True,
            )(x)

        report = analysis.check(
            f, jax.ShapeDtypeStruct((256, 128), jnp.float32),
            passes=["kernel-verify"])
        msgs = [d.message for d in report.errors()]
        assert any(m.startswith(kv.OOB_BLOCK) for m in msgs), \
            report.format()

    def test_traced_paged_decode_pools_stay_in_hbm(self):
        """The paged decode kernel leaves its pools in HBM and DMAs
        them by hand: the pass must not count them as VMEM blocks."""
        import paddle_tpu.analysis as analysis
        from paddle_tpu.ops.pallas.paged_attention import \
            paged_decode_attention
        S = jax.ShapeDtypeStruct
        pool = S((3073, 16, 8, 128), jnp.bfloat16)     # 100 MB each
        report = analysis.check(
            lambda q, kp, vp, bt, ln: paged_decode_attention(
                q, kp, vp, bt, ln, interpret=True),
            S((32, 32, 128), jnp.bfloat16), pool, pool,
            S((32, 161), jnp.int32), S((32,), jnp.int32),
            passes=["kernel-verify"])
        assert report.by_pass("kernel-verify"), report.format()
        assert not report.errors() and not report.warnings(), \
            report.format()

    def test_program_without_pallas_is_informational(self):
        import paddle_tpu.analysis as analysis
        report = analysis.check(
            lambda x: x * 2, jax.ShapeDtypeStruct((8, 8), jnp.float32),
            passes=["kernel-verify"])
        assert not report.errors() and not report.warnings()
        assert any("no pallas_call" in d.message
                   for d in report.by_pass("kernel-verify"))


# ---------------------------------------------------------------------------
# observability + CLI


class TestSurface:
    def test_verify_metric_counts_verdicts(self):
        from paddle_tpu.observability import default_registry
        c = default_registry().counter(
            "paddle_tpu_kernel_verify_total",
            "static kernel verification outcomes",
            labelnames=("kernel", "verdict"))
        before = c.labels(kernel="rmsnorm_fwd", verdict="ok").value()
        from paddle_tpu.ops.pallas import rmsnorm as rn
        rn.verify_static(1024, 2048, "bfloat16")
        after = c.labels(kernel="rmsnorm_fwd", verdict="ok").value()
        assert after == before + 1

    def test_lint_kernels_cli(self, capsys):
        from paddle_tpu.analysis import lint
        rc = lint.main(["--kernels"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "fused_decoder" in out and "verdict" in out
        assert "LANE_CONCAT" in out and "SEQ_SCRATCH" in out

    def test_lint_kernels_strict_fails_on_decoder_warnings(self):
        from paddle_tpu.analysis import lint
        assert lint.main(["--kernels", "--strict"]) == 1
