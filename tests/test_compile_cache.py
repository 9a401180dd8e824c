"""Persistent AOT compile cache + model-artifact bundles (ROADMAP 5).

What must hold for a compiled-executable cache to be shippable:

* keys are stable across PROCESSES (a restarted worker addresses the
  same entry the dead one wrote) and sensitive to everything that
  changes the program (mesh, shardings, jax version, backend, config);
* a stale cache can never break (or silently corrupt) a boot — corrupt
  / truncated / wrong-version entries fall through to live compilation;
* a warm boot performs ZERO explicit XLA compiles and produces
  token-identical serving output;
* the bundle (weights + executables + tuned block sizes) round-trips.
"""

import json
import os
import pickle
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pp
from paddle_tpu import compile_cache as cc
from paddle_tpu.observability import default_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counter_total(name: str, suffix: str = "") -> float:
    m = default_registry().get(name)
    if m is None:
        return 0.0
    return sum(c.value() for k, c in m.series()
               if not suffix or "/".join(k).endswith(suffix))


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    d = tmp_path / "exe_cache"
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", "1")
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE_DIR", str(d))
    cc.reset_memory()
    yield str(d)
    cc.reset_memory()


def _tiny_step(seed=0):
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pp.seed(seed)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    opt = pp.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    step = TrainStep(model, opt)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (2, 17)).astype(np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    return model, step, batch


# ---------------------------------------------------------------- keys
class TestKeys:
    def test_key_deterministic_and_sensitive(self):
        k1 = cc.cache_key("t", "sig", extra="e")
        assert k1 == cc.cache_key("t", "sig", extra="e")
        assert k1 != cc.cache_key("t2", "sig", extra="e")
        assert k1 != cc.cache_key("t", "sig2", extra="e")
        assert k1 != cc.cache_key("t", "sig", extra="e2")

    def test_mesh_and_shardings_change_key(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                    ("dp", "tp"))
        mesh2 = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                     ("dp", "tp"))
        base = cc.cache_key("t", "sig")
        km = cc.cache_key("t", "sig", mesh=mesh)
        km2 = cc.cache_key("t", "sig", mesh=mesh2)
        assert len({base, km, km2}) == 3, \
            "mesh shape must be part of the address"
        sh1 = {"w": NamedSharding(mesh, P("dp"))}
        sh2 = {"w": NamedSharding(mesh, P("tp"))}
        ks1 = cc.cache_key("t", "sig", mesh=mesh, shardings=sh1)
        ks2 = cc.cache_key("t", "sig", mesh=mesh, shardings=sh2)
        assert ks1 != ks2, "sharding mismatch must be a MISS, not a hit"

    def test_model_config_tag_sees_baked_constants(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        pp.seed(0)
        m1 = LlamaForCausalLM(LlamaConfig.tiny())
        pp.seed(0)
        m2 = LlamaForCausalLM(LlamaConfig.tiny(rope_theta=123.0))
        # identical param avals, different rope tables baked at trace
        # time -> the config tag is what keeps them apart
        assert cc.model_config_tag(m1) != cc.model_config_tag(m2)

    @pytest.mark.slow  # subprocess boot; the CI cold-start gate runs it
    def test_key_stable_across_processes(self, tmp_path):
        """The content address a fresh process computes for the same
        TrainStep signature must equal ours — that IS the cache."""
        model, step, batch = _tiny_step()
        from paddle_tpu.observability.device_profiler import signature_of
        placed = step._place_batch(batch)
        lr = jnp.asarray(step.optimizer.get_lr(), jnp.float32)
        sig = signature_of(((step.params, step.opt_state, step.step_count,
                             placed, step._key, lr), {}))
        key = cc.cache_key("TrainStep(LlamaForCausalLM)", sig,
                           extra=step._cache_extra())
        script = textwrap.dedent("""
            import sys
            sys.path.insert(0, %r)
            sys.path.insert(0, %r)
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=8"
            import numpy as np
            import jax.numpy as jnp
            import paddle_tpu as pp
            from paddle_tpu import compile_cache as cc
            from paddle_tpu.jit import TrainStep
            from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
            from paddle_tpu.observability.device_profiler import \\
                signature_of
            pp.seed(0)
            model = LlamaForCausalLM(LlamaConfig.tiny())
            opt = pp.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
            step = TrainStep(model, opt)
            rng = np.random.default_rng(0)
            ids = rng.integers(0, 256, (2, 17)).astype(np.int32)
            batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
            placed = step._place_batch(batch)
            lr = jnp.asarray(step.optimizer.get_lr(), jnp.float32)
            sig = signature_of(((step.params, step.opt_state,
                                 step.step_count, placed, step._key, lr),
                                {}))
            print(cc.cache_key("TrainStep(LlamaForCausalLM)", sig,
                               extra=step._cache_extra()))
        """) % (REPO, os.path.join(REPO, "tests"))
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=300,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == key


# ---------------------------------------------------- entry validation
class TestCacheRoot:
    """One root, placeable from outside, for everything a later process
    can reuse."""

    def _paths(self):
        from paddle_tpu.observability import calibration
        from paddle_tpu.ops.pallas import autotune
        return (cc.cache_dir(), autotune.cache_path(),
                calibration.ledger_dir())

    def test_fixed_path_in_the_checkout_when_unset(self, monkeypatch):
        for var in ("JAX_COMPILATION_CACHE_DIR",
                    "PADDLE_TPU_COMPILE_CACHE_DIR",
                    "PADDLE_TPU_AUTOTUNE_CACHE",
                    "PADDLE_TPU_CALIBRATION_DIR"):
            monkeypatch.delenv(var, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        root = os.path.join(repo, ".paddle_tpu_cache")
        assert cc.cache_root() == root == cc.cache_root()   # never moves
        assert self._paths() == (
            os.path.join(root, "executables"),
            os.path.join(root, "autotune.json"),
            os.path.join(root, "calibration"))
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".paddle_tpu_cache/" in f.read().split()

    def test_env_places_the_root_and_no_other_dir_is_set(self, tmp_path,
                                                         monkeypatch):
        import jax
        for var in ("PADDLE_TPU_COMPILE_CACHE_DIR",
                    "PADDLE_TPU_AUTOTUNE_CACHE",
                    "PADDLE_TPU_CALIBRATION_DIR"):
            monkeypatch.delenv(var, raising=False)
        updates = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.__setitem__(k, v))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.enable_persistent_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
        assert all(p.startswith(str(tmp_path)) for p in self._paths())
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = cc.enable_persistent_cache()
        assert updates["jax_compilation_cache_dir"] == root
        assert root.endswith(".paddle_tpu_cache")

    def test_counts_come_from_jax_cache_events(self):
        before = cc.persistent_cache_counts()
        cc._on_jax_event("/jax/compilation_cache/compile_requests_use_cache")
        cc._on_jax_event("/jax/compilation_cache/compile_requests_use_cache")
        cc._on_jax_event("/jax/compilation_cache/cache_hits")
        after = cc.persistent_cache_counts()
        assert after["hits"] == before["hits"] + 1
        assert after["misses"] == before["misses"] + 1


class TestInvalidation:
    def _store_one(self, cache_env):
        f = jax.jit(lambda x: x * 3 + 1)
        x = jnp.ones((16,), jnp.float32)
        compiled, info, hit = cc.aot_compile_cached(f, x, target="inv")
        assert not hit
        files = [n for n in os.listdir(cache_env) if n.endswith(".exe")]
        assert len(files) == 1
        return f, x, os.path.join(cache_env, files[0])

    def test_truncated_entry_falls_through(self, cache_env):
        f, x, path = self._store_one(cache_env)
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[: len(raw) // 3])
        cc.reset_memory()
        compiled, info, hit = cc.aot_compile_cached(f, x, target="inv")
        assert not hit and not info.cached       # live compile
        assert float(compiled(x)[0]) == 4.0
        assert not os.path.exists(path) or \
            os.path.getsize(path) > len(raw) // 3  # stale file replaced

    def test_corrupt_payload_counts_deserialize_error(self, cache_env):
        f, x, path = self._store_one(cache_env)
        entry = pickle.load(open(path, "rb"))
        entry["payload"] = entry["payload"][: len(entry["payload"]) // 2]
        pickle.dump(entry, open(path, "wb"))
        cc.reset_memory()
        before = _counter_total("paddle_tpu_compile_cache_total",
                                "deserialize_error")
        compiled, info, hit = cc.aot_compile_cached(f, x, target="inv")
        after = _counter_total("paddle_tpu_compile_cache_total",
                               "deserialize_error")
        assert not hit
        assert after == before + 1
        assert float(compiled(x)[0]) == 4.0      # boot survived

    def test_wrong_jax_version_is_a_miss(self, cache_env):
        f, x, path = self._store_one(cache_env)
        entry = pickle.load(open(path, "rb"))
        entry["jax_version"] = "0.0.1"
        pickle.dump(entry, open(path, "wb"))
        cc.reset_memory()
        compiled, info, hit = cc.aot_compile_cached(f, x, target="inv")
        assert not hit and not info.cached

    def test_wrong_backend_is_a_miss(self, cache_env):
        f, x, path = self._store_one(cache_env)
        entry = pickle.load(open(path, "rb"))
        entry["backend"] = "tpu:TPU_v5_lite:n8"   # CPU must never serve it
        pickle.dump(entry, open(path, "wb"))
        cc.reset_memory()
        compiled, info, hit = cc.aot_compile_cached(f, x, target="inv")
        assert not hit and not info.cached

    def test_disabled_writes_nothing(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", "0")
        monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE_DIR",
                           str(tmp_path / "off"))
        cc.reset_memory()
        f = jax.jit(lambda x: x + 1)
        compiled, info, hit = cc.aot_compile_cached(
            f, jnp.ones((4,)), target="off")
        assert not hit
        assert not os.path.isdir(str(tmp_path / "off")) or \
            not os.listdir(str(tmp_path / "off"))


# ------------------------------------------------------------ TrainStep
class TestTrainStepCache:
    def test_compile_hits_and_matches_live_loss(self, cache_env):
        model, step, batch = _tiny_step()
        info = step.compile(batch)
        assert not info.cached
        live_loss = float(step(batch))
        before = _counter_total("paddle_tpu_compile_total")
        cc.reset_memory()
        model2, step2, batch2 = _tiny_step()
        info2 = step2.compile(batch2)
        assert info2.cached, "second process-equivalent boot must hit"
        assert _counter_total("paddle_tpu_compile_total") == before, \
            "a cache hit must not perform an explicit XLA compile"
        from paddle_tpu.observability.tracing import tracer
        names = {s["name"] for s in tracer().finished_spans()}
        assert "compile.cache_hit" in names, \
            "the hit must run under its tracer span"
        assert float(step2(batch2)) == live_loss

    def test_plain_call_adopts_cached_executable(self, cache_env):
        model, step, batch = _tiny_step()
        step.compile(batch)
        live_loss = float(step(batch))
        cc.reset_memory()
        model2, step2, batch2 = _tiny_step()
        # never calls compile(): the first __call__ probes the cache
        loss = float(step2(batch2))
        assert step2._compiled is not None, \
            "transparent cold-start adoption must install the executable"
        assert loss == live_loss


# -------------------------------------------------------------- serving
def _engine(model):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    return ContinuousBatchingEngine(model, slots=2, max_len=64,
                                    prefill_buckets=(16,))


class TestServingWarmup:
    def test_cached_vs_live_token_identical(self, cache_env):
        model, _, _ = _tiny_step()
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, 256, (7,)).astype(np.int32)

        with _engine(model) as eng:
            stats = eng.aot_warmup()
            assert set(stats) == {"serving.decode",
                                  "serving.prefill_chunk[16]"}
            rid = eng.add_request(prompt, max_new_tokens=6)
            live = eng.run()[rid][1]

        cc.reset_memory()
        before = _counter_total("paddle_tpu_compile_total")
        with _engine(model) as eng2:
            stats2 = eng2.aot_warmup()
            assert set(stats2) == set(stats)
            assert _counter_total("paddle_tpu_compile_total") == before, \
                "warm-cache warmup must perform zero XLA compiles"
            assert eng2._decode_compiled is not None
            assert eng2._prefill_chunk_compiled is not None
            rid = eng2.add_request(prompt, max_new_tokens=6)
            cached = eng2.run()[rid][1]
        assert cached == live, "cached executables changed the tokens"

    def test_paged_warmup_round_trips(self, cache_env):
        model, _, _ = _tiny_step()
        from paddle_tpu.inference.serving import ContinuousBatchingEngine

        def build():
            return ContinuousBatchingEngine(
                model, slots=2, max_len=64, prefill_buckets=(16,),
                kv_block_size=8, prefill_chunk=16, spec_decode=2)
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, 256, (9,)).astype(np.int32)
        with build() as eng:
            stats = eng.aot_warmup()
            assert "serving.prefill_chunk[16]" in stats
            assert "serving.spec_verify" in stats
            rid = eng.add_request(prompt, max_new_tokens=5)
            live = eng.run()[rid][1]
        cc.reset_memory()
        before = _counter_total("paddle_tpu_compile_total")
        with build() as eng2:
            assert set(eng2.aot_warmup()) == set(stats)
            assert _counter_total("paddle_tpu_compile_total") == before
            rid = eng2.add_request(prompt, max_new_tokens=5)
            assert eng2.run()[rid][1] == live

    def test_recover_consults_cache_after_fault(self, cache_env):
        """Chaos: an engine that was NEVER warmed takes an engine-step
        fault; _recover must come back holding the cached executables
        (zero-compile restart-after-fault boot)."""
        from paddle_tpu import robustness
        model, _, _ = _tiny_step()
        with _engine(model) as warmer:
            warmer.aot_warmup()              # populate the cache
        cc.reset_memory()
        before = _counter_total("paddle_tpu_compile_total")
        rng = np.random.default_rng(3)
        robustness.reset_registry()
        try:
            with _engine(model) as eng:
                assert eng._decode_compiled is None
                rid = eng.add_request(rng.integers(0, 256, (5,)),
                                      max_new_tokens=4)
                eng.step()                   # admission + prefill
                robustness.inject("serving.engine_step", times=1)
                eng.step()                   # fault fires -> _recover
                assert eng.request_status(rid) == "error"
                assert eng._decode_compiled is not None, \
                    "_recover must adopt cached executables"
                assert _counter_total(
                    "paddle_tpu_compile_total") == before
                # the engine still serves, through the cached programs
                rid2 = eng.add_request(rng.integers(0, 256, (5,)),
                                       max_new_tokens=3)
                out = eng.run()
                assert len(out[rid2][1]) >= 1
        finally:
            robustness.reset_registry()


# --------------------------------------------------------------- bundle
class TestBundle:
    def test_round_trip(self, cache_env, tmp_path, monkeypatch):
        from paddle_tpu.ops.pallas import autotune as at
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_SEED", "0")
        at.reload()
        at._put("flash", "bundle-test-key@cpu-interpret", (128, 128))
        at._save()

        f = jax.jit(lambda x: x * 2)
        x = jnp.ones((8,), jnp.float32)
        cc.aot_compile_cached(f, x, target="bundle.exe")
        weights = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
                   "b": np.ones((3,), np.float32)}

        out = tmp_path / "artifact"
        man = cc.bundle(str(out), state_dict=weights)
        assert man["checkpoint"] == "checkpoint"
        assert len(man["executables"]) == 1
        assert man["autotune_entries"] >= 1
        assert os.path.exists(out / "MANIFEST.json")

        # fresh machine: empty caches, load the bundle
        dest = tmp_path / "dest_cache"
        monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE_DIR", str(dest))
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at2.json"))
        at.reload()
        cc.reset_memory()
        res = cc.load_bundle(str(out))
        assert res["installed"] == ["bundle.exe"]
        assert res["autotune_entries"] >= 1
        np.testing.assert_array_equal(res["state_dict"]["w"],
                                      weights["w"])
        # installed executable actually serves
        compiled, info, hit = cc.aot_compile_cached(f, x,
                                                    target="bundle.exe")
        assert hit and info.cached
        assert float(compiled(x).sum()) == 16.0
        # tuned block sizes visible through the autotune cache
        assert "flash|bundle-test-key@cpu-interpret" in at.cached_entries()
        at.reload()

    def test_load_bundle_rejects_garbage(self, tmp_path):
        with pytest.raises(ValueError):
            cc.load_bundle(str(tmp_path / "nope"))
        bad = tmp_path / "bad"
        bad.mkdir()
        (bad / "MANIFEST.json").write_text('{"schema": 999}')
        with pytest.raises(ValueError):
            cc.load_bundle(str(bad))

    def test_cli_stats_and_clear(self, cache_env, capsys):
        f = jax.jit(lambda x: x + 5)
        cc.aot_compile_cached(f, jnp.ones((4,)), target="cli")
        assert cc.main(["stats"]) == 0
        out = capsys.readouterr().out
        assert "cli" in out
        assert cc.main(["clear"]) == 0
        assert cc.cached_entries() == []


# -------------------------------------------------------------- elastic
class TestElasticRestart:
    @pytest.mark.slow  # two worker-process boots; CI gate runs it
    def test_generation_restart_boots_from_cache(self, tmp_path):
        """Elastic chaos: generation 0 compiles (populating the cache)
        and dies; the restarted generation must boot its TrainStep with
        ZERO explicit XLA compiles — the restart-after-fault cold start
        ROADMAP 5 promises."""
        from paddle_tpu.distributed.elastic import ElasticManager
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""
            import json, os, sys
            sys.path.insert(0, %r)
            sys.path.insert(0, %r)
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=8"
            import numpy as np
            import paddle_tpu as pp
            from paddle_tpu.distributed import ElasticAgent
            from paddle_tpu.jit import TrainStep
            from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
            from paddle_tpu.observability import default_registry
            agent = ElasticAgent(interval=0.2)
            gen = int(os.environ["PADDLE_ELASTIC_GEN"])
            pp.seed(0)
            model = LlamaForCausalLM(LlamaConfig.tiny())
            opt = pp.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
            step = TrainStep(model, opt)
            rng = np.random.default_rng(0)
            ids = rng.integers(0, 256, (2, 17)).astype(np.int32)
            batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
            info = step.compile(batch)
            loss = float(step(batch))
            m = default_registry().get("paddle_tpu_compile_total")
            compiles = sum(c.value() for _k, c in m.series()) if m else 0
            out = sys.argv[1]
            with open(os.path.join(out, f"gen{gen}.json"), "w") as f:
                json.dump({"cached": bool(info.cached), "loss": loss,
                           "compiles": compiles}, f)
            agent.stop()
            os._exit(1 if gen == 0 else 0)
        """) % (REPO, os.path.join(REPO, "tests")))
        env = {
            "PYTHONPATH": REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", ""),
            "PADDLE_TPU_COMPILE_CACHE": "1",
            "PADDLE_TPU_COMPILE_CACHE_DIR": str(tmp_path / "cache"),
        }
        mgr = ElasticManager(
            [sys.executable, str(script), str(tmp_path)], nproc=1,
            max_restarts=2, env=env)
        try:
            rc = mgr.run()
        finally:
            mgr.close()
        assert rc == 0
        g0 = json.load(open(tmp_path / "gen0.json"))
        g1 = json.load(open(tmp_path / "gen1.json"))
        assert g0["cached"] is False and g0["compiles"] >= 1
        assert g1["cached"] is True, \
            "restarted generation must hit the executable cache"
        assert g1["compiles"] == 0, \
            "restarted generation must perform zero XLA compiles"
        assert g1["loss"] == g0["loss"]
