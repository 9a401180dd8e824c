"""Chaos tests for the robustness subsystem (ISSUE 4): every scenario
injects its fault THROUGH the fault registry and asserts the system
recovers — fault-registry semantics, corrupted/truncated-shard restore
fallback, NaN skip-step (params bitwise-unchanged + metric + K-skip
raise), SIGTERM graceful drain of a single-node elastic run, TCP-store
retry, dataloader worker-crash surfacing, and serving deadline /
admission-reject / engine-recovery paths."""

import glob
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as pp
from paddle_tpu import robustness
from paddle_tpu.distributed.checkpoint import (AutoCheckpoint,
                                               load_state_dict,
                                               save_state_dict,
                                               validate_checkpoint)
from paddle_tpu.observability import default_registry
from paddle_tpu.robustness import (FaultRegistry, InjectedFault,
                                   NonFiniteStepError, QueueFullError,
                                   clear_faults, fault_fires, fault_point,
                                   fault_stats, inject)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends with a disarmed registry — injected
    faults must never leak across tests."""
    clear_faults()
    yield
    clear_faults()


# ---------------------------------------------------------------------------
# fault registry semantics
# ---------------------------------------------------------------------------
class TestFaultRegistry:
    def test_disarmed_points_are_noops(self):
        fault_point("nonexistent.point")          # must not raise
        assert fault_fires("nonexistent.point") is False

    def test_fire_counting_nth_and_times(self):
        reg = FaultRegistry()
        reg.inject("p", nth=2, times=2)
        fired = [reg.should_fire("p") for _ in range(5)]
        # call 1 skipped (nth=2), calls 2-3 fire (times=2), rest exhausted
        assert fired == [False, True, True, False, False]
        assert reg.stats("p") == {"calls": 5, "fires": 2}

    def test_probability_is_seeded(self):
        a = FaultRegistry(seed=7)
        b = FaultRegistry(seed=7)
        a.inject("p", probability=0.5)
        b.inject("p", probability=0.5)
        seq_a = [a.should_fire("p") for _ in range(32)]
        seq_b = [b.should_fire("p") for _ in range(32)]
        assert seq_a == seq_b
        assert any(seq_a) and not all(seq_a)

    def test_env_configuration_round_trip(self):
        reg = FaultRegistry()
        reg.configure("a.b:n=3:times=1, c.d:p=0.25 ,e.f:action=exit")
        specs = {s.point: s for s in reg.specs()}
        assert specs["a.b"].nth == 3 and specs["a.b"].times == 1
        assert specs["c.d"].probability == 0.25
        assert specs["e.f"].action == "exit"

    def test_malformed_env_rejected(self):
        reg = FaultRegistry()
        with pytest.raises(ValueError):
            reg.configure("a.b:frequency=2")
        with pytest.raises(ValueError):
            reg.configure("a.b:n")
        with pytest.raises(ValueError):
            reg.inject("x", action="explode")

    def test_fault_point_raises_injected_fault(self):
        inject("unit.point", times=1)
        with pytest.raises(InjectedFault):
            fault_point("unit.point")
        fault_point("unit.point")  # exhausted: back to no-op

    def test_firing_records_metric_and_flight_event(self):
        c = default_registry().counter("paddle_tpu_fault_injections_total",
                                       labelnames=("point",))
        before = c.labels(point="unit.metric").value()
        inject("unit.metric", times=1)
        assert fault_fires("unit.metric", extra="ctx")
        assert c.labels(point="unit.metric").value() == before + 1
        from paddle_tpu.observability import flight_recorder
        events = [e for e in flight_recorder().events()
                  if e["kind"] == "fault.injected"
                  and e.get("point") == "unit.metric"]
        assert events and events[-1]["extra"] == "ctx"

    def test_rearm_replaces_counters(self):
        inject("unit.rearm", times=1)
        assert fault_fires("unit.rearm")
        inject("unit.rearm", times=1)     # re-arm: fresh counters
        assert fault_stats("unit.rearm") == {"calls": 0, "fires": 0}
        assert fault_fires("unit.rearm")


# ---------------------------------------------------------------------------
# checkpoint integrity
# ---------------------------------------------------------------------------
def _state(v: float):
    return {"w": np.full((4, 3), v, np.float32),
            "b": np.arange(3, dtype=np.float32)}


class TestCheckpointIntegrity:
    def test_digests_written_and_validated(self, tmp_path):
        d = str(tmp_path)
        save_state_dict(_state(1.0), d)
        idx = json.load(open(glob.glob(os.path.join(d,
                                                    "index.*.json"))[0]))
        for tmeta in idx["tensors"].values():
            for sh in tmeta["shards"]:
                assert "crc32" in sh and "bytes" in sh
        assert validate_checkpoint(d)

    def test_bit_flip_caught_by_crc(self, tmp_path):
        """Same-size corruption: the size check passes, crc32 must not."""
        d = str(tmp_path)
        save_state_dict(_state(1.0), d)
        shard = glob.glob(os.path.join(d, "*.shard*.npy"))[0]
        with open(shard, "r+b") as f:
            f.seek(os.path.getsize(shard) - 3)
            byte = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([byte[0] ^ 0xFF]))
        assert validate_checkpoint(d) is False
        assert validate_checkpoint(d, verify_digests=False) is True

    def test_torn_shard_fault_fails_validation(self, tmp_path):
        d = str(tmp_path)
        inject("checkpoint.torn_shard", times=1)
        save_state_dict(_state(1.0), d)
        assert fault_stats("checkpoint.torn_shard")["fires"] == 1
        assert validate_checkpoint(d) is False

    def test_crash_before_publish_leaves_no_final_shard(self, tmp_path):
        d = str(tmp_path)
        inject("checkpoint.shard_write", times=1)
        with pytest.raises(InjectedFault):
            save_state_dict(_state(1.0), d)
        clear_faults()
        # atomic write: the half-save left a tmp orphan, no final file
        assert glob.glob(os.path.join(d, "*.tmp.*"))
        assert validate_checkpoint(d) is False
        # the next save purges the orphan and completes
        save_state_dict(_state(2.0), d)
        assert not glob.glob(os.path.join(d, "*.tmp.*"))
        assert validate_checkpoint(d)
        out = load_state_dict(d)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      _state(2.0)["w"])

    def test_unparseable_index_returns_false(self, tmp_path):
        d = str(tmp_path)
        save_state_dict(_state(1.0), d)
        idx = glob.glob(os.path.join(d, "index.*.json"))[0]
        with open(idx, "w") as f:
            f.write('{"tensors": {"w": {"global_')   # truncated JSON
        assert validate_checkpoint(d) is False        # no raise

    def test_predigest_checkpoints_still_validate(self, tmp_path):
        """Checkpoints written before digests existed (no crc32/bytes
        keys) must stay loadable and valid."""
        d = str(tmp_path)
        save_state_dict(_state(3.0), d)
        idx_file = glob.glob(os.path.join(d, "index.*.json"))[0]
        idx = json.load(open(idx_file))
        for tmeta in idx["tensors"].values():
            for sh in tmeta["shards"]:
                sh.pop("crc32", None)
                sh.pop("bytes", None)
                sh.pop("sha256", None)
        json.dump(idx, open(idx_file, "w"))
        assert validate_checkpoint(d)
        out = load_state_dict(d)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      _state(3.0)["w"])

    def test_restore_falls_back_to_newest_valid(self, tmp_path):
        """Acceptance: the torn write is injected THROUGH the registry
        into the newest save; restore resumes from the newest VALID
        step.  Each save writes 2 shards (w, b) sequentially, so shard
        write #5 is step 3's first shard."""
        ck = AutoCheckpoint(str(tmp_path), keep=3, save_interval_steps=1)
        inject("checkpoint.torn_shard", nth=5, times=1)
        for s in (1, 2, 3):
            ck.maybe_save(s, _state(float(s)))
        ck._pending.wait()
        assert fault_stats("checkpoint.torn_shard")["fires"] == 1
        assert validate_checkpoint(
            os.path.join(str(tmp_path), "step_000000000003")) is False
        assert ck.latest_step() == 2
        step, state = ck.restore_latest()
        assert step == 2
        np.testing.assert_array_equal(np.asarray(state["w"]),
                                      _state(2.0)["w"])

    def test_restore_falls_back_past_posthoc_corruption(self, tmp_path):
        """Bit-rot after a clean save (no fault point involved) is also
        caught at restore time and skipped."""
        ck = AutoCheckpoint(str(tmp_path), keep=3, save_interval_steps=1)
        for s in (1, 2):
            ck.maybe_save(s, _state(float(s)))
        ck._pending.wait()
        shard = glob.glob(os.path.join(
            str(tmp_path), "step_000000000002", "*.shard*.npy"))[0]
        with open(shard, "r+b") as f:
            f.truncate(os.path.getsize(shard) // 2)
        step, state = ck.restore_latest()
        assert step == 1
        np.testing.assert_array_equal(np.asarray(state["w"]),
                                      _state(1.0)["w"])

    def test_save_now_is_synchronous_and_durable(self, tmp_path):
        ck = AutoCheckpoint(str(tmp_path), keep=2, save_interval_steps=10)
        ck.maybe_save(10, _state(1.0))        # async save in flight
        ck.save_now(11, _state(7.0))          # must wait + write sync
        assert ck.latest_step() == 11
        assert validate_checkpoint(os.path.join(str(tmp_path),
                                                "step_000000000011"))

    @pytest.mark.slow  # subprocess drill; CI recovery gate runs it
    def test_async_save_racing_a_kill_never_half_indexed(self, tmp_path):
        """An ``_AsyncSave`` in flight when the generation dies must
        leave only tmp orphans (purged by the next save) or a complete
        step — never a half-indexed step that ``restore_latest``
        accepts.  The kill rides ``checkpoint.shard_write`` with
        ``action=exit``: the writer thread hard-exits the process
        mid-save, after some shards published but before the index."""
        import subprocess
        import sys as _sys
        import textwrap as _tw
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = tmp_path / "victim.py"
        script.write_text(_tw.dedent("""
            import os, sys
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["PADDLE_TPU_FAULTS"] = \\
                "checkpoint.shard_write:n=3:action=exit"
            import numpy as np
            from paddle_tpu.distributed.checkpoint import AutoCheckpoint
            ck = AutoCheckpoint(sys.argv[1], keep=3,
                                save_interval_steps=1)
            state = {f"w{i}": np.full((256,), float(i), np.float32)
                     for i in range(8)}
            pending = ck.maybe_save(1, state)
            pending.wait()   # unreachable: the writer hard-exits first
            sys.exit(0)
        """))
        ckpt_dir = str(tmp_path / "ckpt")
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run([_sys.executable, str(script), ckpt_dir],
                              env=env, capture_output=True, timeout=120)
        assert proc.returncode == 13, proc.stderr.decode()[-2000:]
        step_dir = os.path.join(ckpt_dir, "step_000000000001")
        # some shards were published, so the dir exists and is partial
        assert os.path.isdir(step_dir)
        assert not validate_checkpoint(step_dir)
        ck = AutoCheckpoint(ckpt_dir, keep=3, save_interval_steps=1)
        assert ck.latest_step() is None
        assert ck.restore_latest() == (None, None)
        # a fresh save at the same step purges the wreck (tmp orphans
        # included) and produces a complete, restorable checkpoint
        state = {f"w{i}": np.full((256,), float(i), np.float32)
                 for i in range(8)}
        ck.save_now(1, state)
        assert validate_checkpoint(step_dir)
        import glob as _glob
        assert not _glob.glob(os.path.join(step_dir, "*.tmp.*"))
        step, out = ck.restore_latest()
        assert step == 1
        np.testing.assert_array_equal(np.asarray(out["w3"]),
                                      np.full((256,), 3.0, np.float32))


# ---------------------------------------------------------------------------
# TrainStep non-finite step-guard
# ---------------------------------------------------------------------------
def _mean_prod_loss(out, y):
    data = out._data if hasattr(out, "_data") else out
    return (data * y).mean()


def _snapshot(step):
    import jax
    return ({n: np.asarray(a) for n, a in step.params.items()},
            jax.tree.map(np.asarray, step.opt_state))


class TestStepGuard:
    def _make_step(self, **kw):
        from paddle_tpu.jit import TrainStep
        pp.seed(0)
        lin = pp.nn.Linear(4, 2)
        opt = pp.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=lin.parameters())
        return TrainStep(lin, opt, loss_fn=_mean_prod_loss, **kw)

    def _batches(self):
        good = (np.ones((2, 4), np.float32), np.ones((2, 2), np.float32))
        return good

    def test_nan_step_skipped_params_bitwise_unchanged(self):
        import jax
        step = self._make_step()
        good = self._batches()
        step(good)
        params0, opt0 = _snapshot(step)
        sc0 = int(step.step_count)
        c = default_registry().counter(
            "paddle_tpu_train_step_skipped_total", labelnames=("reason",))
        before = c.labels(reason="nonfinite_loss").value()

        # acceptance: the NaN microbatch is injected THROUGH the registry
        inject("train.nonfinite_batch", times=1)
        loss = step(good)
        assert fault_stats("train.nonfinite_batch")["fires"] == 1
        assert not np.isfinite(float(loss))
        params1, opt1 = _snapshot(step)
        for n in params0:
            np.testing.assert_array_equal(params0[n], params1[n])
        jax.tree.map(np.testing.assert_array_equal, opt0, opt1)
        assert int(step.step_count) == sc0
        assert c.labels(reason="nonfinite_loss").value() == before + 1

        # training continues: the next good batch applies normally
        step(good)
        assert int(step.step_count) == sc0 + 1
        params2, _ = _snapshot(step)
        assert any(not np.array_equal(params1[n], params2[n])
                   for n in params1)
        assert step._skip_streak == 0

    def test_k_consecutive_skips_raise(self):
        step = self._make_step(max_consecutive_skips=3)
        good = self._batches()
        step(good)
        params0, _ = _snapshot(step)
        inject("train.nonfinite_batch")     # every batch poisoned
        with pytest.raises(NonFiniteStepError):
            for _ in range(10):
                step(good)
        assert step._skip_streak == 3
        params1, _ = _snapshot(step)
        for n in params0:                   # still untouched after raise
            np.testing.assert_array_equal(params0[n], params1[n])

    def test_guard_disabled_applies_nan(self):
        """The escape hatch: guard off means the old (unprotected)
        behavior — NaN propagates into params."""
        step = self._make_step(guard_nonfinite=False)
        bad = (np.full((2, 4), np.nan, np.float32),
               np.ones((2, 2), np.float32))
        step(bad)
        assert any(np.isnan(np.asarray(a)).any()
                   for a in step.params.values())

    def test_env_knob_disables_guard(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_STEP_GUARD", "0")
        step = self._make_step()
        assert step._guard_nonfinite is False


# ---------------------------------------------------------------------------
# TCP store retry
# ---------------------------------------------------------------------------
class TestTcpStoreRetry:
    def test_connect_retries_until_late_master(self):
        import threading
        from paddle_tpu.distributed.elastic import free_port
        from paddle_tpu.distributed.tcp_store import TCPStore
        port = free_port()
        holder = {}

        def start_master_late():
            time.sleep(0.7)
            holder["master"] = TCPStore("127.0.0.1", port, is_master=True)

        t = threading.Thread(target=start_master_late)
        t.start()
        try:
            # the satellite's contract: a joining rank beats rank-0's
            # store to the socket and must connect anyway, not crash
            client = TCPStore("127.0.0.1", port, is_master=False,
                              connect_timeout=15.0)
            client.set("k", b"v")
            assert client.get("k", wait=False) == b"v"
            client.close()
        finally:
            t.join()
            holder["master"].close()

    def test_injected_connect_failures_retried(self):
        from paddle_tpu.distributed.elastic import free_port
        from paddle_tpu.distributed.tcp_store import TCPStore
        port = free_port()
        master = TCPStore("127.0.0.1", port, is_master=True)
        c = default_registry().counter(
            "paddle_tpu_tcp_store_connect_retries_total")
        before = c.value()
        try:
            inject("tcp_store.connect", times=2)
            client = TCPStore("127.0.0.1", port, is_master=False,
                              connect_timeout=15.0)
            assert fault_stats("tcp_store.connect")["fires"] == 2
            assert c.value() == before + 2
            client.set("x", b"1")
            client.close()
        finally:
            master.close()

    def test_injected_op_failure_retried_with_metric(self):
        from paddle_tpu.distributed.elastic import free_port
        from paddle_tpu.distributed.tcp_store import TCPStore
        port = free_port()
        store = TCPStore("127.0.0.1", port, is_master=True)
        c = default_registry().counter(
            "paddle_tpu_tcp_store_op_retries_total", labelnames=("op",))
        before = c.labels(op="set").value()
        try:
            inject("tcp_store.op", times=1)
            store.set("k", b"v")              # first attempt fails, retried
            assert store.get("k", wait=False) == b"v"
            assert c.labels(op="set").value() == before + 1
        finally:
            store.close()

    def test_add_token_dedup_applies_once(self):
        """The double-count hazard the op-id token kills: an add whose
        response was lost retried with the SAME token must replay the
        recorded result, never re-apply the delta."""
        from paddle_tpu.distributed.elastic import free_port
        from paddle_tpu.distributed.tcp_store import TCPStore
        store = TCPStore("127.0.0.1", free_port(), is_master=True)
        try:
            assert store.add("cnt", 5) == 5
            # simulate: first round-trip applied server-side, response
            # lost on the wire, client resends the identical op id
            assert store._add_once("cnt", 5, "op-abc") == 10
            assert store._add_once("cnt", 5, "op-abc") == 10
            assert store.add("cnt", 0) == 10
            # a DIFFERENT op id is a genuinely new add
            assert store._add_once("cnt", 5, "op-def") == 15
        finally:
            store.close()

    def test_retried_add_counts_once(self):
        """``add`` now rides the PR-4 bounded retry (previously
        excluded): an injected failure is retried and the counter moves
        exactly once."""
        from paddle_tpu.distributed.elastic import free_port
        from paddle_tpu.distributed.tcp_store import TCPStore
        store = TCPStore("127.0.0.1", free_port(), is_master=True)
        c = default_registry().counter(
            "paddle_tpu_tcp_store_op_retries_total", labelnames=("op",))
        before = c.labels(op="add").value()
        try:
            inject("tcp_store.op", times=1)
            assert store.add("cnt2", 7) == 7   # attempt 1 fails, retried
            assert c.labels(op="add").value() == before + 1
            assert store.add("cnt2", 0) == 7   # counted exactly once
        finally:
            store.close()

    def test_barrier_still_counts_correctly(self):
        from paddle_tpu.distributed.elastic import free_port
        from paddle_tpu.distributed.tcp_store import TCPStore
        store = TCPStore("127.0.0.1", free_port(), is_master=True,
                         world_size=1)
        try:
            store.barrier("b1")
            assert store.add("__b1_count", 0) == 1
        finally:
            store.close()


# ---------------------------------------------------------------------------
# preemption-aware elastic
# ---------------------------------------------------------------------------
_DRAIN_WORKER = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from paddle_tpu.distributed import AutoCheckpoint, ElasticAgent

    agent = ElasticAgent(interval=0.1)
    ckpt_dir = sys.argv[1]
    ckpt = AutoCheckpoint(ckpt_dir, keep=2, save_interval_steps=10_000)
    state = {"w": np.zeros((4,), np.float32)}
    for step in range(1, 100_000):
        state = {"w": state["w"] + 1.0}
        time.sleep(0.05)
        if agent.draining:
            # acceptance: SIGTERM produces a FINAL synchronous checkpoint
            if agent.rank == 0:
                ckpt.save_now(step, state)
            agent.stop()
            sys.exit(0)
    sys.exit(5)
""")

_DRAIN_MANAGER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    from paddle_tpu.distributed.elastic import ElasticManager
    env = {"PYTHONPATH": %(repo)r + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    mgr = ElasticManager([sys.executable, sys.argv[1], sys.argv[2]],
                         nproc=2, max_restarts=1, heartbeat_timeout=30.0,
                         drain_timeout=20.0, env=env)
    try:
        rc = mgr.run()
    finally:
        mgr.close()
    sys.exit(rc)
""")


@pytest.mark.slow  # subprocess/sleep drills; CI chaos gate runs them
class TestGracefulDrain:
    def test_sigterm_drains_with_final_checkpoint_and_exit_0(self,
                                                             tmp_path):
        """Acceptance: SIGTERM → final checkpoint + exit code 0."""
        ckpt_dir = str(tmp_path / "ckpt")
        os.makedirs(ckpt_dir)
        worker = tmp_path / "worker.py"
        worker.write_text(_DRAIN_WORKER)
        manager = tmp_path / "mgr.py"
        manager.write_text(_DRAIN_MANAGER % {"repo": REPO})
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, str(manager), str(worker), ckpt_dir],
            env=env)
        try:
            time.sleep(5.0)                  # let workers reach the loop
            assert proc.poll() is None, "manager died before drain"
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=60)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        assert rc == 0, "graceful drain must exit 0"
        ck = AutoCheckpoint(ckpt_dir)
        final = ck.latest_step()
        assert final is not None and final >= 1
        _, state = ck.restore_latest()
        np.testing.assert_array_equal(
            np.asarray(state["w"]), np.full((4,), float(final),
                                            np.float32))

    def test_agent_sees_store_drain_flag(self):
        from paddle_tpu.distributed.elastic import (ElasticAgent,
                                                    free_port)
        from paddle_tpu.distributed.tcp_store import TCPStore
        port = free_port()
        master = TCPStore("127.0.0.1", port, is_master=True)
        try:
            os.environ["PADDLE_ELASTIC_STORE"] = f"127.0.0.1:{port}"
            os.environ["PADDLE_ELASTIC_GEN"] = "0"
            os.environ["PADDLE_TRAINER_ID"] = "0"
            agent = ElasticAgent(interval=0.05, handle_signals=False)
            assert agent.draining is False
            master.set("elastic/drain", b"1")
            deadline = time.time() + 5.0
            while not agent.draining and time.time() < deadline:
                time.sleep(0.02)
            assert agent.draining, "drain flag not observed"
            agent.stop()
        finally:
            for k in ("PADDLE_ELASTIC_STORE", "PADDLE_ELASTIC_GEN",
                      "PADDLE_TRAINER_ID"):
                os.environ.pop(k, None)
            master.close()

    def test_heartbeat_fault_suppresses_beat(self):
        from paddle_tpu.distributed.elastic import (ElasticAgent,
                                                    free_port)
        from paddle_tpu.distributed.tcp_store import TCPStore
        port = free_port()
        master = TCPStore("127.0.0.1", port, is_master=True)
        try:
            os.environ["PADDLE_ELASTIC_STORE"] = f"127.0.0.1:{port}"
            os.environ["PADDLE_ELASTIC_GEN"] = "0"
            os.environ["PADDLE_TRAINER_ID"] = "3"
            agent = ElasticAgent(interval=10.0, handle_signals=False)
            first = master.get("hb/0/3", wait=False)
            inject("elastic.heartbeat")     # every subsequent beat lost
            agent._beat()
            agent._beat()
            assert master.get("hb/0/3", wait=False) == first
            assert fault_stats("elastic.heartbeat")["fires"] == 2
            agent.stop()
        finally:
            for k in ("PADDLE_ELASTIC_STORE", "PADDLE_ELASTIC_GEN",
                      "PADDLE_TRAINER_ID"):
                os.environ.pop(k, None)
            master.close()

    @pytest.mark.slow  # spawns generations; CI chaos gate runs it
    def test_circuit_breaker_opens_on_fast_failures(self, tmp_path):
        """Insta-crashing generations trip the breaker before the
        restart budget is exhausted."""
        from paddle_tpu.distributed.elastic import ElasticManager
        script = tmp_path / "dies.py"
        script.write_text(textwrap.dedent("""
            import os, sys
            sys.path.insert(0, %r)
            os.environ["JAX_PLATFORMS"] = "cpu"
            from paddle_tpu.distributed import ElasticAgent
            ElasticAgent(interval=0.2, handle_signals=False)
            os._exit(3)
        """) % REPO)
        env = {"PYTHONPATH": REPO + os.pathsep + os.environ.get(
            "PYTHONPATH", "")}
        mgr = ElasticManager([sys.executable, str(script)], nproc=1,
                             max_restarts=50, env=env,
                             backoff_base=0.05, backoff_max=0.2,
                             circuit_fast_failures=3,
                             circuit_min_uptime=30.0)
        t0 = time.time()
        try:
            rc = mgr.run()
        finally:
            mgr.close()
        assert rc == 1
        # breaker opened after 3 consecutive fast failures — nowhere
        # near the 50-restart budget
        assert mgr.restarts <= 4
        assert time.time() - t0 < 60


# ---------------------------------------------------------------------------
# dataloader worker crash
# ---------------------------------------------------------------------------
class _CrashDataset:
    def __len__(self):
        return 32

    def __getitem__(self, i):
        return np.full((3,), i, np.float32)


class TestDataLoaderWorkerCrash:
    def test_worker_hard_crash_raises_named_runtime_error(self):
        """Acceptance (satellite): an injected hard worker death surfaces
        as a RuntimeError naming the worker, not a hang."""
        from paddle_tpu.io.dataloader import DataLoader
        os.environ["PADDLE_TPU_FAULTS"] = \
            "io.dataloader.worker:n=2:times=1:action=exit"
        robustness.reset_registry()   # children re-read the env on fork
        try:
            dl = DataLoader(_CrashDataset(), batch_size=4, num_workers=2)
            with pytest.raises(RuntimeError, match="worker.*died|died"):
                list(dl)
        finally:
            os.environ.pop("PADDLE_TPU_FAULTS", None)
            robustness.reset_registry()

    def test_worker_soft_fault_propagates_exception(self):
        from paddle_tpu.io.dataloader import DataLoader
        os.environ["PADDLE_TPU_FAULTS"] = "io.dataloader.worker:times=1"
        robustness.reset_registry()
        try:
            dl = DataLoader(_CrashDataset(), batch_size=4, num_workers=2)
            with pytest.raises(InjectedFault):
                list(dl)
        finally:
            os.environ.pop("PADDLE_TPU_FAULTS", None)
            robustness.reset_registry()

    def test_no_fault_no_change(self):
        from paddle_tpu.io.dataloader import DataLoader
        dl = DataLoader(_CrashDataset(), batch_size=4, num_workers=2)
        batches = list(dl)
        assert len(batches) == 8
        dl.close()


# ---------------------------------------------------------------------------
# serving backpressure + engine recovery
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pp.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


class TestServingBackpressure:
    def _engine(self, model, **kw):
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        return ContinuousBatchingEngine(model, max_len=64,
                                        prefill_buckets=(16,), **kw)

    def test_bounded_admission_rejects(self, tiny_model):
        rng = np.random.default_rng(0)
        eng = self._engine(tiny_model, slots=1, max_queue=2)
        c = default_registry().counter(
            "paddle_tpu_serving_rejections_total", labelnames=("reason",))
        before = c.labels(reason="queue_full").value()
        rids = [eng.add_request(rng.integers(0, 256, (8,)),
                                max_new_tokens=3) for _ in range(2)]
        with pytest.raises(QueueFullError):
            eng.add_request(rng.integers(0, 256, (8,)), max_new_tokens=3)
        assert c.labels(reason="queue_full").value() == before + 1
        res = eng.run()                    # accepted requests unaffected
        assert all(len(res[r][1]) == 3 for r in rids)

    def test_expired_slot_retired_while_others_decode(self, tiny_model):
        """Acceptance: an expired request is retired with a timeout
        status while other slots keep decoding."""
        rng = np.random.default_rng(1)
        eng = self._engine(tiny_model, slots=2)
        ra = eng.add_request(rng.integers(0, 256, (8,)),
                             max_new_tokens=40, timeout_s=0.001)
        rb = eng.add_request(rng.integers(0, 256, (8,)),
                             max_new_tokens=6)
        eng.step()
        eng.step()                         # both admitted into slots
        time.sleep(0.01)                   # ra's deadline passes
        res = eng.run()
        assert eng.request_status(ra) == "timeout"
        assert eng.request_status(rb) == "ok"
        assert len(res[rb][1]) == 6        # survivor decoded to budget
        assert len(res[ra][1]) < 40        # victim stopped early

    def test_expired_queued_request_never_occupies_slot(self, tiny_model):
        rng = np.random.default_rng(2)
        eng = self._engine(tiny_model, slots=1,
                           request_timeout_s=0.001)
        rid = eng.add_request(rng.integers(0, 256, (8,)),
                              max_new_tokens=4)
        time.sleep(0.01)
        res = eng.run()
        assert eng.request_status(rid) == "timeout"
        assert res[rid][1] == []

    def test_engine_step_fault_recovers(self, tiny_model):
        """Acceptance: an engine-step exception fails the in-flight
        batch (status=error) without killing the engine."""
        rng = np.random.default_rng(3)
        eng = self._engine(tiny_model, slots=2)
        r1 = eng.add_request(rng.integers(0, 256, (8,)),
                             max_new_tokens=6)
        eng.step()                         # r1 decoding
        c = default_registry().counter(
            "paddle_tpu_serving_engine_errors_total")
        before = c.value()
        inject("serving.engine_step", times=1)
        eng.step()                         # fault fires mid-service
        assert fault_stats("serving.engine_step")["fires"] == 1
        assert c.value() == before + 1
        assert eng.request_status(r1) == "error"
        # engine alive: a fresh request completes with correct output
        prompt = rng.integers(0, 256, (8,))
        r2 = eng.add_request(prompt, max_new_tokens=5)
        res = eng.run()
        ref = tiny_model.generate(np.asarray(prompt, np.int32)[None],
                                  max_new_tokens=5, do_sample=False)
        assert res[r2][1] == list(np.asarray(ref)[0, len(prompt):])
        assert eng.request_status(r2) == "ok"

    def test_expiry_with_a_dispatch_unread_leaves_the_engine_serving(
            self, tiny_model):
        """A slot whose deadline passes while its decode dispatch is
        unread: the dispatch is collected first, the slot times out with
        the tokens it had, and the other slot's tokens are the
        reference's."""
        rng = np.random.default_rng(5)
        eng = self._engine(tiny_model, slots=2)
        ra = eng.add_request(rng.integers(0, 256, (8,)),
                             max_new_tokens=40, timeout_s=3600)
        prompt = rng.integers(0, 256, (8,))
        rb = eng.add_request(prompt, max_new_tokens=12)
        while eng._inflight is None or len(eng._active[0].out) < 3:
            eng.step()
        unread = eng._inflight
        assert {i for i, _ in unread.rows} == {0, 1}
        eng._active[0].deadline = time.perf_counter()   # ra's passes
        eng.step()
        st = eng.request_status(ra)
        assert st == "timeout" and eng._active[0] is None
        # what the unread dispatch made was read before the slot went
        assert st.timings["generated"] >= 4
        res = eng.run()
        ref = tiny_model.generate(np.asarray(prompt, np.int32)[None],
                                  max_new_tokens=12, do_sample=False)
        assert res[rb][1] == list(np.asarray(ref)[0, len(prompt):])
        assert eng.request_status(rb) == "ok"

    def test_a_fault_with_a_dispatch_unread_serves_what_was_finished(
            self, tiny_model):
        """The step that faults had a dispatch unread whose tokens end
        one request's budget: recovery reads it first, so that request
        is served whole, the other fails with the batch, and the engine
        goes on serving."""
        rng = np.random.default_rng(6)
        eng = self._engine(tiny_model, slots=2)
        short = rng.integers(0, 256, (8,))
        r0 = eng.add_request(short, max_new_tokens=3)
        r1 = eng.add_request(rng.integers(0, 256, (8,)), max_new_tokens=30)
        for _ in range(50):
            eng.step()
            d = eng._inflight
            if d is not None and eng._active[0] is not None and \
                    eng._budget[0] - d.steps <= 0:
                break
        assert eng.request_status(r0) is None
        inject("serving.engine_step", times=1)
        eng.step()
        assert eng._inflight is None
        ref = tiny_model.generate(np.asarray(short, np.int32)[None],
                                  max_new_tokens=3, do_sample=False)
        assert eng.request_status(r0) == "ok"
        assert eng.request_status(r1) == "error"
        done = {rid: out for rid, _, out in eng.finished()}
        assert done[r0] == list(np.asarray(ref)[0, len(short):])
        prompt = rng.integers(0, 256, (8,))
        r2 = eng.add_request(prompt, max_new_tokens=5)
        res = eng.run()
        ref = tiny_model.generate(np.asarray(prompt, np.int32)[None],
                                  max_new_tokens=5, do_sample=False)
        assert res[r2][1] == list(np.asarray(ref)[0, len(prompt):])

    def test_persistent_engine_fault_reraises(self, tiny_model):
        rng = np.random.default_rng(4)
        eng = self._engine(tiny_model, slots=1,
                           max_consecutive_errors=2)
        eng.add_request(rng.integers(0, 256, (4,)), max_new_tokens=3)
        inject("serving.engine_step")
        with pytest.raises(InjectedFault):
            for _ in range(5):
                eng.step()
