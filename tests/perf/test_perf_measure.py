"""The yardstick's arithmetic on the CPU: the trace reduction on hand-made
intervals, FLOP and byte counts against hand arithmetic, the generators,
the timing rules of the open loop, and the plain reference against the
program at a tiny size — with the lower-precision control failing."""

import json
import os

import numpy as np
import pytest

from perf import common, flops, trace_reduce as tr, weights
from perf.kinds import serve

ROOT = common.ROOT
MS = 1e6    # ns


def _config(name):
    return common.load_json(os.path.join(ROOT, "perf", "configs", name))


# -- trace reduction ---------------------------------------------------------

@pytest.fixture()
def trace():
    """Device 0 over a 100 ms window: a 40 ms program, a 10 ms gap while
    the host preps a batch, a second 40 ms program inside which an
    all-gather runs asynchronously (start 2 ms, hidden 6 ms, done 3 ms)
    and an all-reduce holds the core for 5 ms; then 10 ms idle."""
    ops = [("fusion.1", 0, 40 * MS),
           ("all-gather-start.1", 50 * MS, 2 * MS),
           ("fusion.2", 52 * MS, 6 * MS),
           ("all-gather-done.1", 58 * MS, 3 * MS),
           ("all-reduce.7", 61 * MS, 5 * MS),
           ("fusion.3", 66 * MS, 24 * MS)]
    modules = [("jit_step(11)", 0, 40 * MS), ("jit_step(11)", 50 * MS,
                                              40 * MS)]
    host = [("bench.train_step", 0, 41 * MS),
            ("bench.batch_prep", 41 * MS, 8 * MS),
            ("bench.train_step", 49 * MS, 42 * MS),
            ("bench.batch_prep", 91 * MS, 9 * MS)]
    return tr.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules}, host)


def test_busy_idle_and_programs(trace):
    assert trace.window() == (0, 100 * MS)
    assert tr.busy_ns(trace, trace.device0) == 80 * MS
    assert tr.idle_share(trace) == pytest.approx(0.2)
    assert tr.program_durations(trace) == {"jit_step": [40 * MS, 40 * MS]}
    assert tr.op_totals(trace)["fusion.3"] == 24 * MS
    extra, breakdown = tr.device_summary(trace)
    assert extra == {"busy_s": pytest.approx(0.08),
                     "window_s": pytest.approx(0.1)}
    # operations are summed by family: fusion.1, .2 and .3 are "fusion"
    assert breakdown["device_ops"][0] == ["fusion", pytest.approx(0.07)]


def test_collectives_exposed_and_hidden(trace):
    total, exposed = tr.collective_ns(trace)
    assert exposed == (2 + 3 + 5) * MS      # start, done, the all-reduce
    assert total == (11 + 5) * MS           # start..done, and the all-reduce


def test_gaps_are_labelled_by_the_hosts_annotation(trace):
    gaps = tr.idle_gaps(trace)
    # 40-50 ms lies mostly under batch_prep (41-49); 90-100 ms likewise
    assert gaps == {"bench.batch_prep": 20 * MS}
    inside = tr.busy_inside(trace, tr.host_spans(trace, "bench.train_step"))
    assert inside == [40 * MS, 40 * MS]


def test_overlapping_ops_are_not_counted_twice():
    t = tr.Trace({"/device:TPU:0": [("a", 0, 10), ("b", 5, 10),
                                    ("c", 30, 5)]}, {}, [])
    assert tr.union(t.ops["/device:TPU:0"]) == [[0, 15], [30, 35]]
    assert tr.busy_ns(t, t.device0) == 20 and t.window() == (0, 35)


# -- operations and bytes ----------------------------------------------------

def test_flops_internlm2():
    cfg = _config("internlm2-1.8b.L4.json")
    # q, o: 2048x2048 each; k, v: 2048x1024 each; gate, up, down: 2048x8192
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert layer == 62_914_560 == flops.layer_matmul_params(cfg)
    head = 2048 * 92544
    assert flops.matmul_params(cfg) == 4 * layer + head
    assert flops.total_params(cfg) == 4 * layer + 2 * head + 9 * 2048
    # attention, causal: QK^T and AV, 2*(4096/2)*16*128 each forward, x3
    attn = 3 * 2 * 2 * 2048 * 2048 * 4
    assert flops.train_flops_per_token(cfg, 4096) == \
        6 * (4 * layer + head) + attn
    full = dict(cfg, num_hidden_layers=24)
    assert round(flops.total_params(full) / 1e9, 2) == 1.89


def test_flops_and_decode_bytes_mistral():
    cfg = _config("mistral-7b-v0.3.L12.json")
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808 == flops.layer_matmul_params(cfg)
    assert round(flops.total_params(dict(cfg, num_hidden_layers=32)) / 1e9,
                 2) == 7.25
    # per token: K and V, 8 heads x 128, bf16, 12 layers
    assert flops.kv_bytes_per_token(cfg) == 2 * 8 * 128 * 2 * 12 == 49152
    weights_b = (12 * layer + 4096 * 32768) * 2
    assert flops.decode_step_bytes(cfg, 10_000) == weights_b + 491_520_000
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")


# -- generators --------------------------------------------------------------

def _gen(name):
    return common.load_by_path(
        os.path.join(ROOT, "perf", "generators", name + ".py"), name)


def test_chat_generator_same_work_for_every_seed():
    mix = common.load_json(os.path.join(ROOT, "perf", "traffic",
                                        "chat-open-0.8.json"))
    p, cfg = mix["params"], _config("mistral-7b-v0.3.L12.json")
    gen = _gen(mix["generator"])
    a, a2, b = (gen.requests(p, cfg, s, 40.0) for s in (5, 5, 2 ** 31 + 9))
    c = gen.requests(dict(p, schedule_seed=p["schedule_seed"] + 1), cfg, 5,
                     40.0)
    assert len(a) == len(b) == round(p["rate_per_s"] * 40.0)
    assert all(x["due_s"] == y["due_s"] and x["max_new"] == y["max_new"]
               and np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, a2))
    lens = lambda rs, k: sorted(len(r[k]) if k == "prompt" else r[k]
                                for r in rs)
    # every seed: the same sizes at the same instants, other tokens
    assert all(x["due_s"] == y["due_s"] and x["max_new"] == y["max_new"]
               and len(x["prompt"]) == len(y["prompt"])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])
    # another schedule_seed: the same set of sizes in another order
    assert lens(a, "prompt") == lens(c, "prompt")
    assert lens(a, "max_new") == lens(c, "max_new")
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
    due = np.array([r["due_s"] for r in a])
    assert (np.diff(due) > 0).all() and 0 <= due[0] and due[-1] < 40.0
    pl = np.array(lens(a, "prompt"))
    assert pl.min() >= p["prompt"]["min"] and pl.max() <= p["prompt"]["max"]
    assert abs(np.median(pl) - p["prompt"]["median"]) <= 8
    ol = np.array(lens(a, "max_new"))
    assert abs(np.median(ol) - p["output"]["median"]) <= 4
    assert ol.max() <= p["output"]["max"] and ol.min() >= p["output"]["min"]
    # arrivals: exponential gaps, so their spread is about their mean
    gaps = np.diff(due)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    eng = mix["system"]["engine"]
    assert pl.max() + ol.max() <= eng["max_len"] - 1


def test_lm_batches():
    mix = common.load_json(os.path.join(ROOT, "perf", "traffic",
                                        "lm-16k.json"))
    cfg = _config("internlm2-1.8b.L4.json")
    gen = _gen(mix["generator"])
    a, a2 = (gen.batch(mix["params"], cfg, 2 ** 31 + 3, 4) for _ in "ab")
    b = gen.batch(mix["params"], cfg, 2 ** 31 + 3, 5)
    assert a["input_ids"].shape == (4, 4096) and a["labels"].shape == (4, 4096)
    assert np.array_equal(a["input_ids"], a2["input_ids"])
    assert not np.array_equal(a["input_ids"], b["input_ids"])
    assert np.array_equal(a["input_ids"][:, 1:], a["labels"][:, :-1])
    assert len({r.tobytes() for r in a["input_ids"]}) == 4   # rows differ
    assert a["input_ids"].max() < cfg["vocab_size"]


# -- the open loop's timing rules --------------------------------------------

class _Status(str):
    timings = None


class FakeEngine:
    """Admits one request a step, one step later gives its first token,
    one step later retires it; stamps as the real engine does."""

    def __init__(self, stall_s=0.0):
        self.queue, self.done, self.status, self.n = [], [], {}, 0
        self.stall_s = stall_s

    @property
    def pending(self):
        return len(self.queue)

    def add_request(self, prompt, max_new_tokens):
        import time
        self.n += 1
        self.queue.append([self.n, prompt, max_new_tokens,
                           time.perf_counter(), 0])
        return self.n

    def step(self):
        import time
        time.sleep(0.002 + self.stall_s)
        self.stall_s = 0.0
        r = self.queue[0]
        r[4] += 1
        now = time.perf_counter()
        if r[4] == 1:
            r.append(now)                       # admitted
        elif r[4] == 2:
            r.append(now)                       # first token
        else:
            self.queue.pop(0)
            st = _Status("ok")
            st.timings = {"enqueued": r[3], "admitted": r[5],
                          "first_token": r[6], "retired": now}
            self.status[r[0]] = st
            self.done.append((r[0], r[1], [7] * r[2]))

    def finished(self):
        while self.done:
            yield self.done.pop(0)

    def request_status(self, rid):
        return self.status.get(rid)


def test_ttft_is_timed_from_when_the_request_was_due():
    reqs = [{"due_s": 0.0, "prompt": np.arange(8), "max_new": 4},
            {"due_s": 0.01, "prompt": np.arange(8), "max_new": 4}]
    # the engine stalls 80 ms in its first step: the second request is
    # offered ~70 ms late, and that wait is the server's, not the clock's
    recs, w0, _, _ = serve.drive(FakeEngine(stall_s=0.08), reqs, 0.05)
    assert all(r["ok"] for r in recs)
    late = recs[1]["offered"] - recs[1]["due_s"]
    assert late > 0.06
    res = serve.summarise(recs, 0.05)
    ttft1 = res["ttft_ms"][1]
    assert ttft1 == pytest.approx(
        (recs[1]["first_token"] - recs[1]["due_s"]) * 1e3)
    enq_based = (recs[1]["first_token"] - recs[1]["offered"]) * 1e3
    assert ttft1 > enq_based + 60
    tpot = (recs[0]["retired"] - recs[0]["first_token"]) * 1e3 / 3
    assert res["tpot_ms"][0] == pytest.approx(tpot)


def test_a_miss_counts_at_the_drain_limit():
    ok = {"ok": True, "due_s": 0.0, "first_token": 0.1, "retired": 0.5,
          "prompt": np.arange(10), "tokens": [1] * 5}
    late = dict(ok, retired=1.5)
    miss = dict(ok, ok=False, tokens=None)
    res = serve.summarise([ok, late, miss], 1.0)
    assert res["ttft_ms"].tolist() == [100.0, 100.0,
                                       serve.DRAIN_LIMIT_S * 1e3]
    assert res["tpot_ms"][2] == serve.DRAIN_LIMIT_S * 1e3
    assert res["serve_tokens_per_s"] == 15.0    # only `ok` retired inside
    held = serve.live_kv_tokens(
        [dict(ok, admitted=0.05)], 0.0, 1.0, "admitted", 16, n=101)
    assert held.max() == 16 and held[3] == 0 and held[10] == 16


# -- the plain reference -----------------------------------------------------

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
            max_position_embeddings=128, rms_norm_eps=1e-5,
            rope_theta=1e6, tie_word_embeddings=False,
            torch_dtype="float32")


def _program(dtype):
    import jax.numpy as jnp
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    sizes = {k: v for k, v in TINY.items() if k != "torch_dtype"}
    model = LlamaForCausalLM(LlamaConfig(dtype=dtype, **sizes))
    given = weights.make_all(TINY, 3, jnp.dtype(dtype))
    for name, t in model.state_dict(keep_vars=True).items():
        t._set_data(given[name])
    return model, given


def test_reference_agrees_with_the_program_in_float32():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.dispatch import unwrap
    from perf.reference import decoder
    model, w = _program("float32")
    ids = np.random.default_rng(0).integers(0, 256, (2, 48), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = decoder.logits(w, TINY, jnp.asarray(ids))
        got = unwrap(model(jnp.asarray(ids)))
        assert float(jnp.abs(got - want).max()) < 2e-5 * float(
            jnp.abs(want).max())
        l_ref = decoder.loss(w, TINY, jnp.asarray(ids[:, :-1]),
                             jnp.asarray(ids[:, 1:]))
        l_got = unwrap(model.loss(jnp.asarray(ids[:, :-1]),
                                  jnp.asarray(ids[:, 1:])))
        assert float(l_got) == pytest.approx(float(l_ref), rel=1e-5)
    # one jitted call and leaf-at-a-time give the same weights
    some = weights.make_some(TINY, 3, ["model.layers_1.mlp.up_proj.weight"],
                             jnp.float32)
    assert np.array_equal(some["model.layers_1.mlp.up_proj.weight"],
                          w["model.layers_1.mlp.up_proj.weight"])


def test_int8_control_of_the_served_tokens_separates():
    """The serving cells' control at a size a test can hold.  The cell's
    limits were read on the chip at its own depth and width (PERF.md §2:
    rounding noise grows with both), so here the same machinery is held to
    the separation instead: where the int8 reference puts another token
    first, that token lies much further below the float32 reference's best
    than the token a bfloat16-operand forward picks."""
    from perf.reference import served
    cfg = dict(TINY, hidden_size=256, intermediate_size=512,
               num_hidden_layers=4, vocab_size=32768,
               max_position_embeddings=512, torch_dtype="bfloat16")
    rng = np.random.default_rng(0)
    rows = [(rng.integers(0, 32768, 40, dtype=np.int32),
             rng.integers(0, 32768, 200, dtype=np.int32)) for _ in range(2)]
    ref = served.served_logits(cfg, 5, rows, 256, 200)
    choice = lambda p: [a.argmax(-1) for a in served.served_logits(
        cfg, 5, rows, 256, 200, precision=p)]
    assert served.gaps(ref, rows, tokens=choice("float32")).max() == 0
    low = served.gaps(ref, rows, tokens=choice("int8"))
    soft = served.gaps(ref, rows, tokens=choice("bfloat16"))
    assert low.mean() > 10 * soft.mean() and low.max() > 3 * soft.max()
    assert low.max() > 0.005


def test_int8_control_fails_the_train_cells_limits():
    """At a size a test can hold: the reference computed in int8 fails the
    cell's own limits (set on the chip at the cell's size, PERF.md §2)."""
    from perf.reference import train_steps
    mix = common.load_json(os.path.join(ROOT, "perf", "traffic",
                                        "lm-16k.json"))
    lim = mix["limits"]
    hp = mix["system"]["adamw"]
    gen = _gen("lm_batches")
    batches = [gen.batch({"batch": 2, "seq": 32}, TINY, 1, i)
               for i in range(2)]
    ref, ctl = (train_steps.follow(TINY, 1, batches, hp, precision=p)
                for p in ("float32", "int8"))

    def verdict(got):
        ok = abs(got["loss"][0] - ref["loss"][0]) / ref["loss"][0] <= \
            lim["loss_rel"]         # the first step's: the one compared
        for k in ("grad_norm", "delta_norm"):
            gap, _, mean = train_steps.worst_leaf_gap(got[k], ref[k])
            ok &= gap <= lim[k + "_worst_leaf"]
            ok &= mean <= lim.get(k + "_mean_leaf", 1.0)
        return ok

    assert verdict(ref)
    assert not verdict(ctl)


def test_reduction_on_a_trace_cut_from_a_chip_run():
    """Two steps of train-1chip recorded on the v5e (names shortened as
    ``load`` does).  Busy time is checked against a count made another way:
    a 1 us raster of the operation line."""
    import gzip
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "train_two_steps.json.gz"), "rt") as f:
        cut = json.load(f)
    as_events = lambda rows: [(n, float(s), float(d)) for n, s, d in rows]
    t = tr.Trace({"/device:TPU:0": as_events(cut["ops"])},
                 {"/device:TPU:0": as_events(cut["modules"])},
                 as_events(cut["host"]))
    lo, hi = t.window()
    raster = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in t.ops["/device:TPU:0"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            raster[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    busy = tr.busy_ns(t, t.device0)
    assert busy == pytest.approx(raster.sum() * 1000.0, rel=5e-3)
    assert 0.005 < tr.idle_share(t) < 0.02       # the chip run read 1.0 %
    steps = tr.program_durations(t)["jit__step_impl"]
    assert len(steps) == 2 and all(5.5e8 < d < 5.65e8 for d in steps)
    # nothing is counted twice: self times sum to the busy time
    assert sum(tr.op_totals(t).values()) == pytest.approx(busy, rel=1e-6)
    assert set(tr.idle_gaps(t)) <= {"bench.train_step", "bench.batch_prep"}
    assert tr.collective_ns(t) == (0, 0)
