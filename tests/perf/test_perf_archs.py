"""The architecture seam (perf/archs/) changes no value: what the two
accepted configurations' leaves, counts and plain reference gave on the
tree before the seam (PR 26, ``73893c4``), frozen there and held here —
and the reference's rows over several chips against its one-at-a-time
walk."""

import hashlib
import json
import os

import numpy as np
import pytest

from perf import common, flops, weights

ROOT = common.ROOT
DATA = os.path.join(os.path.dirname(__file__), "data")

# read from the parent tree before anything moved (PR 28)
FROZEN = {
    "internlm2-1.8b.L4": dict(
        leaves_sha256="1b2205283ea9f2108074fcc6490ccaa879965b2c7cbcd3c4e59d9c"
                      "817d0d6996",
        n_leaves=39, total_params=630736896, matmul_params=441188352,
        train_flops_per_token_4096=2848456704.0, kv_bytes_per_token=16384,
        decode_step_bytes_12200=1082261504),
    "mistral-7b-v0.3.L12": dict(
        leaves_sha256="e194808c5d04f858334ba7f6e7eb2c6ce31fd2122debfd5af3685c"
                      "9384d9a512",
        n_leaves=111, total_params=2885783552, matmul_params=2751463424,
        train_flops_per_token_4096=17716740096.0, kv_bytes_per_token=49152,
        decode_step_bytes_12200=6102581248),
}
# the sizes reference_frozen.npz was made at: each file's other keys
# (rope_theta, rms_norm_eps, torch_dtype) as they stand
SMALL = {
    "internlm2-1.8b.L4": dict(
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
        max_position_embeddings=128),
    "mistral-7b-v0.3.L12": dict(
        hidden_size=64, intermediate_size=160, num_hidden_layers=3,
        num_attention_heads=8, num_key_value_heads=2, vocab_size=128,
        max_position_embeddings=128),
}
SEED = 2 ** 31 + 5
HP = dict(lr=1e-4, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01)


def _batches(seed, vocab, rows, seq):
    out = []
    for i in range(2):
        ids = np.random.default_rng([seed, i]).integers(
            0, vocab, (rows, seq + 1), dtype=np.int32)
        out.append({"input_ids": ids[:, :-1], "labels": ids[:, 1:]})
    return out


def _config(name):
    return common.load_json(os.path.join(ROOT, "perf", "configs",
                                         name + ".json"))


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_leaves_and_counts_are_the_parents(name):
    cfg, want = _config(name), FROZEN[name]
    assert "arch" not in cfg        # the accepted files are not edited
    assert common.arch_of(cfg).__file__ == os.path.join(
        ROOT, "perf", "archs", "gqa_decoder.py")
    lv = [[n, list(s)] for n, s, _ in weights.leaves(cfg)]
    got = dict(
        leaves_sha256=hashlib.sha256(json.dumps(lv).encode()).hexdigest(),
        n_leaves=len(lv), total_params=flops.total_params(cfg),
        matmul_params=flops.matmul_params(cfg),
        train_flops_per_token_4096=flops.train_flops_per_token(cfg, 4096),
        kv_bytes_per_token=flops.kv_bytes_per_token(cfg),
        decode_step_bytes_12200=flops.decode_step_bytes(cfg, 12200))
    assert got == want
    # 1-D leaves were gains by rule; they are gains by name now
    assert all((k == "gain") == (len(s) == 1)
               for _, s, k in weights.leaves(cfg))
    # what the window observed may be handed over; these counts ignore it
    assert flops.decode_step_bytes(cfg, 12200, live_rows=20) == \
        want["decode_step_bytes_12200"]


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_reference_gives_the_parents_numbers(name):
    """The served walk's float32 logits and the two followed steps'
    losses and norms, same seed, same rows.  Both trees give the same
    bits here; the comparison leaves room for another CPU's rounding and
    none for a leaf out of place (which changes every weight)."""
    from perf.reference import served, train_steps
    frozen = np.load(os.path.join(DATA, "reference_frozen.npz"))
    cfg = dict(_config(name), **SMALL[name])
    rows = [(frozen[f"{name}.prompt{r}"], frozen[f"{name}.served{r}"])
            for r in range(2)]
    lg = served.served_logits(cfg, SEED, rows, 32, 8)
    for r in range(2):
        want = frozen[f"{name}.logits{r}"]
        assert lg[r].dtype == np.float32 and lg[r].shape == want.shape
        np.testing.assert_allclose(lg[r], want, rtol=0,
                                   atol=2e-6 * np.abs(want).max())
    got = train_steps.follow(cfg, SEED, _batches(3, cfg["vocab_size"], 2, 16),
                             HP)
    names = [n for n, _, _ in weights.leaves(cfg)]
    np.testing.assert_allclose(got["loss"], frozen[f"{name}.loss"],
                               rtol=1e-6)
    for k in ("grad_norm", "delta_norm"):
        np.testing.assert_allclose([got[k][n] for n in names],
                                   frozen[f"{name}.{k}"], rtol=2e-5)


def test_reference_rows_over_the_chips_give_the_one_at_a_time_walk():
    """On several chips the reference takes as many rows a call as there
    are chips, each leaf and the rows split along their first axis; on
    one chip it takes one row a call as before.  Same losses and norms."""
    import jax
    from perf.kinds import train
    from perf.reference import train_steps
    cfg = dict(_config("mistral-7b-v0.3.L12"), **SMALL["mistral-7b-v0.3.L12"],
               torch_dtype="float32")
    batches = _batches(4, cfg["vocab_size"], 8, 32)
    one = train_steps.follow(cfg, 9, batches, HP)
    four = train_steps.follow(cfg, 9, batches, HP,
                              sharding=train._leaf_sharding(
                                  jax.devices()[:4]))
    np.testing.assert_allclose(four["loss"], one["loss"], rtol=1e-6)
    for k in ("grad_norm", "delta_norm"):
        assert set(four[k]) == set(one[k])
        np.testing.assert_allclose([four[k][n] for n in one[k]],
                                   list(one[k].values()), rtol=1e-4)
    with pytest.raises(ValueError, match="rows do not go over"):
        train_steps.follow(
            cfg, 9, [{k: v[:3] for k, v in b.items()} for b in batches], HP,
            sharding=train._leaf_sharding(jax.devices()[:4]))


# -- what an architecture file may say beyond gqa_decoder's ---------------------

@pytest.fixture()
def toy_arch(monkeypatch):
    """data/two_kinds_arch.py, for every configuration that names
    ``two_kinds`` (no such file is under perf/archs/)."""
    toy = common.load_by_path(os.path.join(DATA, "two_kinds_arch.py"),
                              "toy_arch")
    real = common.arch_of
    monkeypatch.setattr(common, "arch_of", lambda cfg: toy if cfg.get(
        "arch") == "two_kinds" else real(cfg))
    return toy


def test_decode_roofline_hands_the_count_the_live_rows(toy_arch):
    """gqa_decoder's count ignores the live rows, so the accepted cell
    reads what it read from ``live_kv_tokens`` alone; a count that turns
    on them is handed them."""
    from perf import trace_reduce as tr
    read = common.load_by_path(os.path.join(
        ROOT, "perf", "layer_metrics", "decode_roofline.py"),
        "perf_layer_metric").read
    name, tpu, ms = "mistral-7b-v0.3.L12", "/device:TPU:0", 1e6
    cfg = _config(name)
    obs = {"cell": {"config": cfg}, "live_kv_tokens": 12200.0,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": tr.Trace({tpu: [("fusion.1", 0, 8 * ms)]},
                             {tpu: [("jit_decode_paged(1)", 0, 8 * ms)]},
                             [("bench.engine_step", 0, 9 * ms)])}
    share = lambda nbytes: 100.0 * nbytes / 819e9 / 8e-3
    want = share(FROZEN[name]["decode_step_bytes_12200"])
    assert read(obs) == read(dict(obs, live_rows=20.0)) == \
        pytest.approx(want)
    toy = dict(obs, cell={"config": dict(cfg, arch="two_kinds")})
    few, many = (read(dict(toy, live_rows=n)) for n in (4.0, 20.0))
    # a row's hidden state read and written by each of the 6 odd layers
    assert few == pytest.approx(want + share(4 * 2 * 2 * 6 * 4096))
    assert many - few == pytest.approx(share(16 * 2 * 2 * 6 * 4096))
    with pytest.raises(TypeError):      # it needs them: none is no zero
        read(toy)


def test_live_rows_are_the_requests_between_first_token_and_retirement():
    from perf.kinds import serve
    req = lambda a, b, ok=True: {"ok": ok, "first_token": a, "retired": b}
    recs = [req(0.0, 0.5), req(0.2, 1.0), req(0.3, 0.3), req(0.0, 1.0, False)]
    rows = serve.live_rows(recs, 0.0, 1.0, n=11)    # 0.0, 0.1, ... 1.0
    assert rows.tolist() == [1, 1, 2, 2, 2, 2, 1, 1, 1, 1, 1]
    # the instants and the requests live_kv_tokens counts
    held = serve.live_kv_tokens(
        [dict(r, prompt=[0] * 8, tokens=[0] * 4) for r in recs[:2]],
        0.0, 1.0, n=11)
    assert ((held > 0) == (rows > 0)).all()


@pytest.mark.parametrize("kind,ok", [("log_uniform", True), ("matrix", True),
                                     ("uniform", False)])
def test_an_initialiser_can_be_the_architectures(toy_arch, kind, ok):
    """``weights.leaves`` hands ``_leaf`` the architecture's own function
    for a kind ``weights.py`` has not; the three kinds stay ``_leaf``'s,
    and a kind neither knows is an error.  The key stays the leaf's index
    in ``leaves(cfg)``."""
    import jax.numpy as jnp
    cfg = dict(_config("mistral-7b-v0.3.L12"), arch="two_kinds",
               **SMALL["mistral-7b-v0.3.L12"])
    name = "model.layers_1.shift"
    toy_arch.layer_leaves = lambda cfg, i, real=toy_arch.layer_leaves: [
        (n, s, kind if n.endswith(".shift") else k)
        for n, s, k in real(cfg, i)]
    spec = {n: (i, s, k) for i, (n, s, k) in enumerate(weights.leaves(cfg))}
    assert [n for n in spec if n.endswith(".shift")] == [name]   # 3 layers
    index, shape, init = spec[name]
    if not ok:
        with pytest.raises(ValueError, match="unknown initialiser 'uniform'"):
            weights.make_some(cfg, SEED, [name], jnp.float32)
        return
    got = np.asarray(weights.make_some(cfg, SEED, [name], jnp.float32)[name])
    one = np.asarray(weights._leaf(weights.base_key(SEED), index, shape,
                                   init, jnp.float32))
    assert got.shape == (64,)
    np.testing.assert_allclose(got, one, rtol=1e-6)     # jitted or not
    if kind == "matrix":
        assert init == "matrix" and abs(got.mean()) < 0.01
    else:       # 0.02 log U(1, 16): positive, below 0.02 log 16
        assert init is toy_arch.INITS[kind]
        assert 0 <= got.min() and got.max() <= 0.02 * np.log(16.0)
        assert got.std() > 0.01
    # its neighbours are what they were without the kind
    near = "model.layers_1.mlp.down_proj.weight"
    np.testing.assert_allclose(
        weights.make_some(cfg, SEED, [near], jnp.float32)[near],
        weights._leaf(weights.base_key(SEED), spec[near][0], spec[near][1],
                      "matrix", jnp.float32), rtol=1e-6, atol=1e-9)
