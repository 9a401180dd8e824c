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
