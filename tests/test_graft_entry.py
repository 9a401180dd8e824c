"""Driver-contract coverage: entry() compiles, dryrun_multichip shards the
full train step over an 8-device mesh (conftest forces the virtual CPU mesh)."""

import jax
import numpy as np
import pytest


def test_entry_compiles():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] == 1024
    assert np.isfinite(np.asarray(out).sum())


@pytest.mark.slow
def test_dryrun_multichip_8():
    import __graft_entry__ as g
    g.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_1():
    import __graft_entry__ as g
    g.dryrun_multichip(1)
