"""chip_smoke.py off the chip: it refuses to run, and its phases' logic
holds at a tiny size on the CPU — the one-chip phases (rehearsal 1 of the
on-chip-measurement guide) and the four-chip phase on four virtual devices
(rehearsal 2)."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout + proc.stderr
    assert "platform 'cpu'" in proc.stderr and "refusing" in proc.stderr


@pytest.mark.parametrize("phase", ["train", "serve"])
def test_one_chip_phase_logic_at_a_tiny_size(phase, capsys):
    import chip_smoke
    sz = chip_smoke.sizes(tiny=True)[phase]
    getattr(chip_smoke, phase + "_phase")(sz, 0, on_tpu=False)
    out = capsys.readouterr().out
    assert ("step-0 loss" if phase == "train" else "engine_errors 0") in out
    assert '"ok"' not in out


def test_sharded_phase_logic_on_four_virtual_devices(capsys):
    import chip_smoke
    sz = chip_smoke.sizes(tiny=True)["sharded"]
    chip_smoke.sharded_train_phase(sz, 0, jax.devices()[:4], on_tpu=False)
    out = capsys.readouterr().out
    assert "every array on all of 4 devices" in out
    assert "step-0 loss" in out and '"ok"' not in out
