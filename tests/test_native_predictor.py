"""Native C++ predictor (csrc/predictor): PJRT C API serving path.

Reference parity: the C++ AnalysisPredictor serving engine
(fluid/inference/api/analysis_predictor.cc:1665) — here the C++ shim
compiles the jit.save StableHLO through a PJRT plugin and must produce
the same outputs as the Python Predictor path.

The real-hardware roundtrip needs the chip, which one process holds at a
time, so it runs in a subprocess with a timeout and SKIPs when no plugin
is present or the chip can't be claimed — it must never wedge the suite.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plugin_path():
    sys.path.insert(0, REPO)
    from paddle_tpu.inference.native import default_plugin_path
    return default_plugin_path()


def test_predictor_lib_builds():
    from paddle_tpu.utils.cpp_extension import load_native
    lib = load_native("predictor")
    if lib is None:
        pytest.skip("predictor lib unavailable (no PJRT C API header)")
    assert hasattr(lib, "pd_predictor_create")
    assert hasattr(lib, "pd_predictor_run")


def test_artifact_contains_stablehlo(tmp_path):
    import paddle_tpu as pp
    from paddle_tpu.jit import save
    from paddle_tpu.jit.save_load import InputSpec

    model = pp.nn.Linear(4, 2)
    prefix = str(tmp_path / "m")
    save(model, prefix, input_spec=[InputSpec([1, 4], "float32")])
    assert os.path.exists(prefix + ".pdstablehlo")
    text = open(prefix + ".pdstablehlo").read()
    assert "stablehlo" in text or "func.func" in text
    assert os.path.exists(prefix + ".pdiparams.npz")
    assert os.path.exists(prefix + ".pdmeta")


def test_bad_plugin_clean_error(tmp_path):
    from paddle_tpu.utils.cpp_extension import load_native
    if load_native("predictor") is None:
        pytest.skip("predictor lib unavailable")
    import paddle_tpu as pp
    from paddle_tpu.jit import save
    from paddle_tpu.jit.save_load import InputSpec
    from paddle_tpu.inference.native import NativePredictor

    model = pp.nn.Linear(4, 2)
    prefix = str(tmp_path / "m")
    save(model, prefix, input_spec=[InputSpec([1, 4], "float32")])
    with pytest.raises(RuntimeError, match="dlopen|no PJRT plugin"):
        NativePredictor(prefix, plugin_path=str(tmp_path / "nope.so"))


_ROUNDTRIP = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as pp
    from paddle_tpu.jit import save
    from paddle_tpu.jit.save_load import InputSpec
    from paddle_tpu.inference.native import NativePredictor

    prefix = sys.argv[1] + "/model"
    pp.seed(0)
    model = pp.nn.Sequential(pp.nn.Linear(8, 16), pp.nn.ReLU(),
                             pp.nn.Linear(16, 4))
    save(model, prefix, input_spec=[InputSpec([2, 8], "float32")])
    x = np.random.default_rng(0).normal(size=(2, 8)).astype(np.float32)
    want = np.asarray(model(pp.to_tensor(x))._data)
    npred = NativePredictor(prefix)
    got = npred.run([x])
    assert len(got) == 1 and got[0].shape == (2, 4)
    # device-vs-host matmul precision bound
    np.testing.assert_allclose(got[0], want, rtol=1e-2, atol=5e-3)
    got2 = npred.run([x * 2])  # params stay device-resident
    want2 = np.asarray(model(pp.to_tensor(x * 2))._data)
    np.testing.assert_allclose(got2[0], want2, rtol=1e-2, atol=5e-3)
    print("NATIVE_OK")
""")


def test_native_matches_python_predictor(tmp_path):
    plugin = _plugin_path()
    if plugin is None:
        pytest.skip("no PJRT plugin .so on this host")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _ROUNDTRIP, str(tmp_path)],
            capture_output=True, text=True, timeout=300, env=env)
    except subprocess.TimeoutExpired:
        pytest.skip("TPU busy/unclaimable — roundtrip timed out")
    if proc.returncode != 0:
        tail = (proc.stderr or "")[-2000:]
        if "Client_Create" in tail or "claim" in tail.lower():
            pytest.skip(f"PJRT client unavailable: {tail[-300:]}")
        raise AssertionError(f"native roundtrip failed:\n{tail}")
    assert "NATIVE_OK" in proc.stdout


_INT8_ROUNDTRIP = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as pp
    from paddle_tpu.jit import save
    from paddle_tpu.jit.save_load import InputSpec
    from paddle_tpu.inference.native import NativePredictor
    from paddle_tpu.quantization import PTQ

    prefix = sys.argv[1] + "/qmodel"
    pp.seed(0)
    net = pp.nn.Sequential(pp.nn.Linear(8, 16), pp.nn.ReLU(),
                           pp.nn.Linear(16, 4))
    x = np.random.default_rng(0).normal(size=(4, 8)).astype(np.float32)
    ptq = PTQ()
    net = ptq.quantize(net)
    for _ in range(4):
        net(pp.to_tensor(x))
    net = ptq.convert(net)           # QuantedLinear: int8 weights
    assert net[0].qweight.numpy().dtype == np.int8
    # real int8 x int8 -> int32 dot path, not weight-only dequant
    assert net[0].act_scale is not None
    want = np.asarray(net(pp.to_tensor(x))._data)

    # int8 artifact through jit.save -> C++ PJRT predictor
    save(net, prefix, input_spec=[InputSpec([4, 8], "float32")])
    params = dict(np.load(prefix + ".pdiparams.npz"))
    assert any(a.dtype == np.int8 for a in params.values()), \\
        "int8 weights must survive into the artifact"
    got = NativePredictor(prefix).run([x])[0]
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    print("INT8_NATIVE_OK")
""")


def test_native_runs_int8_artifact(tmp_path):
    """VERDICT r2 item 9 'done' criterion: the C++ path runs a quantized
    model with outputs matching Python within int8 tolerance."""
    plugin = _plugin_path()
    if plugin is None:
        pytest.skip("no PJRT plugin .so on this host")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _INT8_ROUNDTRIP, str(tmp_path)],
            capture_output=True, text=True, timeout=300, env=env)
    except subprocess.TimeoutExpired:
        pytest.skip("TPU busy/unclaimable — roundtrip timed out")
    if proc.returncode != 0:
        tail = (proc.stderr or "")[-2000:]
        if "Client_Create" in tail or "claim" in tail.lower():
            pytest.skip(f"PJRT client unavailable: {tail[-300:]}")
        raise AssertionError(f"int8 native roundtrip failed:\n{tail}")
    assert "INT8_NATIVE_OK" in proc.stdout


_POOL_ROUNDTRIP = textwrap.dedent("""
    import os, sys, threading
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as pp
    from paddle_tpu.jit import save
    from paddle_tpu.jit.save_load import InputSpec
    from paddle_tpu.inference.native import NativePredictorPool

    prefix = sys.argv[1] + "/model"
    pp.seed(0)
    model = pp.nn.Sequential(pp.nn.Linear(8, 16), pp.nn.ReLU(),
                             pp.nn.Linear(16, 4))
    save(model, prefix, input_spec=[InputSpec([2, 8], "float32")])
    pool = NativePredictorPool(prefix, size=3)
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(2, 8)).astype(np.float32) for _ in range(3)]
    wants = [np.asarray(model(pp.to_tensor(x))._data) for x in xs]

    results = [None] * 3
    def work(i):
        # several sequential runs per slot: per-clone output buffers must
        # not be clobbered by the other slots
        for _ in range(3):
            results[i] = pool.retrieve(i).run([xs[i]])[0]
    threads = [threading.Thread(target=work, args=(i,)) for i in range(3)]
    for t in threads: t.start()
    for t in threads: t.join()
    for got, want in zip(results, wants):
        np.testing.assert_allclose(got, want, rtol=1e-2, atol=5e-3)
    print("POOL_NATIVE_OK")
""")


def test_native_pool_shares_executable(tmp_path):
    plugin = _plugin_path()
    if plugin is None:
        pytest.skip("no PJRT plugin .so on this host")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _POOL_ROUNDTRIP, str(tmp_path)],
            capture_output=True, text=True, timeout=300, env=env)
    except subprocess.TimeoutExpired:
        pytest.skip("TPU busy/unclaimable — roundtrip timed out")
    if proc.returncode != 0:
        tail = (proc.stderr or "")[-2000:]
        if "Client_Create" in tail or "claim" in tail.lower():
            pytest.skip(f"PJRT client unavailable: {tail[-300:]}")
        raise AssertionError(f"pool roundtrip failed:\n{tail}")
    assert "POOL_NATIVE_OK" in proc.stdout
