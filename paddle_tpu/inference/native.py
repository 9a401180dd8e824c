"""NativePredictor — ctypes binding over csrc/predictor/predictor.cpp.

Reference role: the C++ AnalysisPredictor
(fluid/inference/api/analysis_predictor.cc:1665) driven from Python via
pybind; here the C++ engine drives the jit.save artifact through the PJRT
C API of any PJRT plugin .so (libtpu), and this module is the
thin ctypes veneer.  The C++ side owns the PJRT client, the compiled
executable, and the device-resident parameters; each ``run`` uploads
inputs, executes, and downloads outputs.
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional

import numpy as np

__all__ = ["NativePredictor", "NativePredictorPool", "default_plugin_path",
           "native_available"]

# keep in sync with code_to_pjrt/pjrt_to_code in predictor.cpp
_DTYPE_CODES = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.int64): 3,
    # 4 = bfloat16 (no numpy dtype; outputs surface as uint16 views)
    np.dtype(np.bool_): 5,
    np.dtype(np.uint8): 6,
    np.dtype(np.int8): 7,
}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}
# output-only codes (inputs keep the table above; keep in sync with
# pjrt_to_code in predictor.cpp)
_CODE_DTYPES.update({
    8: np.dtype(np.float16),
    9: np.dtype(np.uint16),
    10: np.dtype(np.int16),
    11: np.dtype(np.uint32),
    12: np.dtype(np.uint64),
})

_PLUGIN_CANDIDATES = (
    "/usr/lib/libtpu.so",
)


def default_plugin_path() -> Optional[str]:
    env = os.environ.get("PADDLE_TPU_PJRT_PLUGIN")
    if env:
        return env
    for cand in _PLUGIN_CANDIDATES:
        if os.path.exists(cand):
            return cand
    return None


def _lib():
    from paddle_tpu.utils.cpp_extension import load_native
    lib = load_native("predictor")
    if lib is None:
        raise RuntimeError("libpt_predictor.so unavailable (build failed?)")
    lib.pd_predictor_create.restype = ctypes.c_void_p
    lib.pd_predictor_create.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                        ctypes.c_char_p]
    lib.pd_predictor_last_error.restype = ctypes.c_char_p
    lib.pd_predictor_num_outputs.argtypes = [ctypes.c_void_p]
    lib.pd_predictor_run.argtypes = [
        ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.pd_predictor_output_info.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.pd_predictor_output_copy.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p, ctypes.c_int64]
    lib.pd_predictor_destroy.argtypes = [ctypes.c_void_p]
    lib.pd_predictor_clone.restype = ctypes.c_void_p
    lib.pd_predictor_clone.argtypes = [ctypes.c_void_p]
    return lib


def native_available() -> bool:
    try:
        _lib()
    except Exception:
        return False
    return default_plugin_path() is not None


def _default_options(plugin: str) -> str:
    """Plugin create_options as 'k=v;k=v' (the NamedValues jax's
    register_plugin would pass): ``PADDLE_TPU_PJRT_OPTIONS``, default
    none."""
    return os.environ.get("PADDLE_TPU_PJRT_OPTIONS", "")


class NativePredictor:
    """Run a jit.save artifact through the C++ PJRT predictor."""

    def __init__(self, model_prefix: str, plugin_path: Optional[str] = None,
                 options: Optional[str] = None,
                 analyze: Optional[str] = None):
        # artifact lint BEFORE touching the native library: a bad export
        # (fp64 ops, symbolic dims) should fail here with a structured
        # report, not as a PJRT compile error on the serving fleet.
        # Opt-in: analyze="warn"|"strict" or PADDLE_TPU_ANALYZE env.
        from paddle_tpu.analysis import analysis_mode
        mode = analyze if analyze is not None else analysis_mode()
        if mode:
            import sys
            from paddle_tpu.analysis.artifact import check_artifact
            report = check_artifact(model_prefix,
                                    strict=(mode == "strict"))
            if len(report):
                print(report.format(), file=sys.stderr)
        self._lib = _lib()
        plugin = plugin_path or default_plugin_path()
        if plugin is None:
            raise RuntimeError(
                "no PJRT plugin .so found; set PADDLE_TPU_PJRT_PLUGIN")
        meta_path = model_prefix + ".pdmeta"
        if os.path.exists(meta_path):
            import json
            with open(meta_path) as f:
                meta = json.load(f)
            for spec in meta.get("inputs", []):
                if any(not isinstance(d, int) for d in spec.get("shape", [])):
                    raise ValueError(
                        "artifact was saved with dynamic (symbolic) input "
                        "dims; the native predictor compiles static shapes "
                        "only — re-save with concrete InputSpec shapes")
        if options is None:
            options = _default_options(plugin)
        self._h = self._lib.pd_predictor_create(
            model_prefix.encode(), plugin.encode(), options.encode())
        if not self._h:
            raise RuntimeError(
                "native predictor init failed: "
                + self._lib.pd_predictor_last_error().decode())

    def run(self, inputs: List[np.ndarray]) -> List[np.ndarray]:
        arrs = [np.ascontiguousarray(a) for a in inputs]
        n = len(arrs)
        data = (ctypes.c_void_p * n)(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs])
        dims_flat, ndims, dtypes = [], [], []
        for a in arrs:
            dims_flat.extend(a.shape)
            ndims.append(a.ndim)
            code = _DTYPE_CODES.get(a.dtype)
            if code is None:
                raise TypeError(f"unsupported input dtype {a.dtype}")
            dtypes.append(code)
        dims_c = (ctypes.c_int64 * len(dims_flat))(*dims_flat)
        ndims_c = (ctypes.c_int * n)(*ndims)
        dtypes_c = (ctypes.c_int * n)(*dtypes)
        rc = self._lib.pd_predictor_run(self._h, n, data, dims_c, ndims_c,
                                        dtypes_c)
        if rc != 0:
            raise RuntimeError("native run failed: "
                               + self._lib.pd_predictor_last_error().decode())

        outs = []
        for i in range(self._lib.pd_predictor_num_outputs(self._h)):
            dims = (ctypes.c_int64 * 16)()
            nd = ctypes.c_int()
            code = ctypes.c_int()
            if self._lib.pd_predictor_output_info(
                    self._h, i, dims, 16, ctypes.byref(nd),
                    ctypes.byref(code)) != 0:
                raise RuntimeError(
                    "output_info failed: "
                    + self._lib.pd_predictor_last_error().decode())
            shape = tuple(dims[d] for d in range(nd.value))
            if code.value == 4:  # bfloat16: land in uint16, upcast below
                raw = np.empty(shape, np.uint16)
            else:
                raw = np.empty(shape, _CODE_DTYPES[code.value])
            if self._lib.pd_predictor_output_copy(
                    self._h, i, raw.ctypes.data_as(ctypes.c_void_p),
                    raw.nbytes) != 0:
                raise RuntimeError(
                    "output_copy failed: "
                    + self._lib.pd_predictor_last_error().decode())
            if code.value == 4:
                import jax.numpy as jnp
                raw = np.asarray(raw.view(jnp.bfloat16).astype(np.float32))
            outs.append(raw)
        return outs

    def _clone(self) -> "NativePredictor":
        """Share the compiled executable + device params; own out buffers
        (csrc pd_predictor_clone — reference PredictorPool semantics)."""
        h = self._lib.pd_predictor_clone(self._h)
        if not h:
            raise RuntimeError("clone failed: "
                               + self._lib.pd_predictor_last_error().decode())
        twin = object.__new__(NativePredictor)
        twin._lib = self._lib
        twin._h = h
        twin._owner = self  # keep the owner (and its buffers) alive
        return twin

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.pd_predictor_destroy(self._h)
            self._h = None


class NativePredictorPool:
    """N request slots over ONE compiled executable and ONE device-resident
    parameter set (reference PredictorPool over AnalysisPredictor::Clone):
    slot 0 owns the client/executable/params, the rest are clones with
    their own output buffers, so concurrent requests on different slots
    don't race on results."""

    def __init__(self, model_prefix: str, size: int = 1,
                 plugin_path: Optional[str] = None,
                 options: Optional[str] = None):
        if size < 1:
            raise ValueError("pool size must be >= 1")
        first = NativePredictor(model_prefix, plugin_path=plugin_path,
                                options=options)
        self._predictors = [first] + [first._clone()
                                      for _ in range(size - 1)]

    def retrieve(self, idx: int) -> NativePredictor:
        return self._predictors[idx]

    def __len__(self):
        return len(self._predictors)
