"""paddle_tpu.inference — serving entry.

Reference parity: ``paddle.inference`` — ``Config`` (AnalysisConfig,
fluid/inference/api/analysis_config.cc), ``create_predictor`` →
``AnalysisPredictor`` (api/analysis_predictor.cc:1665, Run :1063).

TPU-native: the graph-optimization pass pipeline (267 IR passes, TensorRT
subgraphs) is replaced by XLA compilation of the exported StableHLO — the
optimizer IS the compiler.  The Python ``Predictor`` wraps the deserialized
``jax.export`` artifact; the **native path** is csrc/predictor (C++ shim
that drives the same artifact through the PJRT C API) for embedding in
C++ services, matching the reference's C++ serving story.

LLM serving lives in the sibling modules: ``serving.py`` (the
continuous-batching engine) and ``kv_cache.py`` (its paged KV
allocator, prefix cache, and paged attention path) — see
``inference/README.md``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

__all__ = ["Config", "Predictor", "create_predictor", "PredictorPool"]


class Config:
    """AnalysisConfig-shaped config.  GPU/TRT/MKLDNN knobs are accepted and
    recorded for API parity; on TPU they are inert (XLA owns optimization)."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        if prog_file and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self._model_prefix = prog_file
        self._params_file = params_file
        self._flags: Dict[str, object] = {}

    def set_model(self, prog_file: str, params_file: Optional[str] = None):
        if prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self._model_prefix = prog_file
        self._params_file = params_file

    def model_dir(self):
        return self._model_prefix

    # parity no-ops (recorded so callers can introspect)
    def enable_use_gpu(self, *a, **k):
        self._flags["use_gpu"] = True

    def disable_gpu(self):
        self._flags["use_gpu"] = False

    def enable_tensorrt_engine(self, *a, **k):
        self._flags["tensorrt"] = True

    def enable_mkldnn(self):
        self._flags["mkldnn"] = True

    def switch_ir_optim(self, flag=True):
        self._flags["ir_optim"] = flag

    def enable_memory_optim(self, flag=True):
        self._flags["memory_optim"] = flag

    def set_cpu_math_library_num_threads(self, n):
        self._flags["cpu_threads"] = n


class _Handle:
    """Zero-copy tensor handle (reference ZeroCopyTensor shape)."""

    def __init__(self):
        self._value = None
        self._shape = None

    def copy_from_cpu(self, arr: np.ndarray):
        arr = np.asarray(arr)
        if self._shape is not None:
            arr = arr.reshape(self._shape)
        self._value = arr
        self._shape = None

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._value)

    def reshape(self, shape):
        """ZeroCopyTensor::Reshape parity: reallocates to any shape — the
        held value is reshaped when element counts match, otherwise dropped
        and the shape applies to the next copy_from_cpu."""
        shape = tuple(shape)
        if self._value is not None and \
                int(np.prod(self._value.shape)) == int(np.prod(shape)):
            self._value = self._value.reshape(shape)
        else:
            self._value = None
            self._shape = shape

    @property
    def shape(self):
        return None if self._value is None else list(self._value.shape)


class Predictor:
    def __init__(self, config: Config, _shared_layer=None):
        if _shared_layer is not None:
            self._layer = _shared_layer
        else:
            from paddle_tpu.jit.save_load import load
            self._layer = load(config.model_dir())
        meta = self._layer._meta
        n_in = len(self._layer.input_specs)
        self._input_names = list(
            meta.get("input_names") or [f"x{i}" for i in range(n_in)])
        self._inputs = {n: _Handle() for n in self._input_names}
        self._outputs: List[np.ndarray] = []

    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_input_handle(self, name: str) -> _Handle:
        return self._inputs[name]

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        """reference AnalysisPredictor::Run / ZeroCopyRun."""
        if inputs is not None:
            for n, arr in zip(self._input_names, inputs):
                self._inputs[n].copy_from_cpu(arr)
        args = [self._inputs[n].copy_to_cpu() for n in self._input_names]
        out = self._layer(*args)
        import jax
        flat = jax.tree.leaves(out)
        self._outputs = [np.asarray(o._data if hasattr(o, "_data") else o)
                         for o in flat]
        return self._outputs

    def get_output_names(self) -> List[str]:
        return [f"out{i}" for i in range(len(self._outputs))]

    def get_output_handle(self, name: str) -> _Handle:
        h = _Handle()
        idx = int(name[3:])
        h.copy_from_cpu(self._outputs[idx])
        return h


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


class PredictorPool:
    """Pool sharing ONE loaded executable + parameter set across
    predictors (each has its own input/output handles — reference
    PredictorPool clones the program, shares the weights)."""

    def __init__(self, config: Config, size: int = 1):
        first = Predictor(config)
        self._predictors = [first] + [
            Predictor(config, _shared_layer=first._layer)
            for _ in range(size - 1)]

    def retrieve(self, idx: int) -> Predictor:
        return self._predictors[idx]
