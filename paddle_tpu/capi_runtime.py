"""Python side of the C inference ABI (paddle_tpu/capi/).

The C++ shim (capi.cc) embeds CPython and calls `create` / `Predictor.run`
here; this module owns the model, scope and the jit-compiled step —
exactly the path `Inferencer` uses, so the C ABI and the Python API share
one predictor implementation (reference analog: api_impl.cc
NativePaddlePredictor::Run driving the same Executor as python).
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


class Predictor:
    def __init__(self, model_dir: str):
        import paddle_tpu as fluid
        self._fluid = fluid
        self.scope = fluid.Scope()
        self.exe = fluid.Executor(fluid.TPUPlace(0))
        self.program, self.feed_names, self.fetch_targets = \
            fluid.io.load_inference_model(model_dir, self.exe,
                                          scope=self.scope)

    def run(self, feed_list: List[Tuple[str, tuple, str, bytes]]):
        """feed_list entries: (name, shape, dtype_str, raw_bytes); empty
        name means positional (feed_names order). Returns a list of
        (fetch_name, dtype_str, contiguous ndarray)."""
        feeds = {}
        for i, (name, shape, dtype, raw) in enumerate(feed_list):
            arr = np.frombuffer(raw, dtype=dtype).reshape(shape)
            feeds[name or self.feed_names[i]] = arr
        outs = self.exe.run(self.program, feed=feeds,
                            fetch_list=self.fetch_targets, scope=self.scope)
        results = []
        for tgt, v in zip(self.fetch_targets, outs):
            a = np.ascontiguousarray(np.asarray(v))
            if a.dtype == np.float64:
                a = a.astype(np.float32)
            name = tgt.name if hasattr(tgt, "name") else str(tgt)
            results.append((name, str(a.dtype), a))
        return results


def create(model_dir: str) -> Predictor:
    return Predictor(model_dir)
