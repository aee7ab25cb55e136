"""Op autosweep: every registered op gets a shape/finiteness check and — for
differentiable ops — a program-level gradient check against central finite
differences (reference: python/paddle/fluid/tests/unittests/op_test.py —
OpTest.check_output :288, check_grad :388 via get_numeric_gradient :48,
auto-swept over every op and place :343).

Coverage contract: `SPECS ∪ WAIVED == registry.registered_ops()` is asserted,
so adding an op without a spec (or an explicit, reasoned waiver) fails the
suite — the registry cannot silently grow unchecked ops.

The grad check exercises the FULL program machinery (LayerHelper shape
inference -> append_backward's generic vjp grad ops -> Executor's jitted
step), not jax.grad directly — it validates the framework's autodiff
plumbing per op, which is where bugs live. AMP variants re-run the check
with the executor's bf16 autocast policy for every op in the AMP op sets
(the policy rewrites dtypes mid-program and was previously unverified).
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import registry
from paddle_tpu.core.ir import seqlen_var_name

rng = np.random.RandomState(7)


def T(*shape, lo=-1.0, hi=1.0, dtype="float32"):
    if dtype.startswith("int"):
        return rng.randint(int(lo), int(hi), size=shape).astype(dtype)
    return (rng.uniform(lo, hi, size=shape)).astype(dtype)


def POS(*shape, lo=0.2, hi=2.0):
    return T(*shape, lo=lo, hi=hi)


class Spec:
    def __init__(self, inputs, attrs=None, outs=("Out",), grad=None,
                 lod=None, fwd_only=False, rtol=2e-2, atol=2e-3, eps=1e-3,
                 amp=False, check=None):
        """inputs: slot -> np array | [np arrays]; grad: slots to FD-check
        (None = all float inputs); lod: {slot: lengths}; outs: output slots
        (first one is reduced to the loss); check: optional fn(outs_np)."""
        self.inputs = inputs
        self.attrs = attrs or {}
        self.outs = list(outs)
        self.grad = grad
        self.lod = lod or {}
        self.fwd_only = fwd_only
        self.rtol, self.atol, self.eps = rtol, atol, eps
        self.amp = amp
        self.check = check


E2 = dict(inputs={"X": T(2, 3), "Y": T(2, 3)})          # same-shape binary
E2B = dict(inputs={"X": T(2, 3, 4), "Y": T(3,)}, attrs={"axis": 1})


def _act(**kw):
    return Spec(inputs={"X": T(2, 5)}, **kw)


SPECS = {
    # ---- elementwise unary ------------------------------------------------
    "abs": Spec(inputs={"X": T(2, 5) + np.sign(T(2, 5)) * 0.3}),
    "ceil": _act(grad=[]),     # piecewise-constant: FD is meaningless
    "floor": _act(grad=[]),
    "round": _act(grad=[]),
    "sign": _act(grad=[]),
    "cos": _act(),
    "sin": _act(),
    "exp": _act(),
    "log": Spec(inputs={"X": POS(2, 5)}),
    "sqrt": Spec(inputs={"X": POS(2, 5)}),
    "rsqrt": Spec(inputs={"X": POS(2, 5)}),
    "reciprocal": Spec(inputs={"X": POS(2, 5)}),
    "square": _act(),
    "sigmoid": _act(),
    "logsigmoid": _act(),
    "tanh": _act(),
    "tanh_shrink": _act(),
    "softplus": _act(),
    "softsign": _act(),
    "relu": Spec(inputs={"X": T(2, 5) + np.sign(T(2, 5)) * 0.2}),
    "relu6": Spec(inputs={"X": T(2, 5, lo=0.2, hi=5.0)}),
    "leaky_relu": Spec(inputs={"X": T(2, 5) + np.sign(T(2, 5)) * 0.2}),
    "elu": Spec(inputs={"X": T(2, 5) + np.sign(T(2, 5)) * 0.2}),
    "gelu": _act(),
    "brelu": Spec(inputs={"X": T(2, 5, lo=-8, hi=8)},
                  attrs={"t_min": -5.0, "t_max": 5.0}),
    "soft_relu": _act(),
    "swish": _act(),
    "hard_sigmoid": Spec(inputs={"X": T(2, 5, lo=-0.8, hi=0.8)}),
    "hard_shrink": Spec(inputs={"X": T(2, 5) * 3}, attrs={"threshold": 0.5}),
    "softshrink": Spec(inputs={"X": T(2, 5) * 3}, attrs={"lambda": 0.5}),
    "thresholded_relu": Spec(inputs={"X": T(2, 5) * 3},
                             attrs={"threshold": 1.0}),
    "pow": Spec(inputs={"X": POS(2, 5)}, attrs={"factor": 2.5}),
    "clip": Spec(inputs={"X": T(2, 5) * 2}, attrs={"min": -0.7, "max": 0.7}),
    "clip_by_norm": Spec(inputs={"X": T(2, 5)}, attrs={"max_norm": 0.5}),
    "scale": Spec(inputs={"X": T(2, 5)}, attrs={"scale": 3.0, "bias": 0.5}),
    "cumsum": Spec(inputs={"X": T(2, 5)}, attrs={"axis": 1}),
    "isfinite": _act(grad=[]),
    "logical_not": Spec(inputs={"X": T(2, 3, lo=0, hi=2, dtype="int32")
                                .astype(bool)}, grad=[]),

    # ---- elementwise binary ----------------------------------------------
    "elementwise_add": Spec(**E2),
    "elementwise_sub": Spec(**E2),
    "elementwise_mul": Spec(**E2B),
    "elementwise_div": Spec(inputs={"X": T(2, 3), "Y": POS(2, 3)}),
    "elementwise_max": Spec(**E2),
    "elementwise_min": Spec(**E2),
    "elementwise_pow": Spec(inputs={"X": POS(2, 3), "Y": POS(2, 3)}),
    "elementwise_mod": Spec(inputs={"X": T(2, 3, lo=0, hi=20, dtype="int64"),
                                    "Y": T(2, 3, lo=1, hi=7, dtype="int64")},
                            grad=[]),
    "elementwise_floordiv": Spec(
        inputs={"X": T(2, 3, lo=0, hi=20, dtype="int64"),
                "Y": T(2, 3, lo=1, hi=7, dtype="int64")}, grad=[]),
    "maximum": Spec(**E2),
    "logical_and": Spec(inputs={"X": T(2, 3, lo=0, hi=2, dtype="int32").astype(bool),
                                "Y": T(2, 3, lo=0, hi=2, dtype="int32").astype(bool)},
                        grad=[]),
    "logical_or": Spec(inputs={"X": T(2, 3, lo=0, hi=2, dtype="int32").astype(bool),
                               "Y": T(2, 3, lo=0, hi=2, dtype="int32").astype(bool)},
                       grad=[]),
    "logical_xor": Spec(inputs={"X": T(2, 3, lo=0, hi=2, dtype="int32").astype(bool),
                                "Y": T(2, 3, lo=0, hi=2, dtype="int32").astype(bool)},
                        grad=[]),
    "equal": Spec(inputs={"X": T(2, 3, lo=0, hi=3, dtype="int64"),
                          "Y": T(2, 3, lo=0, hi=3, dtype="int64")}, grad=[]),
    "not_equal": Spec(inputs={"X": T(2, 3, lo=0, hi=3, dtype="int64"),
                              "Y": T(2, 3, lo=0, hi=3, dtype="int64")}, grad=[]),
    "less_than": Spec(**E2, grad=[]),
    "less_equal": Spec(**E2, grad=[]),
    "greater_than": Spec(**E2, grad=[]),
    "greater_equal": Spec(**E2, grad=[]),

    # ---- matmul family ----------------------------------------------------
    "mul": Spec(inputs={"X": T(3, 4), "Y": T(4, 5)}, amp=True),
    "matmul": Spec(inputs={"X": T(2, 3, 4), "Y": T(2, 4, 5)}, amp=True),

    # ---- reductions / argminmax ------------------------------------------
    "reduce_sum": Spec(inputs={"X": T(2, 3, 4)}, attrs={"dim": [1]}),
    "reduce_mean": Spec(inputs={"X": T(2, 3, 4)},
                        attrs={"dim": [0, 2], "keep_dim": True}),
    "reduce_max": Spec(inputs={"X": T(2, 3, 4) * 5}, attrs={"dim": [1]}),
    "reduce_min": Spec(inputs={"X": T(2, 3, 4) * 5}, attrs={"dim": [2]}),
    "reduce_prod": Spec(inputs={"X": POS(2, 3)}, attrs={"dim": [1]}),
    "mean": Spec(inputs={"X": T(3, 4)}),
    "sum": Spec(inputs={"X": [T(2, 3), T(2, 3), T(2, 3)]}),
    "arg_max": Spec(inputs={"X": T(2, 5) * 5}, attrs={"axis": 1}, grad=[]),
    "argsort": Spec(inputs={"X": T(3, 6)}, attrs={"axis": -1},
                    outs=("Out", "Indices"), grad=[]),
    "is_empty": Spec(inputs={"X": T(2, 3)}, grad=[]),
    "arg_min": Spec(inputs={"X": T(2, 5) * 5}, attrs={"axis": 1}, grad=[]),
    "top_k": Spec(inputs={"X": T(2, 8) * 5}, attrs={"k": 3},
                  outs=("Out", "Indices"), grad=[]),

    # ---- shape manipulation ----------------------------------------------
    "reshape": Spec(inputs={"X": T(2, 6)}, attrs={"shape": [3, 4]}),
    "transpose": Spec(inputs={"X": T(2, 3, 4)}, attrs={"axis": [1, 0, 2]}),
    "concat": Spec(inputs={"X": [T(2, 3), T(2, 4)]}, attrs={"axis": 1}),
    "split": Spec(inputs={"X": T(2, 6)},
                  attrs={"num": 3, "axis": 1},
                  outs=("Out",)),
    "stack": Spec(inputs={"X": [T(2, 3), T(2, 3)]}, attrs={"axis": 0},
                  outs=("Y",)),
    "unstack": Spec(inputs={"X": T(3, 2, 4)}, attrs={"axis": 0},
                    outs=("Y",)),
    "squeeze": Spec(inputs={"X": T(2, 1, 4)}, attrs={"axes": [1]}),
    "unsqueeze": Spec(inputs={"X": T(2, 4)}, attrs={"axes": [1]}),
    "flatten": Spec(inputs={"X": T(2, 3, 4)}, attrs={"axis": 1}),
    "expand": Spec(inputs={"X": T(1, 3)}, attrs={"expand_times": [4, 1]}),
    "expand_dims_tile": Spec(inputs={"X": T(2, 3)},
                             attrs={"times": [2, 1]}),
    "pad": Spec(inputs={"X": T(2, 3)},
                attrs={"paddings": [0, 1, 1, 0], "pad_value": 0.5}),
    "pad2d": Spec(inputs={"X": T(1, 2, 3, 3)},
                  attrs={"paddings": [1, 1, 1, 1], "mode": "constant"}),
    "slice": Spec(inputs={"Input": T(3, 5)},
                  attrs={"axes": [0, 1], "starts": [1, 0], "ends": [3, 4]}),
    "reverse": Spec(inputs={"X": T(2, 4)}, attrs={"axis": [1]}),
    "cast": Spec(inputs={"X": T(2, 3)}, attrs={"out_dtype": "float32"}),
    "one_hot": Spec(inputs={"X": T(4, 1, lo=0, hi=5, dtype="int64")},
                    attrs={"depth": 6}, grad=[]),
    "shape": Spec(inputs={"Input": T(2, 3)}, grad=[]),
    "range": Spec(inputs={}, attrs={"start": 0.0, "end": 5.0, "step": 1.0},
                  grad=[]),
    "fill_constant": Spec(inputs={}, attrs={"shape": [2, 3],
                                            "dtype": "float32",
                                            "value": 1.5}, grad=[],
                          check=lambda o: np.testing.assert_allclose(
                              o[0], np.full((2, 3), 1.5))),
    "fill_constant_batch_size_like": Spec(
        inputs={"Input": T(4, 3)},
        attrs={"shape": [-1, 2], "dtype": "float32", "value": 2.0},
        grad=[],
        check=lambda o: np.testing.assert_allclose(o[0],
                                                   np.full((4, 2), 2.0))),
    "assign": Spec(inputs={"X": T(2, 3)}),
    "assign_value": Spec(inputs={}, attrs={"shape": [2, 2],
                                           "dtype": "float32",
                                           "values": [1.0, 2.0, 3.0, 4.0]},
                         grad=[]),
    "increment": Spec(inputs={"X": T(1)}, attrs={"step": 2.0}, grad=[]),

    # ---- gather/scatter ---------------------------------------------------
    "gather": Spec(inputs={"X": T(5, 3),
                           "Index": np.array([0, 2, 4], np.int64)},
                   grad=["X"]),
    "gather_nd": Spec(inputs={"X": T(3, 4),
                              "Index": np.array([[0, 1], [2, 3]], np.int64)},
                      grad=["X"]),
    "batch_gather": Spec(inputs={"X": T(2, 5, 3),
                                 "Index": T(2, 2, lo=0, hi=5, dtype="int64")},
                         grad=["X"]),
    "scatter": Spec(inputs={"X": T(5, 3), "Ids": np.array([1, 3], np.int64),
                            "Updates": T(2, 3)}, grad=["X", "Updates"]),
    "lookup_table": Spec(inputs={"W": T(10, 4),
                                 "Ids": T(3, 2, lo=0, hi=10, dtype="int64")},
                         grad=["W"]),
    "sequence_mask": Spec(inputs={"X": np.array([2, 4, 1], np.int64)},
                          attrs={"maxlen": 5}, grad=[], outs=("Y",)),

    # ---- NN compute -------------------------------------------------------
    "conv2d": Spec(inputs={"Input": T(2, 3, 8, 8), "Filter": T(4, 3, 3, 3)},
                   attrs={"strides": [1, 1], "paddings": [1, 1],
                          "groups": 1}, outs=("Output",), amp=True,
                   rtol=5e-2, atol=5e-3),
    "depthwise_conv2d": Spec(
        inputs={"Input": T(2, 3, 8, 8), "Filter": T(3, 1, 3, 3)},
        attrs={"strides": [1, 1], "paddings": [1, 1], "groups": 3},
        outs=("Output",), amp=True, rtol=5e-2, atol=5e-3),
    "conv2d_transpose": Spec(
        inputs={"Input": T(2, 4, 4, 4), "Filter": T(4, 3, 3, 3)},
        attrs={"strides": [2, 2], "paddings": [1, 1]},
        outs=("Output",), amp=True, rtol=5e-2, atol=5e-3),
    "pool2d": Spec(inputs={"X": T(2, 3, 6, 6)},
                   attrs={"pooling_type": "avg", "ksize": [2, 2],
                          "strides": [2, 2], "paddings": [0, 0]}),
    "batch_norm": Spec(inputs={"X": T(4, 3, 5, 5), "Scale": POS(3),
                               "Bias": T(3), "Mean": T(3),
                               "Variance": POS(3)},
                       attrs={"epsilon": 1e-5, "momentum": 0.9},
                       outs=("Y",), grad=["X", "Scale", "Bias"]),
    "layer_norm": Spec(inputs={"X": T(4, 6), "Scale": POS(6), "Bias": T(6)},
                       attrs={"begin_norm_axis": 1}, outs=("Y",)),
    "lrn": Spec(inputs={"X": T(2, 5, 4, 4)}, attrs={"n": 3}),
    "l2_normalize": Spec(inputs={"X": T(3, 4) + 0.5}, attrs={"axis": 1}),
    "softmax": Spec(inputs={"X": T(3, 5)}, amp=True),
    "log_softmax": Spec(inputs={"X": T(3, 5)}),
    "prelu": Spec(inputs={"X": T(2, 4) + np.sign(T(2, 4)) * 0.2,
                          "Alpha": POS(1)}, attrs={"mode": "all"}),
    "grid_sampler": Spec(inputs={"X": T(1, 2, 4, 4),
                                 "Grid": T(1, 3, 3, 2, lo=-0.9, hi=0.9)},
                         outs=("Output",), rtol=5e-2, atol=5e-3),
    "im2sequence": Spec(inputs={"X": T(1, 2, 4, 4)},
                        attrs={"kernels": [2, 2], "strides": [2, 2],
                               "paddings": [0, 0, 0, 0]}),
    "pixel?": None,
}
SPECS.pop("pixel?")

SPECS.update({
    # ---- RNN --------------------------------------------------------------
    "lstm": Spec(inputs={"Input": T(2, 4, 12), "Weight": T(3, 12),
                         "Bias": T(1, 12)},
                 lod={"Input": np.array([4, 2], np.int32)},
                 outs=("Hidden",), grad=["Weight"], rtol=5e-2, atol=5e-3),
    "gru": Spec(inputs={"Input": T(2, 4, 9), "Weight": T(3, 9),
                        "Bias": T(1, 9)},
                lod={"Input": np.array([3, 4], np.int32)},
                outs=("Hidden",), grad=["Weight"], rtol=5e-2, atol=5e-3),
    "lstm_unit": Spec(inputs={"X": T(3, 8), "C_prev": T(3, 2)},
                      outs=("C", "H")),
    "gru_unit": Spec(inputs={"Input": T(3, 9), "HiddenPrev": T(3, 3),
                             "Weight": T(3, 9)},
                     outs=("Hidden",), grad=["Weight", "HiddenPrev"],
                     rtol=5e-2, atol=5e-3),
    "row_conv": Spec(inputs={"X": T(2, 5, 3), "Filter": T(2, 3)}),

    # ---- sequence ops -----------------------------------------------------
    "sequence_pool": Spec(inputs={"X": T(3, 4, 2)},
                          attrs={"pooltype": "SUM"},
                          lod={"X": np.array([4, 2, 3], np.int32)}),
    "sequence_softmax": Spec(inputs={"X": T(3, 4)},
                             lod={"X": np.array([4, 2, 3], np.int32)}),
    "sequence_expand": Spec(inputs={"X": T(3, 2), "Y": T(3, 4, 2)},
                            grad=["X"]),
    "sequence_expand_as": Spec(inputs={"X": T(3, 2), "Y": T(3, 4, 2)},
                               grad=["X"]),
    # ragged rows: the old padded-axis concat embedded padding
    # mid-sequence for exactly this spec shape (round-5 fix)
    "sequence_concat": Spec(inputs={"X": [T(2, 3, 4), T(2, 2, 4)]},
                            lod={"X": [np.array([2, 3], np.int32),
                                       np.array([1, 2], np.int32)]}),
    "sequence_reshape": Spec(inputs={"X": T(2, 4, 6)},
                             attrs={"new_dim": 12}),
    "sequence_conv": Spec(inputs={"X": T(2, 5, 3), "Filter": T(9, 4)},
                          attrs={"contextLength": 3, "contextStart": -1}),

    # ---- losses / metrics -------------------------------------------------
    "cross_entropy": Spec(inputs={"X": POS(4, 5, lo=0.05, hi=1.0) /
                                  POS(4, 5, lo=0.05, hi=1.0).sum(1, keepdims=True),
                                  "Label": T(4, 1, lo=0, hi=5, dtype="int64")},
                          grad=["X"], outs=("Y",)),
    "softmax_with_cross_entropy": Spec(
        inputs={"Logits": T(4, 5),
                "Label": T(4, 1, lo=0, hi=5, dtype="int64")},
        grad=["Logits"], outs=("Loss",)),
    "sigmoid_cross_entropy_with_logits": Spec(
        inputs={"X": T(4, 3), "Label": T(4, 3, lo=0, hi=2,
                                         dtype="int64").astype("float32")},
        grad=["X"]),
    "square_error_cost": Spec(inputs={"X": T(4, 3), "Y": T(4, 3)}),
    "smooth_l1_loss": Spec(inputs={"X": T(4, 3) * 2, "Y": T(4, 3)},
                           grad=["X"]),
    "huber_loss": Spec(inputs={"X": T(4, 1) * 2, "Y": T(4, 1)},
                       attrs={"delta": 1.0}, grad=["X"]),
    "log_loss": Spec(inputs={"Predicted": POS(4, 1, lo=0.1, hi=0.9),
                             "Labels": T(4, 1, lo=0, hi=2,
                                         dtype="int64").astype("float32")},
                     grad=["Predicted"], outs=("Loss",)),
    "hinge_loss": Spec(inputs={"Logits": T(4, 1) * 2,
                               "Labels": (T(4, 1, lo=0, hi=2, dtype="int64")
                                          .astype("float32"))},
                       grad=["Logits"], outs=("Loss",)),
    "rank_loss": Spec(inputs={"Label": T(4, 1, lo=0, hi=2,
                                         dtype="int64").astype("float32"),
                              "Left": T(4, 1), "Right": T(4, 1)},
                      grad=["Left", "Right"]),
    "margin_rank_loss": Spec(
        inputs={"Label": np.ones((4, 1), np.float32),
                "X1": T(4, 1) * 2, "X2": T(4, 1)},
        attrs={"margin": 0.1}, grad=["X1", "X2"]),
    "cos_sim": Spec(inputs={"X": T(4, 3) + 0.5, "Y": T(4, 3) + 0.5}),
    "hierarchical_sigmoid": Spec(
        inputs={"X": T(4, 6), "W": T(7, 6),
                "Label": T(4, 1, lo=0, hi=8, dtype="int64")},
        attrs={"num_classes": 8}, grad=["X", "W"]),
    "linear_chain_crf": Spec(
        inputs={"Emission": T(2, 4, 5),
                "Transition": T(7, 5),
                "Label": T(2, 4, 1, lo=0, hi=5, dtype="int64")},
        lod={"Emission": np.array([4, 3], np.int32)},
        outs=("LogLikelihood",), grad=["Emission", "Transition"],
        rtol=5e-2, atol=5e-3),
    "crf_decoding": Spec(
        inputs={"Emission": T(2, 4, 5), "Transition": T(7, 5)},
        lod={"Emission": np.array([4, 3], np.int32)},
        outs=("ViterbiPath",), grad=[]),
    "warpctc": Spec(
        inputs={"Logits": T(2, 6, 5),
                "Label": T(2, 3, lo=1, hi=5, dtype="int64")},
        attrs={"blank": 0}, outs=("Loss",), grad=["Logits"],
        rtol=5e-2, atol=5e-3),
    "edit_distance": Spec(
        inputs={"Hyps": T(2, 4, lo=1, hi=6, dtype="int64"),
                "Refs": T(2, 4, lo=1, hi=6, dtype="int64")}, grad=[]),
    "accuracy": Spec(inputs={"Out": POS(4, 3), "Indices":
                             T(4, 1, lo=0, hi=3, dtype="int64"),
                             "Label": T(4, 1, lo=0, hi=3, dtype="int64")},
                     outs=("Accuracy",), grad=[]),

    # ---- optimizer ops (fwd math vs numpy) --------------------------------
    "sgd": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                        "LearningRate": np.array([0.1], np.float32)},
                outs=("ParamOut",), grad=[]),
    "momentum": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                             "Velocity": T(3, 2),
                             "LearningRate": np.array([0.1], np.float32)},
                     attrs={"mu": 0.9}, outs=("ParamOut",), grad=[]),
    "adagrad": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                            "Moment": POS(3, 2),
                            "LearningRate": np.array([0.1], np.float32)},
                    outs=("ParamOut",), grad=[]),
    "adam": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                         "Moment1": T(3, 2), "Moment2": POS(3, 2),
                         "Beta1Pow": np.array([0.9], np.float32),
                         "Beta2Pow": np.array([0.999], np.float32),
                         "LearningRate": np.array([0.1], np.float32)},
                 outs=("ParamOut",), grad=[]),
    "adamax": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                           "Moment": T(3, 2), "InfNorm": POS(3, 2),
                           "Beta1Pow": np.array([0.9], np.float32),
                           "LearningRate": np.array([0.1], np.float32)},
                   outs=("ParamOut",), grad=[]),
    "adadelta": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                             "AvgSquaredGrad": POS(3, 2),
                             "AvgSquaredUpdate": POS(3, 2)},
                     outs=("ParamOut",), grad=[]),
    "decayed_adagrad": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                                    "Moment": POS(3, 2),
                                    "LearningRate": np.array([0.1],
                                                             np.float32)},
                            outs=("ParamOut",), grad=[]),
    "rmsprop": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                            "MeanSquare": POS(3, 2), "Moment": T(3, 2),
                            "LearningRate": np.array([0.1], np.float32)},
                    outs=("ParamOut",), grad=[]),
    "ftrl": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                         "SquaredAccumulator": POS(3, 2),
                         "LinearAccumulator": T(3, 2),
                         "LearningRate": np.array([0.1], np.float32)},
                 outs=("ParamOut",), grad=[]),
    "proximal_gd": Spec(inputs={"Param": T(3, 2), "Grad": T(3, 2),
                                "LearningRate": np.array([0.1], np.float32)},
                        outs=("ParamOut",), grad=[]),
    "proximal_adagrad": Spec(
        inputs={"Param": T(3, 2), "Grad": T(3, 2), "Moment": POS(3, 2),
                "LearningRate": np.array([0.1], np.float32)},
        outs=("ParamOut",), grad=[]),
    # step below min_average_window: sum_1 accumulates param, counters tick
    "average_accumulates": Spec(
        inputs={"param": T(3, 2), "in_sum_1": T(3, 2),
                "in_sum_2": np.zeros((3, 2), np.float32),
                "in_sum_3": np.zeros((3, 2), np.float32),
                "in_num_accumulates": np.array([1], np.int32),
                "in_old_num_accumulates": np.array([0], np.int32),
                "in_num_updates": np.array([1], np.int32)},
        attrs={"average_window": 0.15, "min_average_window": 100,
               "max_average_window": 1000},
        outs=("out_sum_1", "out_num_accumulates", "out_num_updates"),
        grad=[],
        check=lambda o: (o[1][0] == 2 and o[2][0] == 2)),

    # ---- RNG ops: forward-only statistical checks -------------------------
    "dropout": Spec(inputs={"X": np.ones((50, 50), np.float32)},
                    attrs={"dropout_prob": 0.3}, grad=[],
                    check=lambda o: abs((o[0] == 0).mean() - 0.3) < 0.08),
    "uniform_random": Spec(inputs={}, attrs={"shape": [100, 10],
                                             "min": -2.0, "max": 2.0,
                                             "dtype": "float32"},
                           grad=[],
                           check=lambda o: (o[0].min() >= -2.0
                                            and o[0].max() <= 2.0)),
    "uniform_random_batch_size_like": Spec(
        inputs={"Input": T(8, 3)},
        attrs={"shape": [-1, 5], "min": -1.0, "max": 1.0}, grad=[],
        check=lambda o: o[0].shape == (8, 5)),
    "gaussian_random": Spec(inputs={}, attrs={"shape": [100, 10],
                                              "mean": 0.0, "std": 1.0,
                                              "dtype": "float32"},
                            grad=[],
                            check=lambda o: abs(float(o[0].mean())) < 0.2),
    "truncated_gaussian_random": Spec(
        inputs={}, attrs={"shape": [100, 10], "mean": 0.0, "std": 1.0,
                          "dtype": "float32"},
        grad=[], check=lambda o: np.abs(o[0]).max() <= 2.01),
    "nce": Spec(inputs={"Input": T(4, 6),
                        "Label": T(4, 1, lo=0, hi=8, dtype="int64"),
                        "Weight": T(8, 6)},
                attrs={"num_total_classes": 8, "num_neg_samples": 3},
                outs=("Cost",), grad=[]),

    # ---- misc -------------------------------------------------------------
    "sinusoid_pos_encoding": Spec(inputs={},
                                  attrs={"size": 10, "d_model": 8},
                                  grad=[]),
    "causal_mask": Spec(inputs={}, attrs={"size": 6}, grad=[]),

    # ---- detection family (value-level tests in tests/test_detection.py;
    # sweep covers shapes/finiteness + the differentiable pieces) ----------
    "iou_similarity": Spec(
        inputs={"X": np.sort(rng.rand(4, 2, 2).astype(np.float32),
                             axis=1).reshape(4, 4)[:, [0, 2, 1, 3]],
                "Y": np.sort(rng.rand(6, 2, 2).astype(np.float32),
                             axis=1).reshape(6, 4)[:, [0, 2, 1, 3]]},
        grad=[]),
    "smooth_l1_elementwise": Spec(inputs={"X": T(3, 4) * 3 + 0.05}),
    "greater_equal_scalar0": Spec(inputs={"X": T(3, 4)}, grad=[]),
    "softmax_ce_no_reduce": Spec(
        inputs={"Logits": T(2, 5, 4),
                "Label": T(2, 5, 1, lo=0, hi=4, dtype="int64")},
        grad=["Logits"]),
    "box_encode_per_prior": Spec(
        inputs={"TargetBox": POS(2, 3, 4, lo=0.3, hi=0.9),
                "PriorBox": np.sort(rng.rand(3, 2, 2).astype(np.float32),
                                    axis=1).reshape(3, 4)[:, [0, 2, 1, 3]]},
        outs=("OutputBox",), grad=["TargetBox"], rtol=5e-2, atol=5e-3),
    "fake_dequantize_max_abs": Spec(
        inputs={"X": T(3, 4) * 100, "Scale": np.array([2.0], np.float32)},
        grad=["X"]),
    "fake_quantize_abs_max": Spec(
        inputs={"X": T(3, 4)}, outs=("Out", "OutScale"), grad=[]),
    "fake_quantize_range_abs_max": Spec(
        inputs={"X": T(3, 4), "InScale": np.array([1.5], np.float32)},
        outs=("Out", "OutScale"), grad=[]),
    # fluid-wire comm quantizer: lattice function (round), FD meaningless;
    # the conservation property Out + ResidualOut == Grad + Residual and
    # host-codec equality are pinned in tests/test_wire.py
    "comm_quant_dequant": Spec(
        inputs={"Grad": T(3, 7), "Residual": T(3, 7) * 0.01},
        attrs={"codec": "int8", "chunk": 8},
        outs=("Out", "ResidualOut"), grad=[]),
    # ---- breadth ops (extra_nn.py) ---------------------------------------
    "conv3d": Spec(inputs={"Input": T(1, 2, 5, 5, 5),
                           "Filter": T(3, 2, 3, 3, 3)},
                   attrs={"strides": [1, 1, 1], "paddings": [1, 1, 1]},
                   outs=("Output",), rtol=5e-2, atol=5e-3),
    "conv3d_transpose": Spec(
        inputs={"Input": T(1, 2, 3, 3, 3), "Filter": T(2, 3, 3, 3, 3)},
        attrs={"strides": [2, 2, 2], "paddings": [1, 1, 1]},
        outs=("Output",), rtol=5e-2, atol=5e-3),
    "pool3d": Spec(inputs={"X": T(1, 2, 4, 4, 4)},
                   attrs={"pooling_type": "avg", "ksize": [2, 2, 2],
                          "strides": [2, 2, 2], "paddings": [0, 0, 0]}),
    "bilinear_interp": Spec(inputs={"X": T(1, 2, 4, 4)},
                            attrs={"out_h": 8, "out_w": 8}),
    "crop": Spec(inputs={"X": T(2, 6, 6)},
                 attrs={"shape": [1, 3, 3], "offsets": [0, 1, 2]}),
    "random_crop": Spec(inputs={"X": T(2, 3, 6, 6)},
                        attrs={"shape": [4, 4]}, grad=[],
                        check=lambda o: o[0].shape == (2, 3, 4, 4)),
    "label_smooth": Spec(inputs={"X": POS(3, 5)},
                         attrs={"epsilon": 0.1}),
    "multiplex": Spec(inputs={"X": [T(4, 3), T(4, 3)],
                              "Ids": T(4, 1, lo=0, hi=2, dtype="int32")},
                      grad=[]),
    "mean_iou": Spec(inputs={"Predictions": T(2, 6, lo=0, hi=3,
                                              dtype="int32"),
                             "Labels": T(2, 6, lo=0, hi=3, dtype="int32")},
                     attrs={"num_classes": 3},
                     outs=("OutMeanIou",), grad=[]),
    "roi_pool": Spec(
        inputs={"X": T(1, 2, 6, 6),
                "ROIs": np.array([[0, 0, 0, 3, 3], [0, 1, 1, 5, 5]],
                                 np.float32)},
        attrs={"pooled_height": 2, "pooled_width": 2,
               "spatial_scale": 1.0},
        grad=["X"], rtol=5e-2, atol=5e-3),
    "ctc_greedy_decoder": Spec(
        inputs={"X": T(2, 5, 4)}, attrs={"blank": 0},
        outs=("Out", "OutLen"), grad=[]),
    "lod_reset": Spec(inputs={"X": T(4, 3),
                              "Y": np.array([2, 2], np.int32)}),
    "chunk_eval": Spec(
        inputs={"X": T(1, 6, lo=0, hi=4, dtype="int32"),
                "Label": T(1, 6, lo=0, hi=4, dtype="int32")},
        attrs={"num_chunk_types": 2, "chunk_scheme": "IOB"},
        outs=("NumInferChunks", "NumLabelChunks", "NumCorrectChunks"),
        grad=[]),
    "lstmp": Spec(inputs={"Input": T(2, 4, 12), "Weight": T(2, 12),
                          "ProjWeight": T(3, 2), "Bias": T(1, 12)},
                  lod={"Input": np.array([4, 2], np.int32)},
                  outs=("Projection",), grad=["Weight", "ProjWeight"],
                  rtol=5e-2, atol=5e-3),

    # ---- decoder-LM blocks and sparse experts ------------------------------
    "rms_norm": Spec(inputs={"X": T(4, 6), "Scale": POS(6)}, outs=("Y",)),
    "rotary_embedding": Spec(inputs={"X": T(1, 2, 4, 6)},
                             attrs={"theta": 100.0}),
    "swiglu": Spec(inputs={"Gate": T(3, 4), "Up": T(3, 4)}),
    "exit_gate": Spec(inputs={"X": T(2, 3, 5), "W": T(5, 1), "Bias": T(1)}),
    "gated_rms_norm": Spec(inputs={"X": T(3, 2, 6), "Gate": T(3, 2, 6),
                                   "Scale": POS(6)}, outs=("Y",)),
    "causal_conv1d": Spec(inputs={"X": T(2, 6, 3), "W": T(3, 4)},
                          attrs={"activation": "silu"}),
    "delta_rule_gates": Spec(inputs={"A": T(2, 4, 3), "B": T(2, 4, 3),
                                     "ALog": T(3), "DtBias": T(3)},
                             outs=("G", "Beta")),
    # two chunks of four tokens, a key head serving two value heads
    "gated_delta_rule": Spec(inputs={"Q": T(1, 8, 1, 4), "K": T(1, 8, 1, 4),
                                     "V": T(1, 8, 2, 3),
                                     "G": T(1, 8, 2, lo=-1.0, hi=-0.05),
                                     "Beta": T(1, 8, 2, lo=0.1, hi=0.9)},
                             attrs={"chunk": 4}),
    "relu2": Spec(inputs={"X": T(3, 4)}),
    "kda_gates": Spec(inputs={"F": T(2, 4, 6), "B": T(2, 4, 2),
                              "ALog": T(2), "DtBias": T(6)},
                      attrs={"lower_bound": -5.0}, outs=("G", "Beta")),
    # two chunks of four tokens, two heads, a decay per key channel
    "kda_delta_rule": Spec(inputs={"Q": T(1, 8, 2, 4), "K": T(1, 8, 2, 4),
                                   "V": T(1, 8, 2, 3),
                                   "G": T(1, 8, 2, 4, lo=-5.0, hi=-0.05),
                                   "Beta": T(1, 8, 2, lo=0.1, hi=0.9)},
                           attrs={"chunk": 4}),
    "ssd_gates": Spec(inputs={"DtRaw": T(2, 4, 3), "DtBias": T(3),
                              "ALog": T(3)}, outs=("Dt", "A")),
    # two chunks of four tokens, a group serving two heads
    "ssd_scan": Spec(inputs={"X": T(1, 8, 2, 3),
                             "Dt": T(1, 8, 2, lo=0.05, hi=0.5),
                             "A": T(1, 8, 2, lo=-1.0, hi=-0.05),
                             "B": T(1, 8, 1, 4), "C": T(1, 8, 1, 4),
                             "D": T(2)}, attrs={"chunk": 4}),
    # two chunks of four tokens, three channels of two states each
    "selective_scan": Spec(inputs={"X": T(1, 8, 3), "DtRaw": T(1, 8, 3),
                                   "DtBias": T(3, lo=-2.0, hi=0.0),
                                   "ALog": T(3, 2), "B": T(1, 8, 2),
                                   "C": T(1, 8, 2), "D": T(3)},
                           attrs={"chunk": 4}),
    "moe_router": Spec(inputs={"X": T(6, 5), "W": T(5, 4) * 2},
                       attrs={"k": 2},
                       outs=("TopKWeight", "TopKIndex", "TokensPerExpert",
                             "Probs", "LogSumExp")),
    # expert 1 of 4 gets no row; groups of whole 8-row tiles, 8 + 4 * 8 rows
    "moe_dispatch": Spec(inputs={"X": T(4, 3),
                                 "TopKIndex": np.array(
                                     [[2, 0], [3, 2], [0, 3], [2, 3]],
                                     "int32"),
                                 "TokensPerExpert": np.array(
                                     [2, 0, 3, 3], "int32")},
                         attrs={"row_tile": 8},
                         outs=("XSorted", "Slot", "Source", "GroupSizes")),
    # the middle expert gets no row: a group of size zero
    "grouped_matmul": Spec(inputs={"X": T(6, 3), "W": T(3, 3, 4),
                                   "GroupSizes": np.array([2, 0, 4],
                                                          "int32")},
                           amp=True),
    # 4 tokens x 2 slots in 3 groups of 4 rows: rows 3, 7, 10, 11 are padding
    "moe_combine": Spec(inputs={"Y": T(12, 3), "TopKWeight": POS(4, 2),
                                "Slot": np.array(
                                    [4, 0, 8, 5, 1, 9, 6, 2], "int32"),
                                "Source": np.array(
                                    [1, 4, 7, -1, 0, 3, 6, -1, 2, 5, -1, -1],
                                    "int32")}),
})

# Waivers: ops whose correct behavior needs surrounding machinery that a
# one-op program cannot express; each points at the dedicated test that
# covers it.
WAIVED = {
    "while": "sub-block loop; tests/test_control_flow.py",
    "bounded_while": "sub-block loop; tests/test_dynamic_rnn.py",
    "static_rnn": "sub-block scan; tests/test_control_flow.py",
    "dynamic_rnn": "sub-block scan; tests/test_dynamic_rnn.py",
    "conditional_block": "sub-block branch; tests/test_control_flow.py",
    "if_else": "two sub-blocks; tests/test_dynamic_rnn.py",
    "select_input": "needs branch plumbing; tests/test_machine_translation.py",
    "array_write": "tensor-array state; tests/test_dynamic_rnn.py",
    "array_read": "tensor-array state; tests/test_dynamic_rnn.py",
    "array_length": "tensor-array state; tests/test_dynamic_rnn.py",
    "array_to_lod_tensor": "rank-table plumbing; tests/test_dynamic_rnn.py",
    "lod_tensor_to_array": "rank-table plumbing; tests/test_dynamic_rnn.py",
    "lod_rank_table": "rank-table plumbing; tests/test_dynamic_rnn.py",
    "max_sequence_len": "rank-table plumbing; tests/test_dynamic_rnn.py",
    "shrink_memory": "rank-table plumbing; tests/test_dynamic_rnn.py",
    "reorder_lod_tensor_by_rank": "rank-table plumbing; tests/test_dynamic_rnn.py",
    "beam_search_step": "beam state machine; tests/test_machine_translation.py",
    "beam_backtrack": "beam state machine; tests/test_machine_translation.py",
    "tile_beam": "beam plumbing; tests/test_machine_translation.py",
    "fused_attention": "pallas kernel; tests/test_flash_attention.py",
    "dsa_index_scores": "no gradient by design, minus infinity above the "
                        "diagonal; tests/test_keye_vl2.py",
    "dsa_select": "int8 result, no gradient; tests/test_keye_vl2.py",
    "paged_attention": "stateful KV-cache step; tests/test_decode.py",
    "prefill_attention": "stateful KV-cache step; tests/test_decode.py",
    "paged_attention_q8": "stateful int8-KV step; tests/test_torrent.py "
                          "parity vs fp32 cache",
    "prefill_attention_q8": "stateful int8-KV step; tests/test_torrent.py "
                            "parity vs fp32 cache",
    "gather_last_token": "index gather, inference-only; tests/test_decode.py",
    "auc": "stateful metric accumulators; tests/test_smoke.py metrics",
    "sequence_slice": "padded-slice vs numpy; tests/test_api_breadth.py",
    "sequence_erase": "stable-sort compaction; tests/test_api_breadth.py",
    "prior_box": "value-checked vs hand math; tests/test_detection.py",
    "anchor_generator": "prior_box sibling; tests/test_detection.py",
    "box_coder": "encode/decode roundtrip; tests/test_detection.py",
    "bipartite_match": "greedy matching; tests/test_detection.py",
    "target_assign": "gather/mask; tests/test_detection.py",
    "multiclass_nms": "suppression+padding; tests/test_detection.py",
    "mine_hard_examples": "neg mining counts; tests/test_detection.py",
    "polygon_box_transform": "pixel transform; tests/test_detection.py",
    "rpn_target_assign": "label assignment; tests/test_detection.py",
    "print": "host-callback side effect; tests/test_api_breadth.py",
    "load": "reads a file at trace time; tests/test_api_breadth.py",
    "detection_map": "mAP vs brute force; tests/test_api_breadth.py",
}


def test_sweep_is_complete():
    """Every registered op has a spec or an explicit waiver."""
    registered = set(registry.registered_ops())
    covered = set(SPECS) | set(WAIVED)
    missing = registered - covered
    stale = covered - registered
    assert not missing, f"ops without spec or waiver: {sorted(missing)}"
    assert not stale, f"specs/waivers for unknown ops: {sorted(stale)}"


def _is_float(a):
    return a.dtype.kind == "f"


def _build_and_run(op_type, spec, amp):
    """Build a one-op program, check forward, then FD-check grads through
    the emitted grad ops."""
    block = fluid.default_main_program().global_block()
    helper = fluid.layers.nn.LayerHelper(op_type)

    feed = {}
    input_names = {}
    grad_targets = []
    for slot, vals in spec.inputs.items():
        vlist = vals if isinstance(vals, list) else [vals]
        names = []
        for k, v in enumerate(vlist):
            name = f"in_{slot}_{k}"
            lod_lens = spec.lod.get(slot)
            if isinstance(lod_lens, list):   # per-input ragged lengths
                lod_lens = lod_lens[k]
            block.create_var(name=name, shape=tuple(v.shape),
                            dtype=str(v.dtype), is_data=True,
                            lod_level=1 if lod_lens is not None else 0,
                            stop_gradient=not _is_float(v))
            if lod_lens is not None:
                block.create_var(name=seqlen_var_name(name), shape=(-1,),
                                dtype="int32", stop_gradient=True)
                feed[name] = (v, lod_lens)
            else:
                feed[name] = v
            names.append(name)
            if _is_float(v) and (spec.grad is None or slot in spec.grad):
                grad_targets.append((name, v))
        input_names[slot] = names

    out_names = {}
    for slot in spec.outs:
        ov = block.create_var(name=f"out_{slot}", shape=(), dtype="float32")
        out_names[slot] = [ov.name]
    op_inputs = {s: ns for s, ns in input_names.items()}
    # wire SeqLen slot if the rule takes one and a lod input exists
    opdef = registry.get_op_def(op_type)
    if "SeqLen" in opdef.input_slots and spec.lod:
        lod_slot = next(iter(spec.lod))
        # one companion per wired input — multi-input ops (sequence_concat)
        # take positionally aligned SeqLen lists
        op_inputs["SeqLen"] = [seqlen_var_name(n)
                               for n in input_names[lod_slot]]
    helper.append_op(op_type, inputs=op_inputs,
                     outputs=out_names, attrs=dict(spec.attrs))

    primary = block.vars[f"out_{spec.outs[0]}"]
    exe = fluid.Executor(fluid.CPUPlace(), amp=amp)

    if spec.fwd_only or not grad_targets or spec.grad == []:
        outs = exe.run(feed=feed,
                       fetch_list=[f"out_{s}" for s in spec.outs])
        for o in outs:
            if np.asarray(o).dtype.kind == "f":
                assert np.isfinite(np.asarray(o)).all(), f"{op_type}: non-finite"
        if spec.check is not None:
            r = spec.check([np.asarray(o) for o in outs])
            assert r is None or r, f"{op_type}: value check failed"
        return

    # scalar loss over the primary output
    loss_v = block.create_var(name="sweep_loss", shape=(), dtype="float32")
    f32 = block.create_var(name="out_f32", shape=(), dtype="float32")
    helper.append_op("cast", inputs={"X": [primary.name]},
                     outputs={"Out": [f32.name]},
                     attrs={"out_dtype": "float32"})
    helper.append_op("mean", inputs={"X": [f32.name]},
                     outputs={"Out": [loss_v.name]})

    test_prog = fluid.default_main_program().clone(for_test=True)
    fluid.append_backward(loss_v)

    grad_fetch = [n + "@GRAD" for n, _ in grad_targets]
    outs = exe.run(feed=feed, fetch_list=["sweep_loss"] + grad_fetch)
    loss0 = float(np.asarray(outs[0]).reshape(-1)[0])
    assert np.isfinite(loss0), f"{op_type}: non-finite loss"
    ana = [np.asarray(g, np.float64) for g in outs[1:]]

    fd_exe = fluid.Executor(fluid.CPUPlace(), amp=amp)

    def loss_at(feed2):
        l, = fd_exe.run(test_prog, feed=feed2, fetch_list=["sweep_loss"])
        return float(np.asarray(l).reshape(-1)[0])

    for (name, base), g_ana in zip(grad_targets, ana):
        num = np.zeros(base.shape, np.float64)
        it = np.nditer(base, flags=["multi_index"])
        for _ in it:
            idx = it.multi_index
            for sgn in (+1, -1):
                v2 = base.copy()
                v2[idx] += sgn * spec.eps
                f2 = dict(feed)
                if isinstance(feed[name], tuple):
                    f2[name] = (v2, feed[name][1])
                else:
                    f2[name] = v2
                num[idx] += sgn * loss_at(f2)
            num[idx] /= 2 * spec.eps
        np.testing.assert_allclose(
            g_ana, num, rtol=spec.rtol, atol=spec.atol,
            err_msg=f"{op_type}: grad wrt {name} (amp={amp})")


@pytest.mark.parametrize("op_type", sorted(SPECS))
def test_op(op_type):
    _build_and_run(op_type, SPECS[op_type], amp=False)


# a second form of an op that has a spec above: (op type, spec)
VARIANTS = {
    "causal_conv1d+bias": ("causal_conv1d", Spec(
        inputs={"X": T(2, 6, 3), "W": T(3, 4), "Bias": T(3)},
        attrs={"activation": "silu"})),
    # the gate before the norm, three groups of four, a weight a lane
    "gated_rms_norm+gate_first": ("gated_rms_norm", Spec(
        inputs={"X": T(3, 3, 4), "Gate": T(3, 3, 4), "Scale": POS(12)},
        attrs={"gate_first": True, "epsilon": 1e-5}, outs=("Y",))),
    # a KDA layer's output norm: the gate's sigmoid in its silu's place
    "gated_rms_norm+sigmoid": ("gated_rms_norm", Spec(
        inputs={"X": T(3, 3, 4), "Gate": T(3, 3, 4), "Scale": POS(4)},
        attrs={"activation": "sigmoid", "epsilon": 1e-6}, outs=("Y",))),
    # four groups of two experts of which two stay, a selection bias
    "moe_router+groups": ("moe_router", Spec(
        inputs={"X": T(6, 5), "W": T(5, 8) * 2, "Bias": T(8) * 0.1},
        attrs={"k": 2, "score_func": "sigmoid", "norm_topk_prob": True,
               "n_group": 4, "topk_group": 2},
        outs=("TopKWeight", "TopKIndex", "TokensPerExpert", "Probs",
              "LogSumExp"))),
    # the softmax's weights over their sum plus an epsilon, then scaled: an
    # epsilon large enough that the mean of the weights has a gradient
    "moe_router+softmax_norm_eps": ("moe_router", Spec(
        inputs={"X": T(6, 5), "W": T(5, 4) * 2},
        attrs={"k": 2, "norm_topk_prob": True, "norm_eps": 0.5,
               "scaling_factor": 2.5},
        outs=("TopKWeight", "TopKIndex", "TokensPerExpert", "Probs",
              "LogSumExp"))),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_op_variant(variant):
    _build_and_run(*VARIANTS[variant], amp=False)


@pytest.mark.parametrize("k,p,s,d", [(3, 1, 2, 1), (4, 1, 2, 1),
                                     (4, 2, 2, 1), (2, 0, 2, 1),
                                     (5, 2, 1, 1), (3, 0, 1, 1),
                                     (3, 1, 1, 2), (3, 2, 2, 2)])
def test_conv2d_transpose_matches_torch(k, p, s, d):
    """Value-level oracle for the transpose-conv padding/layout/dilation
    math (regression: the op silently mis-shaped for k-1 != 2p; the d>1
    cases pin the k_eff = d*(k-1)+1 padding derivation)."""
    torch = pytest.importorskip("torch")
    import torch.nn.functional as F

    x = T(2, 4, 5, 5)
    w = T(4, 3, k, k)
    ref = F.conv_transpose2d(torch.tensor(x), torch.tensor(w),
                             stride=s, padding=p, dilation=d).numpy()
    block = fluid.default_main_program().global_block()
    helper = fluid.layers.nn.LayerHelper("ct")
    for name, v in (("xin", x), ("win", w)):
        block.create_var(name=name, shape=v.shape, dtype="float32",
                         is_data=True)
    block.create_var(name="ct_out", shape=(), dtype="float32")
    helper.append_op("conv2d_transpose",
                     inputs={"Input": ["xin"], "Filter": ["win"]},
                     outputs={"Output": ["ct_out"]},
                     attrs={"strides": [s, s], "paddings": [p, p],
                            "dilations": [d, d]})
    exe = fluid.Executor(fluid.CPUPlace())
    out, = exe.run(feed={"xin": x, "win": w}, fetch_list=["ct_out"])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_seqlen_flows_through_length_changing_sequence_ops():
    """Regression: sequence_expand / sequence_reshape outputs must carry a
    materialized @SEQLEN so downstream sequence ops can run."""
    x = layers.data(name="sx", shape=[4], dtype="float32", lod_level=1)
    y = layers.data(name="sy", shape=[4], dtype="float32", lod_level=1)
    pooled_x = layers.sequence_pool(x, pool_type="sum")      # [B, 4]
    expanded = layers.sequence_expand(pooled_x, y)
    p1 = layers.sequence_pool(expanded, pool_type="sum")
    reshaped = layers.sequence_reshape(x, new_dim=2)         # lengths double
    p2 = layers.sequence_pool(reshaped, pool_type="sum")
    exe = fluid.Executor(fluid.CPUPlace())
    xs = np.ones((2, 3, 4), np.float32)
    xl = np.array([3, 2], np.int32)
    ys = np.ones((2, 5, 4), np.float32)
    yl = np.array([5, 1], np.int32)
    o1, o2 = exe.run(feed={"sx": (xs, xl), "sy": (ys, yl)},
                     fetch_list=[p1, p2])
    # expand: row b repeats pooled_x[b] over y's length
    np.testing.assert_allclose(np.asarray(o1)[0], 5 * 3 * np.ones(4),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o1)[1], 1 * 2 * np.ones(4),
                               rtol=1e-6)
    # reshape: [B,3,4] -> [B,6,2], lengths [6,4]; sums preserved per row
    np.testing.assert_allclose(np.asarray(o2)[0], 6 * np.ones(2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o2)[1], 4 * np.ones(2), rtol=1e-6)


AMP_OPS_IN_SPECS = sorted(
    (set(registry.AMP_BF16_OPS) | set(registry.AMP_F32_OPS)) & set(SPECS))


@pytest.mark.parametrize("op_type", AMP_OPS_IN_SPECS)
def test_op_amp(op_type):
    """Same check under the bf16 autocast policy: grads reach f32 inputs
    with bf16-limited but FD-consistent values."""
    spec = SPECS[op_type]
    import copy
    s = copy.copy(spec)
    s.rtol, s.atol, s.eps = 0.1, 2e-2, 1e-2  # bf16 tolerance
    _build_and_run(op_type, s, amp=True)


# ---------------------------------------------------------------------------
# Nested (level-2) LoD adapters: each op's nested path must equal running
# the level-1 rule per (doc, sentence) row (round-4 verdict item 6).
# ---------------------------------------------------------------------------

import jax.numpy as jnp  # noqa: E402  (nested adapter sweep below)

NESTED_CASES = {
    "sequence_pool": {"pooltype": "AVERAGE"},
    "sequence_softmax": {},
    "sequence_reshape": {"new_dim": 2},
    "sequence_erase": {"tokens": [0]},
    "sequence_conv": {"contextLength": 3, "contextStart": -1},
}


@pytest.mark.parametrize("op_type", sorted(NESTED_CASES))
def test_nested_adapter_matches_per_row(op_type):
    from paddle_tpu.core.registry import LoweringContext, get_op_def

    rng = np.random.RandomState(5)
    B, S, T, D = 2, 3, 4, 4
    attrs = NESTED_CASES[op_type]
    ctx = LoweringContext(attrs)
    rule = get_op_def(op_type).lower

    if op_type == "sequence_erase":
        X = jnp.asarray(rng.randint(0, 3, (B, S, T)).astype(np.int64))
    else:
        X = jnp.asarray(rng.randn(B, S, T, D).astype(np.float32))
    inner = jnp.asarray(rng.randint(0, T + 1, (B, S)).astype(np.int32))

    kwargs = {}
    if op_type == "sequence_conv":
        F = jnp.asarray(rng.randn(3 * D, 5).astype(np.float32))
        nested = rule(ctx, X, F, SeqLen=inner)
        per_row = [rule(ctx, X[b, s][None], F,
                        SeqLen=inner[b, s][None])
                   for b in range(B) for s in range(S)]
    else:
        nested = rule(ctx, X, SeqLen=inner)
        per_row = [rule(ctx, X[b, s][None], SeqLen=inner[b, s][None])
                   for b in range(B) for s in range(S)]

    flat_out = np.stack([np.asarray(r["Out"][0]) for r in per_row])
    want = flat_out.reshape((B, S) + flat_out.shape[1:])
    np.testing.assert_allclose(np.asarray(nested["Out"]), want,
                               rtol=1e-5, atol=1e-6)
    if "OutLen" in nested:
        flat_len = np.stack([np.asarray(r["OutLen"][0] if
                                        np.ndim(r["OutLen"]) else
                                        r["OutLen"]) for r in per_row])
        np.testing.assert_array_equal(np.asarray(nested["OutLen"]),
                                      flat_len.reshape(B, S))


def test_nested_adapter_sequence_slice_matches_per_row():
    from paddle_tpu.core.registry import LoweringContext, get_op_def

    rng = np.random.RandomState(6)
    B, S, T, D = 2, 3, 4, 2
    ctx = LoweringContext({"nested": True})
    ctx1 = LoweringContext({})          # per-row reference: level-1 path
    rule = get_op_def("sequence_slice").lower
    X = jnp.asarray(rng.randn(B, S, T, D).astype(np.float32))
    off = jnp.asarray(rng.randint(0, 2, (B, S)).astype(np.int32))
    ln = jnp.asarray(rng.randint(1, 3, (B, S)).astype(np.int32))
    nested = rule(ctx, X, off, ln)
    rows = [rule(ctx1, X[b, s][None], off[b, s][None], ln[b, s][None])
            for b in range(B) for s in range(S)]
    want = np.stack([np.asarray(r["Out"][0]) for r in rows]) \
        .reshape(B, S, T, D)
    np.testing.assert_allclose(np.asarray(nested["Out"]), want, rtol=1e-6)
    want_len = np.stack([np.asarray(r["OutLen"][0]) for r in rows]) \
        .reshape(B, S)
    np.testing.assert_array_equal(np.asarray(nested["OutLen"]), want_len)
