"""Optimizers: backward + per-parameter update ops appended to the program.

Capability parity with reference python/paddle/fluid/optimizer.py (Optimizer
base :36, accumulators, `_create_optimization_pass` :188, `minimize` :245 =
append_backward + regularization + clip + apply_gradients; SGD :271,
Momentum :312, Adagrad :386, Adam :452, Adamax :593, DecayedAdagrad :714,
Adadelta :785, RMSProp, Ftrl, ModelAverage).

TPU-native: update ops lower into the same XLA step as fwd/bwd, buffers are
donated, so the whole training iteration is one fused device program.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, List, Optional

from .core import ir
from .core.backward import append_backward
from .observe import steplog as _steplog
from .layer_helper import LayerHelper
from . import initializer as init
from . import unique_name
from .regularizer import append_regularization_ops
from .clip import append_gradient_clip_ops, error_clip_callback


class Optimizer:
    def __init__(self, learning_rate, regularization=None, name=None):
        self.regularization = regularization
        self._name = name
        self._learning_rate = learning_rate
        self._accumulators: Dict[str, Dict[str, ir.Variable]] = {}
        self._lr_var: Optional[ir.Variable] = None
        self.helper = None

    # -- learning rate ----------------------------------------------------
    def _create_lr_var(self, program) -> ir.Variable:
        if isinstance(self._learning_rate, ir.Variable):
            return self._learning_rate
        helper = LayerHelper("learning_rate")
        name = unique_name.generate("learning_rate")
        gb = program.global_block()
        var = gb.create_var(name=name, shape=(1,), dtype="float32",
                            persistable=True, stop_gradient=True)
        helper.set_variable_initializer(
            var, init.ConstantInitializer(float(self._learning_rate)))
        return var

    def _global_learning_rate(self):
        return self._lr_var

    # -- accumulators (reference optimizer.py:103-166) --------------------
    def _add_accumulator(self, name, param, dtype=None, fill_value=0.0,
                         shape=None):
        if name in self._accumulators and param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        helper = LayerHelper(name)
        var_name = unique_name.generate(f"{param.name}_{name}")
        gb = param.block.program.global_block()
        var = gb.create_var(name=var_name, shape=shape or param.shape,
                            dtype=dtype or param.dtype, persistable=True,
                            stop_gradient=True)
        helper.set_variable_initializer(var, init.ConstantInitializer(fill_value))
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- hooks per optimizer ----------------------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _finish_update(self, block, parameters_and_grads):
        pass

    # -- the pass ----------------------------------------------------------
    def _create_optimization_pass(self, parameters_and_grads, loss,
                                  startup_program=None):
        program = loss.block.program
        self._lr_var = self._create_lr_var(program)
        block = program.global_block()
        self._create_accumulators(block,
                                  [p for p, g in parameters_and_grads if g is not None])
        optimize_ops = []
        for param_and_grad in parameters_and_grads:
            if param_and_grad[1] is None:
                continue
            optimize_ops.append(self._append_optimize_op(block, param_and_grad))
        self._finish_update(block, parameters_and_grads)
        # bump the LR-decay global step if a schedule created one
        if "@LR_DECAY_COUNTER@" in block.vars:
            ctr = block.vars["@LR_DECAY_COUNTER@"]
            block.append_op("increment", inputs={"X": [ctr.name]},
                            outputs={"Out": [ctr.name]}, attrs={"step": 1.0})
        return optimize_ops

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        """append_backward + regularization + clip + update ops
        (reference optimizer.py:245)."""
        program = loss.block.program
        block = program.global_block()
        n0 = len(block.ops)
        with _steplog.Phase(_steplog.MINIMIZE, program._uid):
            params_grads = append_backward(
                loss, parameter_list=parameter_list, no_grad_set=no_grad_set)
            params_grads = append_gradient_clip_ops(params_grads)
            params_grads = append_regularization_ops(params_grads,
                                                     self.regularization)
            optimize_ops = self._create_optimization_pass(
                params_grads, loss, startup_program)
        # role-tag everything minimize appended (clip/reg/lr/update ops);
        # grad ops were already tagged "backward" by append_backward. Eval
        # clones strip by role (ir._set_inference_mode).
        for op in block.ops[n0:]:
            op.attrs.setdefault("__role__", "optimize")
        return optimize_ops, params_grads

    def _lr_for_param(self, param):
        """Per-parameter lr multiplier (ParamAttr.learning_rate). A
        Variable is used directly — append_LARS stores the per-layer
        decayed lr here (reference optimizer.py _create_param_lr
        special-cases Variable the same way)."""
        from .core import ir
        mult = getattr(param, "optimize_attr", {}).get("learning_rate", 1.0)
        if isinstance(mult, ir.Variable):
            return mult
        if mult == 1.0:
            return self._lr_var
        return self._lr_var * float(mult)


class SGDOptimizer(Optimizer):
    def __init__(self, learning_rate, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "sgd"

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "sgd",
            inputs={"Param": [p.name], "Grad": [g.name],
                    "LearningRate": [self._lr_for_param(p).name]},
            outputs={"ParamOut": [p.name]})


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, use_nesterov=False, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "momentum"
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            "momentum",
            inputs={"Param": [p.name], "Grad": [g.name], "Velocity": [v.name],
                    "LearningRate": [self._lr_for_param(p).name]},
            outputs={"ParamOut": [p.name], "VelocityOut": [v.name]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov})


class AdamOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_mode=False, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adam"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=[1])
            self._add_accumulator("beta2_pow_acc", p, fill_value=self._beta2,
                                  shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1 = self._get_accumulator("beta1_pow_acc", p)
        b2 = self._get_accumulator("beta2_pow_acc", p)
        return block.append_op(
            "adam",
            inputs={"Param": [p.name], "Grad": [g.name], "Moment1": [m1.name],
                    "Moment2": [m2.name], "Beta1Pow": [b1.name],
                    "Beta2Pow": [b2.name],
                    "LearningRate": [self._lr_for_param(p).name]},
            outputs={"ParamOut": [p.name], "Moment1Out": [m1.name],
                     "Moment2Out": [m2.name], "Beta1PowOut": [b1.name],
                     "Beta2PowOut": [b2.name]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adagrad"
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "adagrad",
            inputs={"Param": [p.name], "Grad": [g.name], "Moment": [m.name],
                    "LearningRate": [self._lr_for_param(p).name]},
            outputs={"ParamOut": [p.name], "MomentOut": [m.name]},
            attrs={"epsilon": self._epsilon})


class AdamaxOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adamax"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        u = self._get_accumulator("inf_norm", p)
        b1 = self._get_accumulator("beta1_pow_acc", p)
        return block.append_op(
            "adamax",
            inputs={"Param": [p.name], "Grad": [g.name], "Moment": [m.name],
                    "InfNorm": [u.name], "Beta1Pow": [b1.name],
                    "LearningRate": [self._lr_for_param(p).name]},
            outputs={"ParamOut": [p.name], "MomentOut": [m.name],
                     "InfNormOut": [u.name], "Beta1PowOut": [b1.name]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})


class DecayedAdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "decayed_adagrad"
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "decayed_adagrad",
            inputs={"Param": [p.name], "Grad": [g.name], "Moment": [m.name],
                    "LearningRate": [self._lr_for_param(p).name]},
            outputs={"ParamOut": [p.name], "MomentOut": [m.name]},
            attrs={"decay": self._decay, "epsilon": self._epsilon})


class AdadeltaOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adadelta"
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("__avg_squared_grad", p)
            self._add_accumulator("__avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        g2 = self._get_accumulator("__avg_squared_grad", p)
        u2 = self._get_accumulator("__avg_squared_update", p)
        return block.append_op(
            "adadelta",
            inputs={"Param": [p.name], "Grad": [g.name],
                    "AvgSquaredGrad": [g2.name], "AvgSquaredUpdate": [u2.name]},
            outputs={"ParamOut": [p.name], "AvgSquaredGradOut": [g2.name],
                     "AvgSquaredUpdateOut": [u2.name]},
            attrs={"epsilon": self._epsilon, "rho": self._rho})


class RMSPropOptimizer(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "rmsprop"
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("mean_square", p)
            self._add_accumulator("momentum", p)
            if self._centered:
                self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        ms = self._get_accumulator("mean_square", p)
        mom = self._get_accumulator("momentum", p)
        inputs = {"Param": [p.name], "Grad": [g.name],
                  "MeanSquare": [ms.name], "Moment": [mom.name],
                  "LearningRate": [self._lr_for_param(p).name]}
        outputs = {"ParamOut": [p.name], "MeanSquareOut": [ms.name],
                   "MomentOut": [mom.name]}
        if self._centered:
            mg = self._get_accumulator("mean_grad", p)
            inputs["MeanGrad"] = [mg.name]
            outputs["MeanGradOut"] = [mg.name]
        return block.append_op(
            "rmsprop", inputs=inputs, outputs=outputs,
            attrs={"decay": self._rho, "epsilon": self._epsilon,
                   "momentum": self._momentum, "centered": self._centered})


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "ftrl"
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        sq = self._get_accumulator("squared", p)
        lin = self._get_accumulator("linear", p)
        return block.append_op(
            "ftrl",
            inputs={"Param": [p.name], "Grad": [g.name],
                    "SquaredAccumulator": [sq.name],
                    "LinearAccumulator": [lin.name],
                    "LearningRate": [self._lr_for_param(p).name]},
            outputs={"ParamOut": [p.name], "SquaredAccumOut": [sq.name],
                     "LinearAccumOut": [lin.name]},
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power})


class ModelAverage(Optimizer):
    """Sliding-window parameter averaging for eval (reference
    optimizer.py:1111 + average_accumulates_op.h).

    Construct AFTER ``optimizer.minimize(loss)`` on the training program:
    it appends one ``average_accumulates`` op per parameter to the main
    program (the sums update in the same fused XLA step as the training
    update), and builds standalone apply/restore programs that swap the
    averaged values into the parameters around an eval pass::

        with model_average.apply(exe, scope=scope):
            ... run eval programs: params hold the window average ...
        # params restored afterwards
    """

    def __init__(self, average_window_rate, min_average_window=10000,
                 max_average_window=10000, main_program=None, **kw):
        super().__init__(0.0, **kw)
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        program = main_program or ir.default_main_program()
        self._backups: Dict[str, str] = {}

        params = [p for p in program.global_block().all_parameters()
                  if getattr(p, "do_model_average", None) is not False]
        block = program.global_block()
        self._create_accumulators(block, params)
        for p in params:
            self._append_accumulate_op(block, p)

        self.apply_program = self._build_apply_program(params)
        self.restore_program = self._build_restore_program(params)

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("sum_1", p)
            self._add_accumulator("sum_2", p)
            self._add_accumulator("sum_3", p)
            for ctr in ("num_accumulates", "old_num_accumulates",
                        "num_updates"):
                self._add_accumulator(ctr, p, dtype="int32", shape=(1,))

    def _append_accumulate_op(self, block, p):
        accs = {n: self._get_accumulator(n, p)
                for n in ("sum_1", "sum_2", "sum_3", "num_accumulates",
                          "old_num_accumulates", "num_updates")}
        block.append_op(
            "average_accumulates",
            inputs={"param": [p.name],
                    **{f"in_{n}": [v.name] for n, v in accs.items()}},
            outputs={f"out_{n}": [v.name] for n, v in accs.items()},
            attrs={"average_window": self.average_window,
                   "min_average_window": self.min_average_window,
                   "max_average_window": self.max_average_window,
                   "__role__": "optimize"})

    def _clone_into(self, block, var):
        return block.create_var(name=var.name, shape=var.shape,
                                dtype=var.dtype, persistable=True,
                                stop_gradient=True)

    def _build_apply_program(self, params):
        from . import layers
        prog = ir.Program()
        with ir.program_guard(prog), unique_name.guard():
            block = prog.global_block()
            for p in params:
                param = self._clone_into(block, p)
                accs = [self._clone_into(block, self._get_accumulator(n, p))
                        for n in ("sum_1", "sum_2", "sum_3")]
                ctrs = [self._clone_into(block, self._get_accumulator(n, p))
                        for n in ("num_accumulates", "old_num_accumulates")]
                backup = block.create_var(
                    name=unique_name.generate(p.name + ".model_average_bak"),
                    shape=p.shape, dtype=p.dtype, persistable=True,
                    stop_gradient=True)
                self._backups[p.name] = backup.name
                layers.assign(input=param, output=backup)
                total = layers.cast(layers.sums(ctrs), dtype=param.dtype)
                avg = layers.elementwise_div(x=layers.sums(accs), y=total)
                layers.assign(input=avg, output=param)
        return prog

    def _build_restore_program(self, params):
        from . import layers
        prog = ir.Program()
        with ir.program_guard(prog), unique_name.guard():
            block = prog.global_block()
            for p in params:
                param = self._clone_into(block, p)
                backup = block.create_var(name=self._backups[p.name],
                                          shape=p.shape, dtype=p.dtype,
                                          persistable=True,
                                          stop_gradient=True)
                layers.assign(input=backup, output=param)
        return prog

    @contextmanager
    def apply(self, executor, need_restore=True, scope=None):
        """Swap window-averaged values into the parameters
        (reference optimizer.py:1247)."""
        kw = {"scope": scope} if scope is not None else {}
        executor.run(self.apply_program, **kw)
        try:
            yield
        finally:
            if need_restore:
                self.restore(executor, scope=scope)

    def restore(self, executor, scope=None):
        """Restore the pre-apply parameter values (reference
        optimizer.py:1268)."""
        kw = {"scope": scope} if scope is not None else {}
        executor.run(self.restore_program, **kw)


SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adam = AdamOptimizer
Adagrad = AdagradOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
