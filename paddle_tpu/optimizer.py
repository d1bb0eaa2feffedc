"""Optimizer library: emits backward + update ops into the program.

Parity: reference ``python/paddle/fluid/optimizer.py`` (1363 LoC): base
``Optimizer:39`` (accumulator creation, ``minimize`` = append_backward +
clip/regularize + per-param update ops), SGD:270, Momentum:316, Adagrad:400,
Adam:475, Adamax:622, DecayedAdagrad:749, Adadelta:830, RMSProp:923,
Ftrl:1072, ModelAverage:1209 — TPU-native: optimizer state are persistable
scope vars updated by optimizer ops inside the same jitted step; sharding
the update (the reference's kReduce strategy) is a pjit sharding choice in
``parallel/``, not a different code path.
"""

from collections import defaultdict

from . import unique_name
from .backward import append_backward
from .clip import append_gradient_clip_ops, error_clip_callback
from .framework import (Parameter, Program, Variable, default_main_program,
                        default_startup_program, program_guard)
from .initializer import ConstantInitializer
from .layer_helper import LayerHelper
from .profiler import build_pass
from .regularizer import append_regularization_ops

__all__ = [
    "SGD", "Momentum", "Adagrad", "Adam", "Adamax", "DecayedAdagrad",
    "Adadelta", "RMSProp", "Ftrl", "ModelAverage",
    "SGDOptimizer", "MomentumOptimizer", "AdagradOptimizer", "AdamOptimizer",
    "AdamaxOptimizer", "DecayedAdagradOptimizer", "AdadeltaOptimizer",
    "RMSPropOptimizer", "FtrlOptimizer",
]


class Optimizer:
    """Base optimizer (reference optimizer.py:39)."""

    def __init__(self, learning_rate, regularization=None, name=None):
        if not isinstance(learning_rate, (float, Variable)):
            raise TypeError("learning rate must be float or Variable")
        self._name = name
        self.regularization = regularization
        self._learning_rate = learning_rate
        self._learning_rate_map = {}
        # {accum_name: {param_name: accum_var}}
        self._accumulators = defaultdict(dict)
        self.helper = None

    # -- learning rate -----------------------------------------------------
    def _create_global_learning_rate(self):
        program = default_main_program()
        lr = self._learning_rate_map.get(id(program))
        if lr is not None:
            return
        if isinstance(self._learning_rate, Variable):
            self._learning_rate_map[id(program)] = self._learning_rate
            return
        name = unique_name.generate("learning_rate")
        var = program.global_block().create_var(
            name=name, shape=(1,), dtype="float32", persistable=True
        )
        startup = default_startup_program().global_block()
        sv = startup.create_var(
            name=name, shape=(1,), dtype="float32", persistable=True
        )
        ConstantInitializer(float(self._learning_rate))(sv, startup)
        self._learning_rate_map[id(program)] = var

    def _global_learning_rate(self, program=None):
        if program is None:
            program = default_main_program()
        return self._learning_rate_map.get(id(program))

    def _create_param_lr(self, param_and_grad):
        param = param_and_grad[0]
        base = self._global_learning_rate()
        mult = (param.optimize_attr or {}).get("learning_rate", 1.0)
        if isinstance(mult, Variable):
            # a per-param LR already computed in-graph (append_LARS
            # writes the fully-scaled rate; reference optimizer.py uses
            # it directly)
            return mult
        if mult == 1.0:
            return base
        helper = LayerHelper("param_lr")
        out = helper.create_variable_for_type_inference(dtype=base.dtype)
        helper.append_op(
            type="scale", inputs={"X": [base]}, outputs={"Out": [out]},
            attrs={"scale": float(mult)},
        )
        return out

    # -- accumulators ------------------------------------------------------
    def _add_accumulator(self, name, param, dtype=None, fill_value=0.0,
                         shape=None):
        if param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        if shape is None:
            shape = param.shape
        dtype = dtype or param.dtype
        program = default_main_program()
        var_name = unique_name.generate("%s_%s" % (param.name, name))
        var = program.global_block().create_var(
            name=var_name, shape=shape, dtype=dtype, persistable=True
        )
        startup = default_startup_program().global_block()
        sv = startup.create_var(
            name=var_name, shape=shape, dtype=dtype, persistable=True
        )
        ConstantInitializer(float(fill_value))(sv, startup)
        self._accumulators[name][param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    def _create_accumulators(self, block, parameters):
        pass

    def _finish_update(self, block, parameters_and_grads):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    # -- main entry points -------------------------------------------------
    def _create_optimization_pass(self, parameters_and_grads, loss,
                                  startup_program=None):
        program = loss.block.program
        block = program.global_block()
        self.helper = LayerHelper(self.__class__.__name__)
        self._create_global_learning_rate()
        self._create_accumulators(
            block, [p for p, g in parameters_and_grads if g is not None]
        )
        optimize_ops = []
        for param_and_grad in parameters_and_grads:
            if param_and_grad[1] is None:
                continue
            if param_and_grad[0].trainable:
                optimize_ops.append(
                    self._append_optimize_op(block, param_and_grad)
                )
        self._finish_update(block, parameters_and_grads)
        return optimize_ops

    def apply_gradients(self, params_grads, loss, startup_program=None):
        params_grads = append_gradient_clip_ops(params_grads)
        params_grads = append_regularization_ops(
            params_grads, self.regularization
        )
        return self._create_optimization_pass(params_grads, loss,
                                              startup_program)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        """append_backward + clip + regularize + update ops
        (reference optimizer.py minimize).  Bound to the loss's program via
        program_guard so minimize works outside the guard that built it."""
        from .framework import default_startup_program

        with build_pass(loss.block.program, "minimize"):
            params_grads = append_backward(loss, parameter_list, no_grad_set)
            with program_guard(loss.block.program,
                               startup_program or default_startup_program()):
                optimize_ops = self.apply_gradients(params_grads, loss,
                                                    startup_program)
        return optimize_ops, params_grads


class SGDOptimizer(Optimizer):
    def __init__(self, learning_rate, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "sgd"

    def _append_optimize_op(self, block, param_and_grad):
        return block.append_op(
            type="sgd",
            inputs={
                "Param": [param_and_grad[0]],
                "Grad": [param_and_grad[1]],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param_and_grad[0]]},
        )


class MomentumOptimizer(Optimizer):
    _velocity_acc_str = "velocity"

    def __init__(self, learning_rate, momentum, use_nesterov=False, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "momentum"
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._velocity_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        velocity = self._get_accumulator(self._velocity_acc_str,
                                         param_and_grad[0])
        return block.append_op(
            type="momentum",
            inputs={
                "Param": [param_and_grad[0]],
                "Grad": [param_and_grad[1]],
                "Velocity": [velocity],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param_and_grad[0]],
                     "VelocityOut": [velocity]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov},
        )


class AdagradOptimizer(Optimizer):
    _moment_acc_str = "moment"

    def __init__(self, learning_rate, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "adagrad"
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._moment_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        moment = self._get_accumulator(self._moment_acc_str, param_and_grad[0])
        return block.append_op(
            type="adagrad",
            inputs={
                "Param": [param_and_grad[0]],
                "Grad": [param_and_grad[1]],
                "Moment": [moment],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param_and_grad[0]], "MomentOut": [moment]},
            attrs={"epsilon": self._epsilon},
        )


class AdamOptimizer(Optimizer):
    _moment1_acc_str = "moment1"
    _moment2_acc_str = "moment2"
    _beta1_pow_acc_str = "beta1_pow_acc"
    _beta2_pow_acc_str = "beta2_pow_acc"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "adam"
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._moment1_acc_str, p)
            self._add_accumulator(self._moment2_acc_str, p)
            self._add_accumulator(self._beta1_pow_acc_str, p,
                                  fill_value=self._beta1, shape=[1])
            self._add_accumulator(self._beta2_pow_acc_str, p,
                                  fill_value=self._beta2, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        m1 = self._get_accumulator(self._moment1_acc_str, param_and_grad[0])
        m2 = self._get_accumulator(self._moment2_acc_str, param_and_grad[0])
        b1p = self._get_accumulator(self._beta1_pow_acc_str, param_and_grad[0])
        b2p = self._get_accumulator(self._beta2_pow_acc_str, param_and_grad[0])
        return block.append_op(
            type="adam",
            inputs={
                "Param": [param_and_grad[0]],
                "Grad": [param_and_grad[1]],
                "LearningRate": [self._create_param_lr(param_and_grad)],
                "Moment1": [m1],
                "Moment2": [m2],
                "Beta1Pow": [b1p],
                "Beta2Pow": [b2p],
            },
            outputs={"ParamOut": [param_and_grad[0]], "Moment1Out": [m1],
                     "Moment2Out": [m2]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon},
        )

    def _finish_update(self, block, parameters_and_grads):
        """Advance beta1^t / beta2^t (reference optimizer.py Adam
        _finish_update appends scale ops)."""
        for p, g in parameters_and_grads:
            if g is None:
                continue
            b1p = self._get_accumulator(self._beta1_pow_acc_str, p)
            b2p = self._get_accumulator(self._beta2_pow_acc_str, p)
            block.append_op(
                type="scale", inputs={"X": [b1p]}, outputs={"Out": [b1p]},
                attrs={"scale": self._beta1},
            )
            block.append_op(
                type="scale", inputs={"X": [b2p]}, outputs={"Out": [b2p]},
                attrs={"scale": self._beta2},
            )


class AdamaxOptimizer(Optimizer):
    _moment_acc_str = "moment"
    _inf_norm_acc_str = "inf_norm"
    _beta1_pow_acc_str = "beta1_pow_acc"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "adamax"
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._moment_acc_str, p)
            self._add_accumulator(self._inf_norm_acc_str, p)
            self._add_accumulator(self._beta1_pow_acc_str, p,
                                  fill_value=self._beta1, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        moment = self._get_accumulator(self._moment_acc_str, param_and_grad[0])
        inf_norm = self._get_accumulator(self._inf_norm_acc_str,
                                         param_and_grad[0])
        b1p = self._get_accumulator(self._beta1_pow_acc_str, param_and_grad[0])
        return block.append_op(
            type="adamax",
            inputs={
                "Param": [param_and_grad[0]],
                "Grad": [param_and_grad[1]],
                "LearningRate": [self._create_param_lr(param_and_grad)],
                "Moment": [moment],
                "InfNorm": [inf_norm],
                "Beta1Pow": [b1p],
            },
            outputs={"ParamOut": [param_and_grad[0]], "MomentOut": [moment],
                     "InfNormOut": [inf_norm]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon},
        )

    def _finish_update(self, block, parameters_and_grads):
        for p, g in parameters_and_grads:
            if g is None:
                continue
            b1p = self._get_accumulator(self._beta1_pow_acc_str, p)
            block.append_op(
                type="scale", inputs={"X": [b1p]}, outputs={"Out": [b1p]},
                attrs={"scale": self._beta1},
            )


class DecayedAdagradOptimizer(Optimizer):
    _moment_acc_str = "moment"

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "decayed_adagrad"
        self._decay = decay
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._moment_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        moment = self._get_accumulator(self._moment_acc_str, param_and_grad[0])
        return block.append_op(
            type="decayed_adagrad",
            inputs={
                "Param": [param_and_grad[0]],
                "Grad": [param_and_grad[1]],
                "Moment": [moment],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param_and_grad[0]], "MomentOut": [moment]},
            attrs={"decay": self._decay, "epsilon": self._epsilon},
        )


class AdadeltaOptimizer(Optimizer):
    _avg_squared_grad_acc_str = "_avg_squared_grad"
    _avg_squared_update_acc_str = "_avg_squared_update"

    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "adadelta"
        self._epsilon = epsilon
        self._rho = rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._avg_squared_grad_acc_str, p)
            self._add_accumulator(self._avg_squared_update_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        g_acc = self._get_accumulator(self._avg_squared_grad_acc_str,
                                      param_and_grad[0])
        u_acc = self._get_accumulator(self._avg_squared_update_acc_str,
                                      param_and_grad[0])
        return block.append_op(
            type="adadelta",
            inputs={
                "Param": [param_and_grad[0]],
                "Grad": [param_and_grad[1]],
                "AvgSquaredGrad": [g_acc],
                "AvgSquaredUpdate": [u_acc],
            },
            outputs={"ParamOut": [param_and_grad[0]],
                     "AvgSquaredGradOut": [g_acc],
                     "AvgSquaredUpdateOut": [u_acc]},
            attrs={"epsilon": self._epsilon, "rho": self._rho},
        )


class RMSPropOptimizer(Optimizer):
    _momentum_acc_str = "momentum"
    _mean_square_acc_str = "mean_square"
    _mean_grad_acc_str = "mean_grad"

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "rmsprop"
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._momentum_acc_str, p)
            self._add_accumulator(self._mean_square_acc_str, p)
            self._add_accumulator(self._mean_grad_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        momentum_acc = self._get_accumulator(self._momentum_acc_str,
                                             param_and_grad[0])
        mean_square_acc = self._get_accumulator(self._mean_square_acc_str,
                                                param_and_grad[0])
        mean_grad_acc = self._get_accumulator(self._mean_grad_acc_str,
                                              param_and_grad[0])
        return block.append_op(
            type="rmsprop",
            inputs={
                "Param": [param_and_grad[0]],
                "Grad": [param_and_grad[1]],
                "Moment": [momentum_acc],
                "MeanSquare": [mean_square_acc],
                "MeanGrad": [mean_grad_acc],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={
                "ParamOut": [param_and_grad[0]],
                "MomentOut": [momentum_acc],
                "MeanSquareOut": [mean_square_acc],
                "MeanGradOut": [mean_grad_acc],
            },
            attrs={"epsilon": self._epsilon, "decay": self._rho,
                   "momentum": self._momentum, "centered": self._centered},
        )


class FtrlOptimizer(Optimizer):
    _squared_acc_str = "squared"
    _linear_acc_str = "linear"

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "ftrl"
        self._l1 = l1
        self._l2 = l2
        self._lr_power = lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._squared_acc_str, p)
            self._add_accumulator(self._linear_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        squared_acc = self._get_accumulator(self._squared_acc_str,
                                            param_and_grad[0])
        linear_acc = self._get_accumulator(self._linear_acc_str,
                                           param_and_grad[0])
        return block.append_op(
            type="ftrl",
            inputs={
                "Param": [param_and_grad[0]],
                "Grad": [param_and_grad[1]],
                "SquaredAccumulator": [squared_acc],
                "LinearAccumulator": [linear_acc],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param_and_grad[0]],
                     "SquaredAccumOut": [squared_acc],
                     "LinearAccumOut": [linear_acc]},
            attrs={"l1": self._l1, "l2": self._l2,
                   "lr_power": self._lr_power},
        )


class ModelAverage(Optimizer):
    """Running parameter average for eval (reference optimizer.py:1209),
    driven by the ``average_accumulates`` op (average_accumulates_op.h):
    three staggered sum buffers (precision-guarded roll every 16384
    updates) plus a restartable trailing window, exactly the reference's
    accumulator protocol.  ``apply()`` swaps
    (sum_1+sum_2+sum_3)/(num_accumulates+old_num_accumulates) into the
    scope."""

    def __init__(self, average_window_rate=0.15, min_average_window=10000,
                 max_average_window=10000, **kwargs):
        super().__init__(0.0, **kwargs)
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        self.params_grads = []
        self._avg_sums = {}

    def _ensure_accumulators(self, program):
        block = program.global_block()
        for p in block.all_parameters():
            if p.name in self._avg_sums:
                continue
            sums = (self._add_accumulator("sum_1", p),
                    self._add_accumulator("sum_2", p),
                    self._add_accumulator("sum_3", p))
            counts = (
                self._add_accumulator("num_accumulates", p, shape=[1],
                                      dtype="int64"),
                self._add_accumulator("old_num_accumulates", p, shape=[1],
                                      dtype="int64"),
                self._add_accumulator("num_updates", p, shape=[1],
                                      dtype="int64"),
            )
            self._avg_sums[p.name] = sums + counts
            s1, s2, s3, na, ona, nu = self._avg_sums[p.name]
            block.append_op(
                type="average_accumulates",
                inputs={"param": [p], "in_sum_1": [s1], "in_sum_2": [s2],
                        "in_sum_3": [s3], "in_num_accumulates": [na],
                        "in_old_num_accumulates": [ona],
                        "in_num_updates": [nu]},
                outputs={"out_sum_1": [s1], "out_sum_2": [s2],
                         "out_sum_3": [s3], "out_num_accumulates": [na],
                         "out_old_num_accumulates": [ona],
                         "out_num_updates": [nu]},
                attrs={"average_window": self.average_window,
                       "min_average_window": self.min_average_window,
                       "max_average_window": self.max_average_window},
            )

    def apply(self, executor, scope=None):
        """Swap averaged params into the scope (context manager)."""
        import contextlib

        import numpy as np

        from .scope import global_scope

        scope = scope or global_scope()

        @contextlib.contextmanager
        def _ctx():
            saved = {}
            for name, accs in self._avg_sums.items():
                s1, s2, s3, na, ona, _ = accs
                saved[name] = scope.var(name)
                total = (np.asarray(scope.var(s1.name))
                         + np.asarray(scope.var(s2.name))
                         + np.asarray(scope.var(s3.name)))
                cnt = float(np.asarray(scope.var(na.name))[0]
                            + np.asarray(scope.var(ona.name))[0]) or 1.0
                scope.set_var(name, (total / cnt).astype(total.dtype))
            try:
                yield
            finally:
                for name, v in saved.items():
                    scope.set_var(name, v)

        return _ctx()


# aliases matching the reference's short names (fluid.optimizer.SGD etc.)
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
