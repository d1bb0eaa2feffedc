"""Automatic mixed precision (bf16) — the TPU rebuild of the reference's
fp16 story (``contrib/float16/float16_transpiler.py``: rewrites a program
to fp16 by inserting casts and retyping vars).

TPU-first redesign: bfloat16 is the MXU's native input format and shares
float32's exponent range, so — unlike fp16 on GPUs — **no loss scaling is
required** and there is no transpiler pass inserting cast ops into the
program.  Instead the policy is applied at *trace time*: ops on the white
list (the MXU-bound FLOPs: matmuls/convs) compute in bf16, ops on the
black list (numerically sensitive: losses, norms, optimizer updates)
compute in fp32, everything else follows its inputs' promotion.  Master
weights stay fp32 automatically: parameters live fp32 in the scope and
only their *use* inside whitelisted ops is cast, while the (blacklisted)
optimizer ops update the fp32 originals.

API parity targets: ``fluid.contrib.mixed_precision.decorate(optimizer)``
and the float16 transpiler's program rewrite
(``contrib/float16/float16_transpiler.py``); ``init_loss_scaling`` is
accepted for signature parity and ignored (bf16 needs none — documented
SURVEY.md §2.6 float16 demo row).
"""

import jax.numpy as jnp

from ..profiler import build_pass

__all__ = ["AutoMixedPrecisionLists", "AMPPolicy", "decorate",
           "bf16_program_guard", "cast_parameters_to_bf16"]


class AutoMixedPrecisionLists:
    """White/black op lists (the reference AMP concept; the float16
    transpiler's implicit op partition made explicit)."""

    # MXU-bound: cast fp32 inputs to bf16
    WHITE = {
        "matmul", "mul", "conv2d", "conv3d", "depthwise_conv2d",
        "conv2d_transpose", "bilinear_tensor_product", "fused_attention",
    }
    # numerically sensitive: force fp32 compute.  batch_norm/layer_norm
    # are NOT here: their kernels accumulate statistics in fp32
    # internally while activations pass through in bf16 — blacklisting
    # them would insert two full-activation cast passes around every
    # conv/sublayer (measured 20%+ of the ResNet step).
    BLACK = {
        "softmax_with_cross_entropy", "cross_entropy", "mean",
        "reduce_sum", "reduce_mean",
        "group_norm", "lrn", "norm", "exp", "log", "softmax",
        "log_softmax", "sigmoid_cross_entropy_with_logits",
        # optimizer updates read/write fp32 master weights
        "sgd", "momentum", "adam", "adamax", "adagrad", "adadelta",
        "rmsprop", "ftrl", "decayed_adagrad", "proximal_gd",
        "proximal_adagrad", "sum", "clip_by_norm", "squared_l2_norm",
        "isfinite",
    }

    # The sparse-attention mixture-of-experts ops (ops/sparse_select.py,
    # ops/moe.py) and what they get unless a custom list says otherwise:
    # the indexer's products and the experts' grouped products in bf16,
    # the router's product and softmax — they decide which experts a
    # token goes to — in float32.  rms_norm, rotary_embedding, swiglu,
    # select_topk_keys and moe_dispatch are on no list, like layer_norm:
    # float32 inside, the activations' dtype outside.  Kept apart from
    # WHITE/BLACK because ``AMPPolicy.__repr__`` goes into every AMP
    # program's fingerprint, and with it into its compiled module's name
    # and compile-cache key: these sets decide nothing for a program that
    # holds none of their ops, so they must not rename it.
    WHITE_SPARSE_MOE = {"indexer_score", "moe_expert_ffn"}
    BLACK_SPARSE_MOE = {"moe_router"}
    # A state-space layer's recurrence (ops/state_space.py): its step size,
    # ``A``, state and output stay float32 — 4096 steps multiply a state by
    # ``exp(delta A)`` and a bf16 step size compounds.  ``causal_conv1d`` is
    # on no list, like layer_norm.  Apart from BLACK for the reason above.
    BLACK_STATE_SPACE = {"selective_scan"}
    # ops that cast their own operands, whatever their colour: the grouped
    # expert products (white) take X and the expert matrices in bf16 but
    # keep the routing weights, the incoming gradient and every sum in
    # float32, which a cast of all their inputs would lose (ops/moe.py
    # ``_ffn_args``).  ``gated_delta_rule`` is float32 by its own text
    # (ops/gated_delta_rule.py: its decays compound over the sequence and
    # its chunk algebra inverts a matrix) and on no other list: it takes
    # what the products and convolutions left (bf16) and widens it INSIDE,
    # its gradient op behind a barrier, so that a step keeps the bf16
    # operands between the two and not float32 copies of them.
    SELF_CAST = {"moe_expert_ffn", "gated_delta_rule"}

    def __init__(self, custom_white_list=None, custom_black_list=None):
        self.white_list = (set(self.WHITE) | set(custom_white_list or ())) \
            - set(custom_black_list or ())
        self.black_list = (set(self.BLACK) | set(custom_black_list or ())) \
            - set(custom_white_list or ())

    def colour(self, op_type):
        """"white", "black" or None (follow the inputs) for a forward op
        type: the lists first — a custom entry overrides a default — then
        the sparse-attention mixture-of-experts and state-space
        defaults."""
        if op_type in self.white_list:
            return "white"
        if op_type in self.black_list:
            return "black"
        if op_type in self.WHITE_SPARSE_MOE:
            return "white"
        if op_type in self.BLACK_SPARSE_MOE \
                or op_type in self.BLACK_STATE_SPACE:
            return "black"
        return None


class AMPPolicy:
    """Trace-time dtype policy consulted by registry.compute_op."""

    def __init__(self, amp_lists=None):
        self.lists = amp_lists or AutoMixedPrecisionLists()

    def __repr__(self):
        # by content, not by address: compile_cache.program_fingerprint
        # hashes this, and a compiled program's name is taken from the
        # fingerprint — it has to come out the same in the next process
        return "AMPPolicy(white=%s, black=%s)" % (
            sorted(self.lists.white_list), sorted(self.lists.black_list))

    def cast_inputs(self, op_type, ins):
        """Return ``ins`` with float32<->bf16 casts applied per the lists.
        Grad ops follow their forward op's color (the generic auto-vjp
        grad re-runs the forward, so the same cast yields the same
        bf16 compute in the backward pass)."""
        base = op_type[:-5] if op_type.endswith("_grad") else op_type
        colour = self.lists.colour(base)
        if colour is None or base in self.lists.SELF_CAST:
            return ins
        if colour == "white":
            target, source = jnp.bfloat16, jnp.float32
        else:
            target, source = jnp.float32, jnp.bfloat16
        out = {}
        for slot, vals in ins.items():
            out[slot] = [
                v.astype(target)
                if hasattr(v, "dtype") and v.dtype == source else v
                for v in vals
            ]
        return out


def decorate(optimizer, amp_lists=None, init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False):
    """Wrap an optimizer so that ``minimize(loss)`` marks the loss's
    program for bf16 mixed-precision execution.

    ``init_loss_scaling``/``use_dynamic_loss_scaling`` are accepted for
    API parity with the GPU fp16 recipe and ignored: bf16 keeps fp32's
    exponent range, so gradients cannot underflow the way fp16's do.
    """

    class _AMPOptimizer:
        def __init__(self, inner):
            self._inner = inner
            self._amp_policy = AMPPolicy(amp_lists)

        def minimize(self, loss, startup_program=None, **kw):
            # the rewrite is this mark: the policy casts at trace time,
            # so the pass's own seconds read ~0 and its cost is part of
            # the step's jax trace
            with build_pass(loss.block.program, "mixed_precision"):
                result = self._inner.minimize(
                    loss, startup_program=startup_program, **kw)
                loss.block.program._amp_policy = self._amp_policy
            return result

        def __getattr__(self, name):
            return getattr(self._inner, name)

    return _AMPOptimizer(optimizer)


class bf16_program_guard:
    """Context manager marking ``program`` for bf16 execution without an
    optimizer — the inference-side analog of the float16 transpiler
    (``float16_transpiler.py`` rewrites inference programs)."""

    def __init__(self, program, amp_lists=None):
        self.program = program
        self.policy = AMPPolicy(amp_lists)
        self._prior = None

    def __enter__(self):
        self._prior = getattr(self.program, "_amp_policy", None)
        self.program._amp_policy = self.policy
        return self.program

    def __exit__(self, *exc):
        self.program._amp_policy = self._prior
        return False


def cast_parameters_to_bf16(program, scope):
    """Hard-cast persistable fp32 params in ``scope`` to bf16 — the
    float16 transpiler's var-retyping path, for inference deployments
    that want bf16 weights at rest (half the HBM footprint)."""
    import numpy as np

    for var in program.global_block().vars.values():
        if not getattr(var, "persistable", False):
            continue
        if scope.has_var(var.name):
            v = scope.var(var.name)
            if hasattr(v, "dtype") and np.dtype(v.dtype) == np.float32:
                scope.set_var(var.name, jnp.asarray(v, dtype=jnp.bfloat16))
