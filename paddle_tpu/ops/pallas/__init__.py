"""Pallas TPU kernels — alternative compute bodies for hot ops.

The op registry's kernels are pure JAX (``registry.py``); the modules here
are hand-tiled Pallas bodies for ops where explicit VMEM staging beats XLA's
automatic fusion on a ledger line.

Selection: ONE way.  Each kernel is picked by a rule over what its op can
observe — platform, mesh, shapes, dtype — written beside the op, and every
rule goes through ``kernel_allowed`` here: a trace for one of the rule's
platforms, and ``FLAGS_pallas_kernels`` (default True: "the rules may pick
Pallas kernels"; False is the operator's switch against a kernel that
miscompiles on a new runtime, part of every step's cache key, and then every
op lowers to its XLA body).  No flag, table or environment variable turns a
kernel ON.  The rules:

* ``packed_attention`` — short-sequence attention over the projections'
  ``[B, T, H*D]`` layout: ``ops/attention._packed_applicable`` (a TPU, the
  shape inside its VMEM bound, ``packed_attention.supported``; measured 2.8x
  ahead of the XLA body, PERF.md 6.6).
* ``streamed_attention`` — grouped heads, selected keys, values narrower than
  the keys, or plain heads the layer marked ``keep_lse``; any length:
  ``ops/attention._streamed_applicable``.  The same kernels over a latent
  block's projections where they lie (``[B, T, H * D]`` operands, the shared
  key part its own; PERF.md 6.25): ``ops/attention._in_place_applicable``;
  and over a grouped-head block's three projections (``[B, T, H * D]`` with a
  V, the rotation inside the op; PERF.md 6.27):
  ``ops/attention._grouped_body``.
* ``topk_select`` — ``select_topk_keys`` with a query block's scores held in
  VMEM, one read of the scores where the XLA body makes 46 (PERF.md 6.8):
  ``ops/sparse_select._kernel_applicable``.
* ``grouped_experts`` — ``moe_expert_ffn`` and its gradient over the live
  tiles of the dispatch layout, a tile's expert picked in the weights' index
  maps, results combined in VMEM where the XLA body scatters 640 rows a tile
  (PERF.md 6.15): ``ops/moe``'s rule.
* ``embedding_grad`` — ``lookup_table_grad`` into a dense table, the sorted
  rows added into blocks of the table resident in VMEM, each written once
  (PERF.md 6.20): ``ops/manipulation.segment_body``.
* ``selective_scan`` — the chunked scan and its backward:
  ``ops/state_space``'s rule.
* ``gated_delta_rule`` — the delta-attention recurrence's chunk algebra and
  its pull-back with a chunk's local parts and the running state in VMEM
  (three kernels; PERF.md 6.24): ``ops/gated_delta_rule._kernels``.
* ``head_grad`` — the one body of SEVERAL ops: where a program's backward
  holds ``softmax_with_cross_entropy_grad`` -> ``elementwise_add_grad`` (the
  bias; optional) -> ``mul_grad`` of the same logits, consecutive, hard
  labels, no ``ignore_index``, nothing else reading the ``Softmax`` output or
  the two gradients in between, the chain rule in ``ops/loss.py``
  (``registry.compute_ops`` asks it) lowers the two or three ops with it
  under ``mul_grad``'s Fluid scope — on a TPU, bf16 products under a float32
  loss, a 2-D weight at most 1024 wide, whole tiles (V % 128, N % 128), dX
  inside VMEM; per shard under a mesh whose every populated axis is a data
  axis, dW and db summed over it (``kernel_bodies``: ``mul_grad:head_fused``,
  else ``mul_grad:head_by_op``; PERF.md 6.21).

(``conv_bn`` is the body of the fused conv+BN ops, which only the
``fuse_conv_bn`` program pass emits.)  On the CPU these ops keep their XLA
bodies: no rule names it as a platform, and the tests that want a kernel
through its op patch the rule's platform tuple and run it interpreted
(``interpret_mode``).  Every call site records the body it lowered to
(``compile_cache.note_kernel_body``); ``run_traced`` traces and lowers a
kernel once a step program.
"""

import functools
import time

from ...flags import flag
from ...compile_cache import note_kernel_trace

# What a grid step of the kernels an op picks by shape may hold in VMEM, and
# the limit they are compiled under (Mosaic's default scoped limit is 16 MiB;
# a v5e core has 128 MiB): ``streamed_attention`` says what fills it there,
# ``grouped_experts`` here.
VMEM_BUDGET = 48 * 1024 * 1024
# Signatures whose jaxprs ``traced`` keeps (a step program has a handful; a
# jaxpr is a few hundred equations and holds no array).
_TRACES_KEPT = 3 * 32


def interpret_mode(ctx):
    """Whether a Pallas call traced under ``ctx`` runs interpreted.

    Decided ONLY by the platform of the device the executor places the
    step on (``ctx.platform``, threaded from the Place / mesh at trace
    time): "tpu" compiles through Mosaic — interpret mode is impossible
    there, not merely unlikely — and "cpu" interprets.  Anything else
    (no platform threaded, a backend with no Pallas path here) is an
    error: guessing from which devices happen to exist is how a chip run
    could quietly execute the interpreter, or a CPU-placed step receive
    a Mosaic kernel."""
    platform = getattr(ctx, "platform", None)
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        "a Pallas kernel was selected but the trace context carries "
        "platform=%r: the executor must thread its device's platform "
        "('tpu' compiles via Mosaic, 'cpu' interprets)" % (platform,))


def kernel_allowed(ctx, platforms):
    """What every kernel's rule shares: a trace for one of ``platforms``,
    and the operator has not switched Pallas off
    (``FLAGS_pallas_kernels=False``)."""
    return getattr(ctx, "platform", None) in platforms \
        and flag("pallas_kernels")


@functools.lru_cache(maxsize=_TRACES_KEPT)
def traced(kernel, call, operands, **statics):
    """The jaxpr of ``call`` — a function that makes one ``pallas_call`` —
    on ``operands`` (a ``ShapeDtypeStruct`` each, None for one that is not
    there) under ``statics``, traced the first time the signature is asked
    for; ``kernel`` names the family in ``stats()["kernel_traces"]``."""
    import jax

    t0 = time.perf_counter_ns()
    closed = jax.make_jaxpr(functools.partial(call, **statics))(*operands)
    note_kernel_trace(kernel, "traces", (time.perf_counter_ns() - t0) / 1e9)
    assert not closed.consts, "a kernel's trace holds no arrays"
    return closed.jaxpr


def run_traced(kernel, call, operands, **statics):
    """``call(*operands, **statics)`` by the signature's one jaxpr: every
    site of a step program binds the SAME ``pallas_call`` equation, under
    its own name stack, so jax lowers a distinct kernel to Mosaic once
    (``mlir._cached_lowering`` is keyed on the equation's params, and a
    ``pallas_call`` built anew carries new index maps and a new partial of
    its kernel: eighteen traces and eighteen lowerings for the three
    streamed kernels of a six-block step).  Not a ``jax.jit`` around the
    kernel: that would lower once too, into a shared function under no
    op's ``fluid[..]`` scope, where the device trace's readers lose it."""
    import jax

    note_kernel_trace(kernel, "sites")
    jaxpr = traced(kernel, call, tuple(
        None if x is None else jax.ShapeDtypeStruct(x.shape, x.dtype)
        for x in operands), **statics)
    return jax.core.eval_jaxpr(
        jaxpr, (), *[x for x in operands if x is not None])


def block_rows(n, row_bytes, max_rows, vmem_budget=4 * 1024 * 1024):
    """Pick a row-block size and the padded row count for a [n, ...]
    kernel: fit ``row_bytes`` per row into the VMEM budget, then pad n
    UP to a multiple of the block (an exact-divisor search would
    degenerate to 1-row blocks for prime n).  Returns (bn, n_padded);
    callers zero-pad inputs to n_padded and slice outputs back to n.
    """
    bn = max(1, vmem_budget // max(row_bytes, 1))
    bn = min(bn, max(n, 1), max_rows)
    # Mosaic requires the sublane (second-to-last) block dim be a multiple
    # of 8 (or equal the array dim): round down to 8-aligned, minimum 8 —
    # tiny n still pads up to one 8-row block
    bn = max(8, (bn // 8) * 8)
    n_padded = ((n + bn - 1) // bn) * bn
    return bn, n_padded
