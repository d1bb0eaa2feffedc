"""The packed attention kernel at the benchmark cells' real widths,
compiled by the TPU's own compiler for a v5e that is described and not
attached (no chip time, nothing runs): Mosaic has to accept the 64-lane
head slices, the batched products and the VMEM the blocks take, forward
and backward, or the step would fail on the chip at trace time.

The topology is described inside a fixture, after this file's first test
has started, and only here: one process at a time may load the TPU's
library."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import packed_attention as pa


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tk,causal,rate", [
    (64, False, 0.0),                # encoder self-attention, the cells'
    (64, True, 0.1),                 # decoder self-attention with dropout
    (128, False, 0.1),               # cross attention, Tq != Tk
])
def test_packed_kernel_compiles_for_v5e_at_the_cell_shape(one_chip, tk,
                                                          causal, rate):
    b, tq, h, d = 256, 64, 8, 64
    assert pa.supported((b, h, tq, d), (b, h, tk, d), jnp.bfloat16)

    def step(q, k, v, klen, seed, ct):
        out, vjp = jax.vjp(
            lambda q, k, v: pa.packed_attention(
                q, k, v, klen, seed, None, h, causal, rate, None, False),
            q, k, v)
        return (out,) + vjp(ct)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(step).lower(
            arg((b, tq, h * d), jnp.bfloat16),
            arg((b, tk, h * d), jnp.bfloat16),
            arg((b, tk, h * d), jnp.bfloat16), arg((b,), jnp.int32),
            arg((), jnp.uint32),
            arg((b, tq, h * d), jnp.bfloat16)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    # one forward and ONE backward kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("hk", [4, 32])
def test_streamed_kernel_compiles_for_v5e_at_the_long_document_shape(
        one_chip, hk):
    """The streamed kernel at ``keye_vl2_30b_a3b.train_longdoc_8k``'s shape
    — 32 query heads over 4 key/value heads of 128, T = 8192, bf16, a
    selection — forward and the fused backward: Mosaic has to accept the
    bit-plane unpacking of the packed key mask, the clamped block index
    maps, the rolled loop over the eight heads a grid step serves, the
    K/V head's whole float32 dK and dV ``[8192, 128]`` resident beside the
    step's ``[8, 512, 128]`` blocks, padded columns and scratch, and their
    one-buffered ``[8192, 128]`` output blocks (more than the default
    scoped limit: the kernels state their own).  And the same over 32
    key/value heads: plain heads, several a grid step, each with its own
    K/V block and resident gradients."""
    from paddle_tpu.ops import sparse_select as ss
    from paddle_tpu.ops.pallas import streamed_attention as sa

    b, h, t, d = 1, 32, 8192, 128
    assert sa.supported((b, h, t, d), (b, hk, t, d), jnp.bfloat16, True,
                        False, 0.0)
    kh, gh = sa._heads_per_step(h // hk, hk, 512, 512, d, 2)
    # 8 query heads a step over their one K/V head; plain heads by the block
    assert (kh, gh) == (1, 8) if hk == 4 else (kh > 1 and gh == 1)
    x = [jax.ShapeDtypeStruct((b, n, t, d), jnp.bfloat16) for n in (h, hk, hk)]
    assert sa.grad_step(*x) == ("streamed_fused",
                                (1, 8) if hk == 4 else (2, 1))

    def step(q, k, v, sel, ct):
        out, vjp = jax.vjp(
            lambda q, k, v: sa.streamed_attention(q, k, v, sel, True, None,
                                                  False), q, k, v)
        return (out,) + vjp(ct)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(step).lower(
            arg((b, h, t, d), jnp.bfloat16), arg((b, hk, t, d), jnp.bfloat16),
            arg((b, hk, t, d), jnp.bfloat16),
            arg((b, t, ss.packed_width(t)), jnp.int32),
            arg((b, h, t, d), jnp.bfloat16)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    # no [32, 8192, 8192] scores anywhere: the temporaries are the
    # log-sum-exp and delta columns and the outputs' staging
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 1024 * 1024


@pytest.mark.parametrize("h,t,dk,dv", [(32, 8192, 192, 128),
                                       (16, 4096, 128, 128)])
def test_streamed_kernel_compiles_for_v5e_at_the_plain_head_cells_shapes(
        one_chip, h, t, dk, dv):
    """The streamed kernel at ``joyai_llm_flash.train_mtp_8k``'s shape — 32
    plain heads, keys 192 wide over values 128 wide, T = 8192, bf16, no
    selection — forward and the fused backward: Mosaic has to accept a
    block whose last axis is the array's full 192 (two lane tiles, the
    second half full) and the contraction over it, several heads a grid
    step with a resident ``[8192, 192]`` float32 dK beside a ``[8192, 128]``
    dV each, the forward's per-lane state, all inside the VMEM limit the
    kernels state, and nowhere the ``[32, 8192, 8192]`` scores.  And at
    ``ouro_2_6b.train_loop_4k``'s: 16 plain heads of 128, T = 4096.  Eight
    heads a grid step forward at both; backward two and four."""
    from paddle_tpu.ops.pallas import streamed_attention as sa

    b = 1
    assert sa.supported((b, h, t, dk), (b, h, t, dk), jnp.bfloat16, True,
                        False, 0.0, dv)
    assert sa._heads_per_step(1, h, 512, 512, dk, 2, dv) == (8, 1)
    assert sa._fused_heads_per_step(1, h, 512, 512, t, dk, 2, dv) == (
        (2, 1) if dk == 192 else (4, 1))

    def step(q, k, v, ct):
        out, lse = sa.forward(q, k, v, None, True, dk ** -0.5, False)
        return (out,) + sa.backward(q, k, v, None, out, lse, ct, True,
                                    dk ** -0.5, False)

    def arg(width):
        return jax.ShapeDtypeStruct((b, h, t, width), jnp.bfloat16,
                                    sharding=one_chip)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(step).lower(arg(dk), arg(dk), arg(dv),
                                       arg(dv)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    # (float32 scores would be 8.6 GB; the log-sum-exp and delta columns
    # pad to 128 lanes, 134 MB each, and so do the backward's stagings)
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 1024 * 1024


@pytest.mark.parametrize("h,hk,t,dk,dv,window,kernels", [
    # the hybrid decoder's differential pairs: 64-wide keys under 128-wide
    # values, two query heads a K/V head, under the 512-key window and not
    (40, 20, 4096, 64, 128, 512, 2), (40, 20, 4096, 64, 128, None, 2),
    # 32k tokens: a K/V head's float32 dK and dV (48 MiB with the block they
    # are cast into) do not fit beside a step's blocks — dQ and dK/dV
    (8, 1, 32768, 128, 128, None, 3)])
def test_streamed_backward_compiles_for_v5e_in_either_body(
        one_chip, h, hk, t, dk, dv, window, kernels):
    """The body the rule picks by the operands' shapes — the fused kernel
    with its resident gradients, or dQ and dK/dV where they do not fit —
    compiles inside the VMEM limit the kernels state."""
    from paddle_tpu.ops.pallas import streamed_attention as sa

    def arg(n, width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, n, t, width), dtype,
                                    sharding=one_chip)
    q, k, v = arg(h, dk), arg(hk, dk), arg(hk, dv)
    assert sa.grad_step(q, k, v)[0] == (
        "streamed_fused" if kernels == 2 else "streamed")

    def step(q, k, v, ct):
        out, lse = sa.forward(q, k, v, None, True, None, False, window)
        return (out,) + sa.backward(q, k, v, None, out, lse, ct, True, None,
                                    False, window)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(step).lower(q, k, v, arg(h, dv)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == kernels


def _blocks_of_attention(sa, n, selected, scale, interpret):
    """A function of (q, k, v, dO) -> n x (out, dQ, dK, dV): ``n`` blocks'
    forward and backward through the streamed kernels, each call under its
    own Fluid scope as the step program's ops are."""
    def step(q, k, v, ct):
        got = []
        for i in range(n):
            with jax.named_scope("fluid[fused_attention]out_%d" % i):
                out, lse = sa.forward(q, k, v, selected, True, scale,
                                      interpret)
            with jax.named_scope("fluid[fused_attention_grad]q_%d.GRAD" % i):
                got.append((out,) + sa.backward(q, k, v, selected, out, lse,
                                                ct, True, scale, interpret))
            q = q + got[-1][1]
        return got
    return step


def _kernel_traces():
    from paddle_tpu import compile_cache

    counts = compile_cache.stats()["kernel_traces"].get(
        "streamed_attention", {"sites": 0, "traces": 0})
    return counts["sites"], counts["traces"]


def test_a_step_program_traces_and_lowers_each_streamed_kernel_once(one_chip):
    """Six blocks of forward + backward at the latent cell's shape in one
    jitted function, lowered for the described v5e: 12 call sites reach the
    kernels — a block's forward and its fused backward — and 2 jaxprs are
    made (``compile_cache.stats()``); the lowered text holds 12 custom
    calls, each under ITS OWN block's Fluid scope — the device trace's
    readers find the kernels by that scope, which is why the kernels may
    not move into shared jitted functions.  A second signature adds exactly
    two traces."""
    import re

    from paddle_tpu.ops.pallas import streamed_attention as sa

    b, h, t, dk, dv, blocks = 1, 32, 8192, 192, 128, 6
    pallas.traced.cache_clear()

    def arg(t, width):
        return jax.ShapeDtypeStruct((b, h, t, width), jnp.bfloat16,
                                    sharding=one_chip)
    step = _blocks_of_attention(sa, blocks, None, dk ** -0.5, False)
    sites, traces = _kernel_traces()
    text = jax.jit(step).lower(arg(t, dk), arg(t, dk), arg(t, dv),
                               arg(t, dv)).as_text(debug_info=True)
    assert _kernel_traces() == (sites + 2 * blocks, traces + 2)
    calls = re.findall(
        r"stablehlo\.custom_call @tpu_custom_call.*?loc\((#loc\d+)\)\s*$",
        text, re.M)
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    assert len(calls) == 2 * blocks
    scopes = [re.search(r"fluid\[\w+\][\w.]+", locs[c]).group(0)
              for c in calls]
    assert scopes == [s for i in range(blocks) for s in
                      ["fluid[fused_attention]out_%d" % i,
                       "fluid[fused_attention_grad]q_%d.GRAD" % i]]
    # another length: two more jaxprs; the first length again: none
    jax.jit(step).lower(arg(t // 2, dk), arg(t // 2, dk), arg(t // 2, dv),
                        arg(t // 2, dv))
    assert _kernel_traces() == (sites + 4 * blocks, traces + 4)
    jax.jit(lambda *a: step(*a)).lower(arg(t, dk), arg(t, dk), arg(t, dv),
                                       arg(t, dv))
    assert _kernel_traces() == (sites + 6 * blocks, traces + 4)


def test_a_program_traced_again_reuses_the_streamed_kernels_jaxprs():
    """The same function traced a second time (a new ``jax.jit`` of it, as
    a program lowered again is) makes no new jaxpr and, interpreted, gives
    the first trace's results; a selection is another signature: two
    more."""
    import numpy as np

    from paddle_tpu.ops import sparse_select as ss
    from paddle_tpu.ops.pallas import streamed_attention as sa

    h, t, dk, dv = 4, 256, 192, 128
    args = [jax.random.normal(jax.random.key(i), (1, h, t, w), jnp.float32)
            for i, w in enumerate((dk, dk, dv, dv))]
    pallas.traced.cache_clear()
    step = _blocks_of_attention(sa, 2, None, dk ** -0.5, True)
    sites, traces = _kernel_traces()
    first = jax.jit(step)(*args)
    assert _kernel_traces() == (sites + 4, traces + 2)
    again = jax.jit(lambda *a: step(*a))(*args)
    assert _kernel_traces() == (sites + 8, traces + 2)
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    words = ss.pack_key_mask(ss.topk_key_mask(
        jax.random.normal(jax.random.key(9), (1, t, t)), 64, True))
    jax.jit(_blocks_of_attention(sa, 2, words, dk ** -0.5, True))(*args)
    assert _kernel_traces() == (sites + 12, traces + 4)


def _primitives(jaxpr):
    """Every primitive's name in ``jaxpr``, in the jaxprs its equations
    hold (a kernel's body, its loops and branches) and in a
    ``pallas_call``'s index maps."""
    def inside(value):
        if hasattr(value, "block_mappings"):                # a grid mapping
            for m in value.block_mappings:
                yield from _primitives(m.index_map_jaxpr.jaxpr)
        elif hasattr(value, "eqns"):
            yield from _primitives(value)
        elif hasattr(value, "jaxpr"):
            yield from _primitives(value.jaxpr)
        elif isinstance(value, (tuple, list)):
            for x in value:
                yield from inside(x)
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            yield from inside(value)


@pytest.mark.parametrize("kernels", [2, 3])
@pytest.mark.parametrize("hk,selected", [(32, False), (4, True), (32, True)])
def test_no_index_of_the_streamed_kernels_divides_through_sign(
        hk, selected, kernels, monkeypatch):
    """``//`` and ``%`` on a traced index round to the floor through
    ``sign`` and a select — a dozen scalar operations each in the kernel's
    text, 480 of them once in a head loop (6.6 s of a step's lowering).
    The kernels' bodies and index maps divide with ``lax.div`` /
    ``lax.rem``: no ``sign``, no ``floor`` in the forward and the fused
    backward, nor in the dQ and dK/dV kernels of a sequence whose gradients
    VMEM does not hold."""
    from paddle_tpu.ops import sparse_select as ss
    from paddle_tpu.ops.pallas import streamed_attention as sa

    if kernels == 3:
        monkeypatch.setattr(sa, "_fused_heads_per_step", lambda *a: None)
    h, t, d = 32, 8192, 128
    q, ct = (jax.ShapeDtypeStruct((1, h, t, d), jnp.bfloat16),) * 2
    k = v = jax.ShapeDtypeStruct((1, hk, t, d), jnp.bfloat16)
    words = jax.ShapeDtypeStruct((1, t, ss.packed_width(t)), jnp.int32)

    def step(q, k, v, ct, words=None):
        out, lse = sa.forward(q, k, v, words, True, None, False)
        return sa.backward(q, k, v, words, out, lse, ct, True, None, False)
    jaxpr = jax.make_jaxpr(step)(q, k, v, ct,
                                 *([words] if selected else [])).jaxpr
    assert [e.primitive.name for e in jaxpr.eqns].count(
        "pallas_call") == kernels
    inside = set(_primitives(jaxpr))
    assert {"div", "rem"} & inside and not {"sign", "floor"} & inside


def _in_place_step(sa, n, dv, theta, blocks=1):
    """(q, kv, kr, dO) -> blocks x (out, dQ, dKV, dKShared) through the
    streamed kernels over the projections' layout, each call under its own
    Fluid scope."""
    def step(q, kv, kr, ct):
        got = []
        for i in range(blocks):
            with jax.named_scope("fluid[fused_attention]out_%d" % i):
                out, lse = sa.forward_in_place(q, kv, kr, n, dv, theta, True)
            with jax.named_scope("fluid[fused_attention_grad]q_%d.GRAD" % i):
                got.append((out,) + sa.backward_in_place(
                    q, kv, kr, n, dv, out, lse, ct, theta, True))
            q = q + got[-1][1]
        return got
    return step


@pytest.mark.parametrize("t,theta,heads", [(8192, 3.2e7, (8, 2)),
                                           (4096, None, (8, 4))])
def test_in_place_kernels_compile_for_v5e_at_the_latent_cells_shapes(
        one_chip, t, theta, heads):
    """The streamed kernels over the projections' layout at
    ``joyai_llm_flash.train_mtp_8k``'s shape — 32 heads, ``[q_nope 128 |
    q_rope 64]`` of Q ``[1, 8192, 6144]``, ``[k_nope 128 | v 128]`` of KV
    ``[1, 8192, 8192]``, the shared ``[1, 8192, 64]``, rotation — and at
    ``kimi_linear_48b_a3b.train_doc_4k``'s (4096 tokens, none): Mosaic has
    to accept the column blocks, the head loop's index in whole lane tiles,
    the lane rolls and parity selects of the rotation and of the odd heads'
    half-tile shift, two heads' (four at 4096) float32 ``[T, 256]`` dK|dV
    resident beside the shared part's ``[T, 128]``, inside the VMEM limit
    the kernels state.  Three blocks: 6 call sites, 2 traces."""
    from paddle_tpu.ops.pallas import streamed_attention as sa

    b, n, nope, rope, dv, blocks = 1, 32, 128, 64, 128, 3

    def arg(width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((b, t, width), dtype, sharding=one_chip)
    q, kv, kr, ct = (arg(n * (nope + rope)), arg(n * (nope + dv)), arg(rope),
                     arg(n * dv))
    assert sa.in_place_supported(q.shape, kv.shape, rope, n, dv, False, 0.0)
    assert sa.in_place_step(q, kv, n, dv) == heads
    pallas.traced.cache_clear()
    sites, traces = _kernel_traces()
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(_in_place_step(sa, n, dv, theta, blocks)).lower(
            q, kv, kr, ct).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert _kernel_traces() == (sites + 2 * blocks, traces + 2)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2 * blocks
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 1024 * 1024


@pytest.mark.parametrize("theta", [1e4, None])
def test_no_index_of_the_in_place_kernels_divides_through_sign(theta):
    from paddle_tpu.ops.pallas import streamed_attention as sa

    n, t, nope, rope, dv = 32, 8192, 128, 64, 128
    q, kv, kr, ct = (jax.ShapeDtypeStruct((1, t, w), jnp.bfloat16)
                     for w in (n * (nope + rope), n * (nope + dv), rope,
                               n * dv))
    jaxpr = jax.make_jaxpr(_in_place_step(sa, n, dv, theta))(q, kv, kr,
                                                             ct).jaxpr
    inside = list(_primitives(jaxpr))
    assert inside.count("pallas_call") == 2
    assert "div" in inside and not {"sign", "floor"} & set(inside)


def _expert_operands(held, tile, sharding=None):
    """The expert ops' operands at the two expert cells' size — 8192 tokens
    of 2048, eight experts a token, ``held`` experts of width 768 at tiles of
    ``tile`` rows, bf16 — as shapes: (x, routing weights, [gate, up, down],
    the dispatch layout's four, the layout's capacity in rows)."""
    from paddle_tpu.ops import moe

    n, d, f, k = 8192, 2048, 768, 8
    cap = moe.dispatch_capacity(n * k, held, tile)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    mats = [arg((held, d, f)), arg((held, d, f)), arg((held, f, d))]
    layout = [arg((cap,), jnp.int32), arg((cap,), jnp.int32),
              arg((cap // tile,), jnp.int32), arg((1,), jnp.int32)]
    return arg((n, d)), arg((n, k), jnp.float32), mats, layout, cap


def test_no_index_of_the_grouped_kernels_divides_through_sign():
    """The same guard over ``grouped_experts``: its five kernels' bodies
    and index maps, and the chunk loop around them, hold no ``sign`` and no
    ``floor`` (the one division, chunks from live tiles, is ``lax.div``)."""
    from paddle_tpu.ops.pallas import grouped_experts as ge

    tile = 640
    x, w, mats, layout, _ = _expert_operands(16, tile)

    def step(x, w, gate, up, down, *layout):
        y, _ = ge.forward(x, w, gate, up, down, layout, tile)
        return ge.backward(x, w, gate, up, down, layout, tile, y)
    inside = list(_primitives(jax.make_jaxpr(step)(x, w, *mats,
                                                   *layout).jaxpr))
    assert inside.count("pallas_call") == 5
    assert "div" in inside and not {"sign", "floor"} & set(inside)


@pytest.mark.parametrize("tile,held", [(640, 16), (384, 8)])
def test_grouped_expert_kernels_compile_for_v5e_at_both_cells_shapes(
        one_chip, tile, held):
    """``moe_expert_ffn`` and its gradient by the grouped kernels at the two
    expert cells' shapes — 8192 tokens of 2048, eight experts a token,
    experts of width 768, bf16; 16 held at tiles of 640 rows, 8 at 384:
    Mosaic has to accept the experts picked in the index maps from scalar
    prefetch, the clamped maps of dead tiles, the one-row read-modify-writes
    at token ids read from SMEM, the results carried in place with their
    fetch by DMA, and each kernel's VMEM under the limit it states — with
    the blocks and the chunk the rule derives.  Nothing of the capacity's
    rows is ever whole: the temporaries are a chunk's."""
    from paddle_tpu.ops.pallas import grouped_experts as ge

    x, w, mats, layout, cap = _expert_operands(held, tile, one_chip)
    d = x.shape[1]
    assert ge.supported(x, mats[0], tile)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        fwd = jax.jit(lambda x, w, g, u, dn, *lay: ge.forward(
            x, w, g, u, dn, lay, tile)).lower(x, w, *mats, *layout).compile()
        bwd = jax.jit(lambda x, w, g, u, dn, dy, *lay: ge.backward(
            x, w, g, u, dn, lay, tile, dy)).lower(
                x, w, *mats, x, *layout).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    target = 'custom_call_target="tpu_custom_call"'
    assert fwd.as_text().count(target) == 2
    assert bwd.as_text().count(target) == 3
    # ONE [capacity, D] array would be 0.3 GB in bf16, 0.6 in float32: all
    # the temporaries of either direction together are less
    assert ge._chunk_tiles(held) * tile * 5 < cap
    assert fwd.memory_analysis().temp_size_in_bytes < cap * d * 2 // 4
    assert bwd.memory_analysis().temp_size_in_bytes < cap * d * 2


def _tiny_expert_decoder(kind):
    """A two-expert-layer decoder of either kind at widths the grouped
    kernels take (whole lane tiles), its startup run: (program, loss, the
    feeds' names)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import sparse_moe_decoder as smd

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        names = ("tok", "lbl") + (("lbl2",) if kind == "latent" else ())
        feeds = [fluid.layers.data(n, shape=[32, 1], dtype="int64")
                 for n in names]
        if kind == "latent":
            loss, _ = smd.latent_decoder_lm(
                *feeds, 64, 2, 1, 128, smd.LatentSizes(4, 24, 16, 16, 8, 16),
                48, (2, 4, 1), 128, 2, 16, route_scale=2.5, expert_tile=128)
        else:
            loss, _, _ = smd.decoder_lm(*feeds, 64, 2, 128, 4, 2, 8,
                                        (2, 4, 1), 128, 2, 2, 8, 8,
                                        expert_tile=128)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    fluid.Executor(fluid.CPUPlace()).run(startup)
    return main, loss, names


@pytest.mark.parametrize("kind", ["sparse", "latent"])
def test_a_decoder_step_traces_and_lowers_each_grouped_kernel_once(
        one_chip, kind):
    """A step of a tiny decoder with two expert layers, traced for a TPU and
    lowered for the described v5e: both ops of both layers take the grouped
    kernels, ten call sites reach them (the forward's two and the backward's
    three a layer) and five jaxprs are made; the lowered text holds ten
    custom calls of theirs, each under ITS OWN op's Fluid scope."""
    import re

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import compile_cache
    from paddle_tpu import executor as ex

    def counts():
        stats = compile_cache.stats()
        traces = stats["kernel_traces"].get("grouped_experts",
                                            {"sites": 0, "traces": 0})
        return (stats["kernel_bodies"].get("moe_expert_ffn:grouped", 0),
                stats["kernel_bodies"].get("moe_expert_ffn_grad:grouped", 0),
                stats["kernel_bodies"].get("moe_expert_ffn:loop", 0),
                traces["sites"], traces["traces"])
    with fluid.scope_guard(fluid.Scope()):
        main, loss, names = _tiny_expert_decoder(kind)
        scope = fluid.global_scope()
        state, writeback = ex.analyze(main, sorted(names), scope, [loss.name])
        fn, _, _ = ex.trace_program(main, sorted(names), state, writeback,
                                    [loss.name], platform="tpu")

        def arg(v):
            return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
        pallas.traced.cache_clear()
        before = counts()
        ids = np.zeros((2, 32, 1), "int64")
        text = jax.jit(fn).lower(
            [arg(ex._coerce_feed(main.global_block(), n, ids))
             for n in sorted(names)],
            [arg(scope.var(n)) for n in state],
            arg(jax.random.key(0))).as_text(debug_info=True)
    assert tuple(a - b for a, b in zip(counts(), before)) == (2, 2, 0, 10, 5)
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    scopes = [re.search(r"fluid\[\w+\][\w.@]+", locs[c]).group(0)
              for c in re.findall(
                  r"stablehlo\.custom_call @tpu_custom_call.*?"
                  r"loc\((#loc\d+)\)\s*$", text, re.M)]
    ours = [s for s in scopes if "moe_expert_ffn" in s]
    forward = [s for s in ours if s.startswith("fluid[moe_expert_ffn]")]
    grad = [s for s in ours if s.startswith("fluid[moe_expert_ffn_grad]")]
    assert len(forward) == 4 and len(set(forward)) == 2
    assert len(grad) == 6 and len(set(grad)) == 2


@pytest.mark.parametrize("causal", [True, False])
def test_topk_select_kernel_compiles_for_v5e_at_the_long_document_shape(
        one_chip, causal):
    """``select_topk_keys``'s Pallas body at the cell's scores — float32
    [1, 8192, 8192], the 2048 highest keys a query: Mosaic has to accept the
    signed keys, the slab loops with a bound read from the grid position,
    the scalar test that skips the index passes, and 64-row blocks (2 MB of
    scores, twice for the pipeline, and the keys' scratch)."""
    from paddle_tpu.ops import sparse_select as ss
    from paddle_tpu.ops.pallas import topk_select

    shape = (1, 8192, 8192)
    assert topk_select.supported(shape, jnp.float32)
    assert topk_select._rows(*shape[1:]) == 64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(
            lambda x: topk_select.select_topk_words(x, 2048, causal, False)
        ).lower(jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
                ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    # nothing of the scores' size beside the scores: no key matrix, no mask
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1024 * 1024
    assert mem.output_size_in_bytes < 9 * 1024 * 1024
    assert ss.packed_width(shape[2]) == 256


@pytest.mark.parametrize("n,v,d,dtype", [
    (4096, 25008, 2560, jnp.float32),      # phi4_mini_flash.train_reason_4k
    (4096, 25008, 2560, jnp.bfloat16),
    (8192, 18992, 2048, jnp.float32),      # keye_vl2_30b_a3b.train_longdoc_8k
    (8192, 16160, 2048, jnp.float32),      # joyai_llm_flash.train_mtp_8k
    (4096, 6144, 2048, jnp.float32),       # ouro_2_6b.train_loop_4k
    (16384, 32000, 512, jnp.float32),      # transformer_base.train_nmt
    (4096, 200064, 2560, jnp.float32),     # the published tied table
])
def test_embedding_grad_kernel_compiles_for_v5e_at_the_cells_shapes(
        one_chip, n, v, d, dtype):
    """``lookup_table_grad``'s segment body at the one-chip cells' tables:
    Mosaic has to accept the five scalar-prefetched arrays (the sorted ids
    among them: 64 KB of SMEM at 16,384 rows), the one-row dynamic
    read-modify-write, the row loop between bounds read from SMEM, a last
    block that ends past the table (25,008 = 97 x 256 + 176) and four
    2.6 MB blocks under the kernel's own VMEM limit.  Beside the result and
    the sorted rows there is nothing of the table's size."""
    from paddle_tpu.ops.pallas import embedding_grad as eg

    assert eg.supported(n, v, d, jnp.float32, dtype)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(
            lambda ids, g: eg.embedding_grad(ids, g, v)
        ).lower(arg((n,), jnp.int32), arg((n, d), dtype)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes <= v * d * 4 + 4096
    rows = -(-n // eg.block_rows(d)) * eg.block_rows(d) * d * 4
    assert mem.temp_size_in_bytes <= rows + 1024 * 1024


@pytest.mark.parametrize("n", [16384, 4096])   # train_nmt; a shard of _dp4
def test_head_grad_kernel_compiles_for_v5e_at_the_cell_shape(one_chip, n):
    """The head's backward kernel at ``transformer_base``'s shape — 16,384
    target positions (4,096 a chip under the four-chip mesh) of 512-wide
    bf16 operands over 32,000 columns: Mosaic has to accept the whole float32
    dX ``[n, 512]`` as ONE resident, one-buffered output block (32 MiB)
    beside the ``[1024, 1280]`` tiles under the kernel's own VMEM limit, the
    ``[1024, 1]`` columns of the rows' scalars, and the row block's dynamic
    slice of dX.  Beside the results there are the two transposed operands
    and nothing of the logits' size."""
    from paddle_tpu.ops.pallas import head_grad as hg

    d, v = 512, 32000
    assert hg.supported(n, d, v, jnp.bfloat16)
    assert hg.blocks(n, d, v) == (1024, 1280)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(
            lambda x, w, z, b, lse, label, ct: hg.head_grad(
                x, w, z, b, lse, label, ct, 0.1)
        ).lower(arg((n, d), jnp.bfloat16), arg((d, v), jnp.bfloat16),
                arg((n, v), jnp.bfloat16), arg((v,), jnp.float32),
                arg((n,), jnp.float32), arg((n,), jnp.int32),
                arg((n,), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes <= (n * d + d * v) * 2 + v * 4 + 4096
    # x^T, w^T, the float32 dX before its rounding, the rows' columns
    assert mem.temp_size_in_bytes <= (n * d + d * v) * 2 + n * d * 4 \
        + 2 * 1024 * 1024


@pytest.mark.parametrize("body", ["chunked", "xla"])
def test_gated_delta_rule_compiles_for_v5e_inside_its_memory(
        one_chip, body, request):
    """``gated_delta_rule`` and its gradient op at ONE layer of the
    delta-attention cell — 4096 steps of 32 heads of 128, the operands bf16
    as a mixed-precision step hands them over — for the described v5e.  By
    the op's rule on a TPU both lower to the kernels of
    ``ops/pallas/gated_delta_rule.py`` (one custom call forward, two
    backward) and a chunk's local parts never reach HBM: what is left is the
    inputs' relayout to ``[B, T, H * D]`` and, backward, the 128 MiB of
    states the chunks start on.  With ``FLAGS_pallas_kernels`` off they keep
    the XLA body and its temporaries: the forward's chunk-local parts for
    all 64 chunks at once (0.55 GB), the backward's a group of 16 chunks at
    a time (0.9 GB where all at once took 1.9).  ``Starts`` is the same
    array either way."""
    from paddle_tpu.ops import gated_delta_rule as gdr

    if body == "xla":
        request.getfixturevalue("no_pallas")

    class Ctx:
        platform, mesh = "tpu", None
    b, t, h, d = 1, 4096, 32, 128
    attrs = {"chunk": 64, "scale": d ** -0.5, "epsilon": 1e-5}

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    ins = {"Q": [arg((b, t, h, d))], "K": [arg((b, t, h, d))],
           "V": [arg((b, t, h, d))], "G": [arg((b, t, h, d))],
           "Beta": [arg((b, t, h), jnp.float32)],
           "ALog": [arg((h,), jnp.float32)],
           "DtBias": [arg((h, d), jnp.float32)],
           "OutGate": [arg((b, t, h, d))],
           "OutNorm": [arg((d,), jnp.float32)]}
    starts = arg((b, 4, h, d, d), jnp.float32)      # 64 chunks in 4 groups
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    pallas.traced.cache_clear()
    try:
        fwd = jax.jit(lambda ins: gdr._compute(ins, attrs, Ctx, 0)).lower(
            ins).compile()
        bwd = jax.jit(lambda ins, starts, dout: gdr._grad_compute(
            dict(ins, **{"Out::Starts": [starts], "GRAD::Out": [dout]}),
            attrs, Ctx, 0)).lower(
            ins, starts, arg((b, t, h, d), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        pallas.traced.cache_clear()
    calls = [text.count("tpu_custom_call") > 0
             for text in (fwd.as_text(), bwd.as_text())]
    assert calls == [body == "chunked"] * 2
    out = jax.eval_shape(lambda ins: gdr._compute(ins, attrs, Ctx, 0), ins)
    assert out["Starts"].shape == starts.shape
    assert out["Out"].shape == (b, t, h, d) and out["Out"].dtype == jnp.float32
    gib = 1024 ** 3
    limits = {"chunked": (0.2, 0.4), "xla": (0.75, 1.25)}[body]
    assert fwd.memory_analysis().temp_size_in_bytes < limits[0] * gib
    assert bwd.memory_analysis().temp_size_in_bytes < limits[1] * gib
