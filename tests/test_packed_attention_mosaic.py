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
    selection — forward, dQ and dK/dV: Mosaic has to accept the bit-plane
    unpacking of the packed key mask, the clamped block index maps, the
    rolled loop over the eight heads a grid step serves and the VMEM their
    ``[8, 512, 128]`` blocks, padded columns and scratch take (more than
    the default scoped limit: the kernels state their own).  And the same
    over 32 key/value heads: plain heads, a loop of one."""
    from paddle_tpu.ops import sparse_select as ss
    from paddle_tpu.ops.pallas import streamed_attention as sa

    b, h, t, d = 1, 32, 8192, 128
    assert sa.supported((b, h, t, d), (b, hk, t, d), jnp.bfloat16, True,
                        False, 0.0)
    assert sa._heads_per_step(h // hk, 512, 512, d, 2) == h // hk

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
        'custom_call_target="tpu_custom_call"') == 3
    # no [32, 8192, 8192] scores anywhere: the temporaries are the
    # log-sum-exp and delta columns and the outputs' staging
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 1024 * 1024


def test_streamed_kernel_compiles_for_v5e_at_the_latent_attention_shape(
        one_chip):
    """The streamed kernel at ``joyai_llm_flash.train_mtp_8k``'s shape — 32
    plain heads, keys 192 wide over values 128 wide, T = 8192, bf16, no
    selection — forward, dQ and dK/dV: Mosaic has to accept a block whose
    last axis is the array's full 192 (two lane tiles, the second half
    full) and the contraction over it, a ``[512, 192]`` float32 dQ
    accumulator beside a ``[512, 128]`` one for dV, and nowhere the
    ``[32, 8192, 8192]`` scores."""
    from paddle_tpu.ops.pallas import streamed_attention as sa

    b, h, t, dk, dv = 1, 32, 8192, 192, 128
    assert sa.supported((b, h, t, dk), (b, h, t, dk), jnp.bfloat16, True,
                        False, 0.0, dv)

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
        'custom_call_target="tpu_custom_call"') == 3
    # (float32 scores would be 8.6 GB; the log-sum-exp and delta columns
    # pad to 128 lanes, 134 MB each, and so do the backward's stagings)
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 1024 * 1024


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
