"""Ring attention: sequence/context parallelism over the mesh.

The reference's long-sequence story is LoD + RNN (SURVEY.md §5); the
2026-scale equivalent this framework makes first-class is context
parallelism: the sequence axis is sharded over a mesh axis (``sp``) and
attention runs as a RING — each device holds its local Q block
resident and streams the K/V blocks around the ring with ``ppermute``
(one ICI hop per step), accumulating the softmax online (flash-style
running max/denominator).  Peak memory per device is O(T/n * T/n)
instead of O(T^2), and the K/V transfer overlaps compute on real ICI.

Public surface:

* ``ring_attention(q, k, v, mesh, axis='sp', causal=False)`` — jittable;
  q/k/v are [B, H, T, D] global arrays (or host arrays) that get
  time-sharded over ``axis`` via shard_map.
* ``ring_attention_shard(...)`` — the per-device body, usable inside an
  existing shard_map (e.g. a pjit'ed training step that already runs
  under the mesh).

Design refs: the blockwise/ring formulation in PAPERS.md; collectives
per pallas_guide.md "Ring Collectives" (ppermute ring pattern) — here
expressed at the XLA level (lax.ppermute) so GSPMD schedules ICI DMAs;
a Pallas RDMA variant can slot in later without changing the surface.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import AXIS_SP, shard_map_norep

__all__ = ["ring_attention", "ring_attention_shard"]

_NEG_INF = -1e30


def ring_attention_shard(q, k, v, axis_name, causal=False, scale=None,
                         k_len=None, dropout_rate=0.0, seed=None,
                         batch_axis_name=None, head_axis_name=None):
    """Per-device ring attention body (run under shard_map).

    q [B, H, Tq, D] local query block; k/v [B, H, Tk, D] local key/value
    blocks.  Streams K/V around the ``axis_name`` ring; returns the
    local attention output [B, H, Tq, D].

    ``k_len`` [B] masks padded key positions (global valid-key counts for
    this shard's batch rows); ``dropout_rate``/``seed`` apply the same
    counter-hash weight dropout as the single-chip fused_attention op
    (``ops/attention_xla._keep_mask`` on GLOBAL positions, so a
    ring run reproduces a single-chip run's mask bit-for-bit —
    downgrade_in_infer semantics: masked, not upscaled).
    ``batch_axis_name`` names the mesh axis the batch is sharded over, so
    the hash's global (batch*head) index stays correct under dp.
    ``head_axis_name`` likewise names the axis the HEAD dim is sharded
    over (tensor parallelism composing with the sequence ring): heads
    attend independently, so tp sharding is transparent to the math, and
    the head offset keeps dropout masks identical to a single-chip run.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    in_dtype = q.dtype
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # scores and online-softmax statistics accumulate in fp32: bf16
    # inputs (the AMP path) would drift across the n ring steps
    q = q.astype(jnp.float32) * scale

    # ring: at step i we hold the K/V block originally owned by shard
    # (idx + i) mod n; send to the previous neighbor each step so the
    # blocks rotate forward through every device exactly once
    perm = [(j, (j - 1) % n) for j in range(n)]

    q_pos = idx * tq + jnp.arange(tq)             # global query positions
    masked = causal or k_len is not None
    if dropout_rate:
        from ..ops.attention_xla import _keep_mask
        if seed is None:
            seed = jnp.zeros((), jnp.uint32)
        b_off = 0
        if batch_axis_name is not None:
            b_off = lax.axis_index(batch_axis_name) * b
        h_off = 0
        h_total = h
        if head_axis_name is not None:
            h_off = lax.axis_index(head_axis_name) * h
            h_total = h * lax.psum(1, head_axis_name)
        # global (batch*head) index per row, same layout as single-chip
        bh_idx = ((b_off + jnp.arange(b))[:, None] * h_total +
                  (h_off + jnp.arange(h))[None, :])[:, :, None, None]

    def step(i, carry):
        k_blk, v_blk, m, l, o = carry
        kv_owner = (idx + i) % n
        k_pos = kv_owner * tk + jnp.arange(tk)    # global key positions
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32)
        if masked:
            valid = jnp.ones((b, 1, tq, tk), bool)
            if k_len is not None:
                valid = k_pos[None, None, None, :] < \
                    k_len.astype(jnp.int32)[:, None, None, None]
            if causal:
                valid = valid & \
                    (q_pos[:, None] >= k_pos[None, :])[None, None]
            s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            # a fully-masked row keeps m_new == _NEG_INF, so exp(s - m_new)
            # is 1.0 per masked key; zero them explicitly rather than rely
            # on the diagonal block (tq == tk at step 0) being seen first —
            # ring_attention guarantees that, standalone shard use may not
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate:
            keep = _keep_mask(seed.astype(jnp.uint32), bh_idx,
                              q_pos[:, None], k_pos[None, :], dropout_rate)
            p = jnp.where(keep, p, 0.0)
        o = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk,
                                  preferred_element_type=jnp.float32)

        def rotate(blks):
            return tuple(lax.ppermute(x, axis_name, perm) for x in blks)

        # the final iteration's rotation would be discarded: skip the
        # two ICI transfers (n-1 hops move every block everywhere)
        k_blk, v_blk = lax.cond(i < n - 1, rotate,
                                lambda blks: blks, (k_blk, v_blk))
        return k_blk, v_blk, m_new, l, o

    m0 = jnp.full((b, h, tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq, 1), jnp.float32)
    o0 = jnp.zeros((b, h, tq, d), jnp.float32)
    _, _, m, l, o = lax.fori_loop(0, n, step, (k, v, m0, l0, o0))
    return (o / jnp.maximum(l, 1e-20)).astype(in_dtype)


def ring_attention(q, k, v, mesh, axis=AXIS_SP, causal=False,
                   scale=None, batch_axis=None):
    """Context-parallel attention over ``mesh``'s ``axis``.

    q/k/v: [B, H, T, D] with T divisible by the axis size.  Returns
    [B, H, T, D] sharded the same way (time over ``axis``).
    ``batch_axis`` optionally shards the batch dim over another mesh
    axis (dp composition); without it the batch replicates across the
    non-sp axes."""
    if axis not in mesh.axis_names:
        raise ValueError("mesh has no axis %r (axes: %s)"
                         % (axis, mesh.axis_names))
    if batch_axis is not None:
        if batch_axis not in mesh.axis_names:
            raise ValueError("mesh has no axis %r (axes: %s)"
                             % (batch_axis, mesh.axis_names))
        if batch_axis == axis:
            raise ValueError(
                "batch_axis must differ from the sequence axis %r" % axis)
    spec = P(batch_axis, None, axis, None)
    body = functools.partial(ring_attention_shard, axis_name=axis,
                             causal=causal, scale=scale)
    fn = shard_map_norep(body, mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return fn(q, k, v)
