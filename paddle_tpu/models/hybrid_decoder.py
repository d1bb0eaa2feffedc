"""A decoder-hybrid-decoder language model (SambaY, arXiv:2507.06607): a
self-decoder of state-space (Mamba) layers and differential attention under
a sliding window, one full-attention layer, and a cross-decoder whose layers
READ what two self-decoder layers wrote — gated memory units the last Mamba
layer's scan output, cross-attention the full-attention layer's keys and
values.  The fourth kind of block of the decoder family in
:mod:`.sparse_moe_decoder`, which holds what the kinds share (``_proj``,
``_attr``, ``_declare_step_stats``).

Every layer, for ``x`` [B, T, D]: ``x += mixer(LN1(x))``; ``[g | u] = LN2(x)
W1``; ``x += (silu(g) * u) W2``.  ``LN`` is LayerNorm with scale and bias; no
bias on any projection; no positional encoding anywhere.  The mixers
(``kinds``, one a layer):

* ``mamba`` — ``[u | z] = h Win``; ``c = silu(causal_conv1d(u))`` (width 4,
  depthwise, bias); ``[d | B | C] = c Wx``; the selective scan of ``c`` with
  step size ``softplus(d Wdt + bdt)``, ``A = -exp(A_log)``, ``B``, ``C``, ``D``
  (``layers.selective_scan``, float32) gives ``y``; out ``(y * silu(z))
  Wout``.  ``y``, BEFORE the gate, is the memory the gated memory units read.
* ``window`` / ``full`` — differential attention: ``[q | k | v] = h Wqkv`` as
  ``n_head`` query and ``n_kv_head`` key/value heads of ``head_dim``, read in
  pairs of neighbours: query pair ``p`` = heads ``2p, 2p + 1``; it reads
  key/value pair ``r = p // (n_head / n_kv_head)``: keys ``k_{2r}, k_{2r+1}``
  and ONE value ``[v_{2r} | v_{2r+1}]``, twice ``head_dim`` wide.  ``a_{p,i}
  = softmax_mask(q_{p,i} k_{r,i}^T head_dim^-0.5) V_r`` — two
  ``fused_attention`` calls a layer, the first softmax map and the second,
  grouped heads with keys half as wide as the values —; ``o_p = (1 - lam0)
  rms_norm(a_{p,1} - lam a_{p,2})`` (one learned gain of ``2 head_dim`` a
  layer); ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``, four learned
  ``head_dim``-vectors a layer, ``lam0 = 0.8 - 0.6 exp(-0.3 l)`` for the
  layer's published number ``l``; out ``concat_p(o_p) Wo``.  Mask: causal,
  and under ``window`` the nearest ``window`` keys.  A ``full`` layer's
  projected ``k`` and ``v`` are what ``cross`` layers read.
* ``cross`` — ``q = h Wq`` only; the same differential form over the ``full``
  layer's keys and values, causal, with its own ``lam`` vectors, gain and
  ``Wo``.
* ``gmu`` — a gated memory unit: ``(M * silu(h Wg1)) Wg2`` over the last
  ``mamba`` layer's ``y``.

After the last layer a final LayerNorm and the TIED head: logits ``x Emb^T``
(the embedding table transposed: one parameter, its gradient the lookup's
scattered rows plus the head's product) and the mean next-token cross entropy
in float32.  So three variables have gradients from two layers each, which
``backward``'s accumulator sums as it does a parameter's: the table, the
``full`` layer's K and V, the ``mamba`` layer's ``y``.
"""

import collections
import math

import numpy as np

from .. import layers, unique_name
from ..initializer import (ConstantInitializer, NormalInitializer,
                           NumpyArrayInitializer)
from ..param_attr import ParamAttr
from .sparse_moe_decoder import _attr, _declare_step_stats, _proj

__all__ = ["hybrid_decoder_lm", "HybridSizes", "HYBRID_STEP_STATS",
           "lambda_init", "window_pair_share"]

# the step's counters (``Program.step_stats``): the share of the causal
# (query, key) pairs a window layer attends, the RMS of the last state-space
# layer's final state and of its scan output (the memory), the attention
# layers' mean lambda
HYBRID_STEP_STATS = ("window_pair_share", "scan_state_rms", "memory_rms",
                     "diff_lambda")

# a hybrid decoder's sizes beyond the model width and the layer kinds
HybridSizes = collections.namedtuple(
    "HybridSizes", "n_head n_kv_head head_dim ffn_width window ssm_width "
    "ssm_state conv_width dt_rank")


def lambda_init(layer):
    """``lam0`` of the published layer ``layer`` (the Differential
    Transformer's schedule)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def window_pair_share(seq, window):
    """The share of the causal pairs of ``seq`` positions that a window of
    ``window`` keys keeps."""
    w = min(window, seq)
    return (w * (w + 1) // 2 + (seq - w) * w) / (seq * (seq + 1) // 2)


def _layer_norm(x, name, eps):
    return layers.layer_norm(
        x, begin_norm_axis=2, epsilon=eps,
        param_attr=ParamAttr(name=name + ".g",
                             initializer=ConstantInitializer(1.0)),
        bias_attr=ParamAttr(name=name + ".b",
                            initializer=ConstantInitializer(0.0)))


def _param(name, shape, initializer):
    return layers.create_parameter(
        shape, "float32", attr=ParamAttr(name=name, initializer=initializer))


def _rms(v):
    """sqrt(mean(v^2)) as a [1] float32 variable, off the gradient's
    path."""
    out = layers.sqrt(layers.reduce_mean(layers.square(v), keep_dim=False))
    out = layers.reshape(out, shape=[1])
    out.stop_gradient = True
    return out


def _mamba(h, prefix, sizes):
    """The state-space mixer over ``h`` [B, T, D]; returns ``(out, y,
    state)``: the mixer's output, the scan's output before the gate (the
    memory) and the final state [B, E, N]."""
    e, n, r = sizes.ssm_width, sizes.ssm_state, sizes.dt_rank
    u, z = layers.split(_proj(h, 2 * e, prefix + "ssm.in"), 2, dim=-1)
    c = layers.causal_conv1d(
        u, sizes.conv_width, act="silu",
        param_attr=_attr(prefix + "ssm.conv.w"),
        bias_attr=ParamAttr(name=prefix + "ssm.conv.b",
                            initializer=ConstantInitializer(0.0)))
    d, b, cc = layers.split(_proj(c, r + 2 * n, prefix + "ssm.x"),
                            [r, n, n], dim=-1)
    # Mamba's initialisation: A = -(1 .. N) for every channel, D = 1, and
    # a step-size bias whose softplus runs from 1e-3 to 0.1 over the
    # channels (log-spaced here; drawn log-uniform where weights are drawn)
    a_log = np.tile(np.log(np.arange(1, n + 1, dtype=np.float32)), (e, 1))
    step = np.exp(np.linspace(math.log(1e-3), math.log(0.1), e))
    a = layers.scale(layers.exp(_param(
        prefix + "ssm.A_log", [e, n], NumpyArrayInitializer(a_log))),
        scale=-1.0)
    y, state = layers.selective_scan(
        c, _proj(d, e, prefix + "ssm.dt.w"), a, b, cc,
        _param(prefix + "ssm.D", [e], ConstantInitializer(1.0)),
        delta_bias=_param(prefix + "ssm.dt.b", [e], NumpyArrayInitializer(
            (step + np.log(-np.expm1(-step))).astype(np.float32))))
    out = _proj(layers.swiglu(z, y), h.shape[-1], prefix + "ssm.out")
    return out, y, state


def _to_bhtd(t):
    return layers.transpose(t, perm=[0, 2, 1, 3])


def _pair_heads(v, pairs, width):
    """``v`` [B, T, 2 * pairs * width] as its pairs' first and second heads,
    each [B, pairs, T, width]."""
    v = layers.reshape(v, shape=[0, 0, pairs, 2 * width])
    return tuple(_to_bhtd(t) for t in layers.split(v, 2, dim=-1))


def _differential(q, kv, prefix, layer, sizes, eps, window=None):
    """Differential attention of the projected queries ``q`` [B, T, n_head *
    head_dim] over ``kv`` = (k1, k2 [B, pairs, T, head_dim], v [B, pairs, T, 2
    head_dim]); returns ``(o [B, T, n_head * head_dim], lam [1])``."""
    dh = sizes.head_dim
    q1, q2 = _pair_heads(q, sizes.n_head // 2, dh)
    k1, k2, v = kv
    a1, a2 = (layers.fused_attention(qi, ki, v, causal=True,
                                     scale=dh ** -0.5, window=window)
              for qi, ki in ((q1, k1), (q2, k2)))
    vec = {n: _param(prefix + "attn." + n, [dh], NormalInitializer(0.0, 0.1))
           for n in ("lq1", "lk1", "lq2", "lk2")}
    lam0 = lambda_init(layer)
    lam = layers.scale(layers.elementwise_sub(*(
        layers.exp(layers.reduce_sum(layers.elementwise_mul(
            vec["lq" + i], vec["lk" + i]), keep_dim=True)) for i in "12")),
        bias=lam0)
    diff = layers.elementwise_sub(a1, layers.elementwise_mul(a2, lam))
    o = layers.scale(layers.rms_norm(
        diff, eps, ParamAttr(name=prefix + "attn.sub.g")), scale=1.0 - lam0)
    return layers.reshape(_to_bhtd(o), shape=[0, 0, sizes.n_head * dh]), lam


def _attention(h, prefix, layer, sizes, eps, window):
    """A self-attention mixer; returns ``(out, kv, lam)``."""
    dh, d = sizes.head_dim, h.shape[-1]
    q, k, v = layers.split(
        _proj(h, (sizes.n_head + 2 * sizes.n_kv_head) * dh,
              prefix + "attn.qkv"),
        [sizes.n_head * dh, sizes.n_kv_head * dh, sizes.n_kv_head * dh],
        dim=-1)
    pairs = sizes.n_kv_head // 2
    kv = _pair_heads(k, pairs, dh) + (_to_bhtd(layers.reshape(
        v, shape=[0, 0, pairs, 2 * dh])),)
    o, lam = _differential(q, kv, prefix, layer, sizes, eps, window)
    return _proj(o, d, prefix + "attn.o"), kv, lam


def _cross(h, kv, prefix, layer, sizes, eps):
    q = _proj(h, sizes.n_head * sizes.head_dim, prefix + "attn.q")
    o, lam = _differential(q, kv, prefix, layer, sizes, eps)
    return _proj(o, h.shape[-1], prefix + "attn.o"), lam


def _gmu(h, memory, prefix):
    return _proj(layers.swiglu(
        _proj(h, memory.shape[-1], prefix + "gmu.in"), memory),
        h.shape[-1], prefix + "gmu.out")


def hybrid_decoder_lm(tokens, labels, vocab_size, kinds, first_layer, d_model,
                      sizes, norm_eps=1e-5):
    """The training graph over ``tokens`` / ``labels`` [B, T, 1] int64 (every
    position real, one document a row).  ``kinds`` names the layers' mixers in
    order (``mamba``, ``window``, ``full``, ``gmu``, ``cross``; a ``gmu``
    after a ``mamba``, a ``cross`` after a ``full``), ``first_layer`` the
    published number of the first of them — parameters are named
    ``l<number>.<..>`` and a layer's ``lam0`` follows its number.  Returns
    ``(loss, stats, state)``: the mean next-token cross entropy [1], a ``[4]``
    float32 variable a caller fetches WITH the loss under
    ``HYBRID_STEP_STATS`` (``Program.step_stats``), and the last ``mamba``
    layer's final state [B, E, N] (no gradient)."""
    seq = tokens.shape[1]
    x = layers.embedding(tokens, size=[vocab_size, d_model],
                         param_attr=_attr("tok_emb"))
    table = x.block.program.global_block().var("tok_emb")
    memory = state = kv = None
    lams, share = [], 1.0
    for i, kind in enumerate(kinds):
        number = first_layer + i
        prefix = "l%d." % number
        with unique_name.guard(prefix):
            h = _layer_norm(x, prefix + "ln1", norm_eps)
            if kind == "mamba":
                out, memory, state = _mamba(h, prefix, sizes)
            elif kind in ("window", "full"):
                window = sizes.window if kind == "window" else None
                out, layer_kv, lam = _attention(h, prefix, number, sizes,
                                                norm_eps, window)
                lams.append(lam)
                if kind == "full":
                    kv = layer_kv
                else:
                    share = window_pair_share(seq, window)
            elif kind == "cross":
                out, lam = _cross(h, kv, prefix, number, sizes, norm_eps)
                lams.append(lam)
            elif kind == "gmu":
                out = _gmu(h, memory, prefix)
            else:
                raise ValueError("unknown kind of layer %r" % (kind,))
            x = layers.elementwise_add(x, out)
            g, u = layers.split(
                _proj(_layer_norm(x, prefix + "ln2", norm_eps),
                      2 * sizes.ffn_width, prefix + "mlp.w1"), 2, dim=-1)
            x = layers.elementwise_add(
                x, _proj(layers.swiglu(g, u), d_model, prefix + "mlp.w2"))
    x = _layer_norm(x, "ln_f", norm_eps)
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.matmul(x, table, transpose_y=True), labels))
    lam = layers.scale(layers.sums(lams), scale=1.0 / len(lams))
    lam.stop_gradient = True
    stats = layers.concat([
        layers.fill_constant([1], "float32", share), _rms(state),
        _rms(memory), lam], axis=0)
    return loss, _declare_step_stats(loss, stats, HYBRID_STEP_STATS), state
