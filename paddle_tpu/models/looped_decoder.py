"""A looped dense decoder-only language model: ONE stack of sandwich-norm
blocks run ``passes`` times over the same weights, with a learned exit gate
and the expected loss over it.  The third kind of block of the decoder
family in :mod:`.sparse_moe_decoder`, which holds what the kinds share
(``_proj``, ``_gated_ffn``, ``_head_token_loss``, ``_declare_step_stats``).

**Sandwich-norm blocks** (``sandwich_block``), for ``x`` [B, T, D], no
biases: an RMSNorm before AND after each half, the residual added after the
second —

1. ``a = rms_norm(x; ln1)``; ``q, k, v = a Wq, a Wk, a Wv`` as ``n_head``
   heads of ``head_dim``; rotary on ``q``, ``k`` (rotate-half, all
   ``head_dim`` dimensions, ``rope_theta``); ``o`` = causal softmax
   attention, scale ``head_dim^-0.5``; ``x += rms_norm(o Wo; ln2)``.
2. ``m = rms_norm(x; ln3)``; ``x += rms_norm((silu(m Wg) * (m Wu)) Wd;
   ln4)``.

**The loop** (``looped_decoder_lm``): ``h_0 = Emb(tok)``; for ``t = 1 ..
passes``: ``h_t = rms_norm(Stack(h_{t-1}); ln_f)`` — the SAME blocks and the
same final norm each pass, the normed state fed to the next pass — and per
pass the untied head's per-token cross entropy of ``h_t``.  The step
minimises ``layers.exit_gate_loss`` of the passes' states and losses.

The passes are UNROLLED in the ``Program``: every application of a block is
its own run of ops, all reading the same parameters, so a weight's gradient
is ONE ``sum`` over its ``passes`` applications' contributions
(``backward._GradAccumulator``).
Every variable an application makes is named ``p<t>.l<i>.<..>`` (pass from
1, block from 0; an application starts by naming its input so, with an
``assign``), a pass's final norm, head and loss ``p<t>.<..>``, so a device
trace's ``fluid[<type>]<variable>`` scopes split by pass and block.
"""

from .. import layers, unique_name
from ..initializer import ConstantInitializer
from ..param_attr import ParamAttr
from .sparse_moe_decoder import (_attr, _declare_step_stats, _gated_ffn,
                                 _head_token_loss, _proj)

__all__ = ["sandwich_block", "looped_decoder_lm", "step_stat_names"]


def step_stat_names(passes):
    """The fields of ``looped_decoder_lm``'s step counters: each pass's
    mean cross entropy, then each pass's mean exit mass."""
    return tuple("pass%d_%s" % (t, what) for what in ("loss", "exit_mass")
                 for t in range(1, passes + 1))


def sandwich_block(x, prefix, n_head, head_dim, ffn_width, rope_theta=1e6,
                   rms_eps=1e-6):
    """One sandwich-norm block over ``x`` [B, T, D] with parameters named
    ``prefix + ...``; returns the new ``x``."""
    def norm(v, name):
        return layers.rms_norm(v, rms_eps, ParamAttr(name=prefix + name))

    a = norm(x, "ln1.g")
    # the three projections go to the op as they lie, the rotation inside it
    q, k, v = (_proj(a, n_head * head_dim, prefix + "attn." + m)
               for m in "qkv")
    ctx = layers.fused_attention(q, k, v, causal=True,
                                 scale=head_dim ** -0.5, n_head=n_head,
                                 rope_theta=rope_theta)
    x = layers.elementwise_add(
        x, norm(_proj(ctx, x.shape[-1], prefix + "attn.o"), "ln2.g"))
    return layers.elementwise_add(
        x, norm(_gated_ffn(norm(x, "ln3.g"), prefix, ffn_width), "ln4.g"))


def looped_decoder_lm(tokens, labels, vocab_size, n_layer, passes, d_model,
                      n_head, head_dim, ffn_width, exit_beta=0.05,
                      rope_theta=1e6, rms_eps=1e-6):
    """The training graph over ``tokens`` / ``labels`` [B, T, 1] int64 (every
    position real, one document a row): ``n_layer`` sandwich-norm blocks
    run ``passes`` times.  Returns ``(loss, stats)``: the exit-gated loss
    [1] and a ``[2 * passes]`` float32 variable a caller fetches WITH the
    loss — the passes' mean cross entropy, then their mean exit mass —
    under ``step_stat_names(passes)`` (``Program.step_stats``)."""
    x = layers.embedding(tokens, size=[vocab_size, d_model],
                         param_attr=_attr("tok_emb"))
    states, losses = [], []
    for t in range(1, passes + 1):
        for i in range(n_layer):
            with unique_name.guard("p%d.l%d." % (t, i)):
                # the application's own name for its input, so that the
                # gradients of its first norm and first residual add are
                # named after THIS pass and block, not after the
                # application before it (an assign is no device work)
                x = sandwich_block(layers.assign(x), "l%d." % i, n_head,
                                   head_dim, ffn_width, rope_theta, rms_eps)
        with unique_name.guard("p%d." % t):
            x = layers.rms_norm(x, rms_eps, ParamAttr(name="ln_f.g"))
            states.append(x)
            losses.append(_head_token_loss(x, labels, vocab_size))
    loss, stats = layers.exit_gate_loss(
        states, losses, exit_beta, param_attr=_attr("gate.w"),
        bias_attr=ParamAttr(name="gate.b",
                            initializer=ConstantInitializer(0.0)))
    return loss, _declare_step_stats(loss, stats, step_stat_names(passes))
