"""The plain reference of ``ouro_2_6b``: Ouro-2.6B (config.json at
huggingface.co/ByteDance; arXiv:2510.25741, "Scaling Latent Reasoning via
Looped Language Models") in straightforward ``jax.numpy`` float32 — one
stack of sandwich-norm blocks run ``total_ut_steps`` times over the same
weights, an exit gate after each pass and the expected loss over it — with
its gradients and Adam.

It imports nothing of ``paddle_tpu`` and takes nothing the program made:
weights come from :mod:`benchmark.weights` (seeded).  The products' one
switch (``f32_matmul`` / ``lowp_matmul``), the RMSNorm and the rotate-half
rotary are those of the other decoders' reference, and Adam the latent
decoder's, imported, not written again.  No kernels, no cache: attention is
a causal softmax over whole rows of scores.  Only to fit the chip, query
rows (and the head's rows) are taken in blocks of ``block_rows``, and
blocks of rows and every application of a layer are rematerialised in the
backward pass; neither changes a number.  The passes are a ``lax.scan``
and the layers of a pass another (the model IS one stack's text run
several times): sixteen unrolled applications made an 810 MB executable,
too large for the chip machine's compile cache, and the reference compiled
for 130 s in every run.

The block ``i``, for ``x`` [T, D] (one document a row), no biases:

1. ``a = rms(x; g1_i)``; ``q, k, v = a Wq, a Wk, a Wv`` [T, H, Dh]; rotary
   on ``q``, ``k`` over all Dh dimensions (rotate-half, theta 1e6, no
   scaling); ``o[t, h] = softmax_{s <= t}(q[t, h] . k[s, h] Dh^-0.5) v[s,
   h]``; ``x += rms(concat(o) Wo; g2_i)``.
2. ``m = rms(x; g3_i)``; ``x += rms((silu(m Wg) * (m Wu)) Wd; g4_i)``.

The loop: ``h_0 = Emb(tok)``; for ``t = 1 .. P``: ``h_t = rms(Stack(h_{t-1});
gf)`` (the same blocks, the same final norm; the normed state goes on),
``CE_t[n]`` = cross entropy of ``h_t[n] Wout`` against ``tok[n + 1]``,
``lam_t[n] = sigmoid(h_t[n] . wg + bg)``.  A token's exit distribution:
``p_1 = lam_1``, ``p_t = lam_t prod_{j<t} (1 - lam_j)``, ``p_P =
prod_{j<P} (1 - lam_j)``.  Its loss: ``sum_t p_t CE_t - beta H(p)``,
``H(p) = -sum_t p_t log p_t``; the step minimises the mean over tokens.

Departures from the published description (as recalled: no network here):
the loss is the paper's stage-one objective with a uniform prior over exit
steps, written with its entropy term (the KL to a uniform prior is ``log P -
H(p)``; the constant is left out); ``early_exit_threshold`` is inference's
and enters nothing.  The gate's product goes through ``f32_matmul`` in the
control too: the configuration states the gate in float32.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.latent_moe_decoder import (      # noqa: F401
    adam_init, adam_step)
from benchmark.reference.sparse_moe_decoder import (      # noqa: F401
    NEG, _cached, _sizes, f32_matmul, lowp_matmul, rms_norm, rotary)


def param_spec(cfg):
    """name -> (shape, init) in a fixed order; inits are read by
    :mod:`benchmark.weights`."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    spec = {"tok_emb": ((v, d), "embedding")}
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        for g in ("ln1.g", "ln2.g", "ln3.g", "ln4.g"):
            spec[p + g] = ((d,), "ones")
        for m in "qkv":
            spec[p + "attn." + m] = ((d, hd), "xavier")
        spec[p + "attn.o"] = ((hd, d), "xavier")
        spec[p + "mlp.gate"] = ((d, f), "xavier")
        spec[p + "mlp.up"] = ((d, f), "xavier")
        spec[p + "mlp.down"] = ((f, d), "xavier")
    spec["ln_f.g"] = ((d,), "ones")
    spec["out_w"] = ((d, v), "xavier")
    spec["gate.w"] = ((d, 1), "xavier")
    spec["gate.b"] = ((1,), "zeros")
    return spec


def attention(p, pre, a, cfg, block_rows, mm):
    """The heads' outputs [T, H * Dh], before Wo."""
    t = a.shape[0]
    nh, dh = cfg["num_attention_heads"], cfg["head_dim"]
    theta = float(cfg["rope_theta"])
    q, k, v = (mm(a, p[pre + "attn." + m]).reshape(t, nh, dh) for m in "qkv")
    q, k = rotary(q, theta), rotary(k, theta)
    kh = k.transpose(1, 2, 0)                                # [H, Dh, T]
    vh = v.transpose(1, 0, 2)                                # [H, T, Dh]

    def block(args):
        row0, qb = args
        rows = row0 + jnp.arange(qb.shape[0])
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        s = mm(qb.transpose(1, 0, 2), kh) * dh ** -0.5       # [H, R, T]
        pr = jax.nn.softmax(jnp.where(causal[None], s, NEG), -1)
        return mm(pr, vh).transpose(1, 0, 2).reshape(qb.shape[0], nh * dh)
    r = min(block_rows, t)
    out = jax.lax.map(jax.checkpoint(block), (
        jnp.arange(t // r) * r, q.reshape(t // r, r, nh, dh)))
    return out.reshape(t, nh * dh)


def layer(p, pre, x, cfg, block_rows, mm=f32_matmul):
    """One sandwich-norm block over ``x`` [T, D]."""
    eps = cfg["rms_norm_eps"]
    a = rms_norm(x, p[pre + "ln1.g"], eps)
    x = x + rms_norm(mm(attention(p, pre, a, cfg, block_rows, mm),
                        p[pre + "attn.o"]), p[pre + "ln2.g"], eps)
    m = rms_norm(x, p[pre + "ln3.g"], eps)
    y = mm(jax.nn.silu(mm(m, p[pre + "mlp.gate"])) * mm(m, p[pre + "mlp.up"]),
           p[pre + "mlp.down"])
    return x + rms_norm(y, p[pre + "ln4.g"], eps)


def token_losses(p, h, labels, block_rows, mm):
    """Each token's cross entropy [T] of the normed state ``h``."""
    def rows_loss(xl):
        logp = jax.nn.log_softmax(mm(xl[0], p["out_w"]), -1)
        return -jnp.take_along_axis(logp, xl[1][:, None], -1)[:, 0]
    r = min(block_rows, h.shape[0])
    return jax.lax.map(jax.checkpoint(rows_loss), (
        h.reshape(-1, r, h.shape[1]), labels.reshape(-1, r))).reshape(-1)


def exit_distribution(lam):
    """``lam`` [P, T], the passes' gates -> ``p`` [P, T]: the last pass
    takes what the gates before it left (its own gate enters nothing)."""
    p, rest = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * rest)
        rest = rest * (1.0 - lam[t])
    return jnp.stack(p + [rest])


def stack_layers(p, cfg):
    """The layers' leaves ``l<i>.<name>`` stacked as ``<name>`` ``[L, ..]``:
    what a scan over the layers takes."""
    return {n[len("l0."):]: jnp.stack([p["l%d.%s" % (i, n[len("l0."):])]
                                       for i in range(
                                           cfg["num_hidden_layers"])])
            for n in p if n.startswith("l0.")}


def doc_sums(p, tokens, labels, cfg, block_rows, mm=f32_matmul, use=None):
    """Over one document ``tokens`` [T]: (sum of the tokens' losses, the
    passes' summed cross entropy [P], the passes' summed exit mass [P]).
    The passes are a ``lax.scan`` over the state, and the layers of a pass
    one inside it: one block's text, run L x P times.  ``use`` (a tuple of pass numbers from 1) says in which passes
    THIS ``p`` is applied; the other passes run on ``stop_gradient(p)`` —
    the tests take a weight's gradient one pass at a time so."""
    eps, n_pass = cfg["rms_norm_eps"], cfg["total_ut_steps"]
    used = jnp.asarray([use is None or t in use
                        for t in range(1, n_pass + 1)])

    def weights(flag):
        if use is None:
            return p
        return jax.tree.map(lambda a: jnp.where(
            flag, a, jax.lax.stop_gradient(a)), p)

    def one_layer(x, lw):
        return layer(lw, "", x, cfg, block_rows, mm), None

    def one_pass(h, flag):
        w = weights(flag)
        h, _ = jax.lax.scan(jax.checkpoint(one_layer), h, stack_layers(w, cfg))
        h = rms_norm(h, w["ln_f.g"], eps)
        lam = jax.nn.sigmoid(f32_matmul(h, w["gate.w"])[:, 0]
                             + w["gate.b"][0])
        return h, (token_losses(w, h, labels, block_rows, mm), lam)
    h0 = weights(used[0])["tok_emb"][tokens]   # the embedding is pass 1's
    _, (ce, lam) = jax.lax.scan(one_pass, h0, used)
    prob = exit_distribution(lam)
    entropy = -jnp.sum(prob * jnp.log(prob), 0)
    loss = jnp.sum(jnp.sum(prob * ce, 0) - cfg["exit_beta"] * entropy)
    return loss, jnp.sum(ce, 1), jnp.sum(prob, 1)


def loss_and_grad(p, batch, cfg, block_rows, mm=f32_matmul, use=None):
    """(the loss, the passes' mean cross entropy [P], their mean exit mass
    [P], the gradient of the loss): means over the batch's positions, one
    document at a time.  ``use`` as ``doc_sums``': the gradient through
    those passes' uses of the weights alone."""
    rows, t = batch["tok"].shape
    n_pass = cfg["total_ut_steps"]

    def make():
        def doc_loss(p, tok, lbl):
            loss, ce, mass = doc_sums(p, tok, lbl, cfg, block_rows, mm, use)
            return loss / (rows * t), jnp.concatenate([ce, mass]) / (rows * t)

        def step(p, tok, lbl, loss, aux, grad):
            (l, a), g = jax.value_and_grad(doc_loss, has_aux=True)(p, tok, lbl)
            return loss + l, aux + a, jax.tree.map(jnp.add, grad, g)
        return jax.jit(step, donate_argnums=(5,))
    step = _cached(("looped_grad", _sizes(cfg), rows, t, mm, use), make)
    loss = jnp.zeros((), jnp.float32)
    aux = jnp.zeros((2 * n_pass,), jnp.float32)
    grad = jax.tree.map(jnp.zeros_like, p)
    for r in range(rows):
        loss, aux, grad = step(p, batch["tok"][r], batch["lbl"][r], loss, aux,
                               grad)
    return loss, aux[:n_pass], aux[n_pass:], grad
