"""The plain reference of ``joyai_llm_flash``: JoyAI-LLM-Flash (config.json at
huggingface.co/jdopensource) in straightforward ``jax.numpy`` float32 —
pre-norm blocks of latent attention, a leading dense gated FFN, then
sigmoid-routed SiLU-gated experts with a selection-only bias and a shared
expert, and the multi-token-prediction module — with its two losses, their
gradients and Adam.

It imports nothing of ``paddle_tpu`` and takes nothing the program made:
weights come from :mod:`benchmark.weights` (seeded).  The products' one
switch (``f32_matmul`` / ``lowp_matmul``) and the RMSNorm are those of the
other decoder's reference, imported, not written again; Adam is this file's
own, because its update gives the old state's room back (7.9 GB of float32
state leave the chip no room for a second copy).  No
kernels, no cache, no dispatch: attention is a causal softmax over whole
rows of 192-wide scores, and every held expert runs over EVERY token with a
routing weight that is zero where the token was not routed to it.  Only to
fit the chip, query rows (and the head's rows) are taken in blocks of
``block_rows``, and blocks, experts and layers are rematerialised in the
backward pass; neither changes a number.

The layer, for ``x`` [T, D] (one document a row):

1. ``h = rms(x; g1)``; ``cq = rms(h Wqa; gqa)``, ``q = cq Wqb`` [T, H, 192]
   = ``[q_nope 128 | q_rope 64]``; ``[ckv 512 | kr 64] = h Wkva``, ``ckv =
   rms(ckv; gkva)``, ``[k_nope 128 | v 128] = ckv Wkvb`` a head.  Rotary on
   ``q_rope`` of every head and on ``kr`` — ONE key part all heads share —
   over its 64 dimensions in interleaved pairs ``(2j, 2j + 1)``, theta
   3.2e7.  ``k_i = [k_nope_i | rope(kr)]``.
2. ``o[t, i] = softmax_{s <= t}(q[t, i] . k[s, i] * 192^-0.5) v[s, i]``;
   ``x += concat(o) Wo``.
3. ``h2 = rms(x; g2)``.  A dense layer: ``x += (silu(h2 Wg) * (h2 Wu))
   Wd``.  An expert layer: ``s = sigmoid(h2 Wr)`` over ALL experts; ``E_t``
   = the ``k`` largest of ``s + b`` (``b`` is frozen: no gradient, no Adam
   state); ``c = scale * s / (sum_{E_t} s + 1e-20)``; ``x += sum_{e in
   E_t, e held} c[t, e] E_e(h2) + E_shared(h2)`` — the share ``(held,
   first)`` says which experts are held; the others' part is left out.

After the last layer ``x_last``: ``L_main`` = mean cross entropy of
``rms(x_last; gf) Wout`` against the next token.  The module: ``h' =
[rms(Emb(tok[t+1]); ge) | rms(x_last[t]; gh)] Weh``, one more expert layer
(prefix ``mtp.``), ``L_mtp`` = mean cross entropy of ``rms(.; gmf) Wout``
against ``tok[t+2]`` with the SAME ``Emb`` and ``Wout``.  The loss is
``L_main + mtp_loss_weight * L_mtp``.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.sparse_moe_decoder import (      # noqa: F401
    _cached, _sizes, f32_matmul, lowp_matmul, rms_norm)

NEG = -1e30


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def share_of(cfg):
    """(experts held here, the first one's number)."""
    return cfg["n_routed_experts_held"], cfg.get("first_local_expert", 0)


def _attention_spec(spec, p, cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    spec[p + "ln1.g"] = ((d,), "ones")
    spec[p + "attn.q_a"] = ((d, qr), "xavier")
    spec[p + "attn.q_a_g"] = ((qr,), "ones")
    spec[p + "attn.q_b"] = ((qr, h * (nope + rope)), "xavier")
    spec[p + "attn.kv_a"] = ((d, kvr + rope), "xavier")
    spec[p + "attn.kv_a_g"] = ((kvr,), "ones")
    spec[p + "attn.kv_b"] = ((kvr, h * (nope + dv)), "xavier")
    spec[p + "attn.o"] = ((h * dv, d), "xavier")
    spec[p + "ln2.g"] = ((d,), "ones")


def _expert_spec(spec, p, cfg):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, first = share_of(cfg)
    spec[p + "moe.router"] = ((d, cfg["n_routed_experts"]), "xavier")
    spec[p + "moe.bias"] = ((cfg["n_routed_experts"],), "zeros")
    for e in ["e%d" % n for n in range(first, first + held)] + ["shared"]:
        spec[p + "moe.%s.gate" % e] = ((d, f), "xavier")
        spec[p + "moe.%s.up" % e] = ((d, f), "xavier")
        spec[p + "moe.%s.down" % e] = ((f, d), "xavier")


def prefixes(cfg):
    """(the trunk's layers' prefixes, how many of them are dense)."""
    return (["l%d." % i for i in range(cfg["num_hidden_layers"])],
            cfg["first_k_dense_replace"])


def param_spec(cfg):
    """name -> (shape, init) in a fixed order; inits are read by
    :mod:`benchmark.weights`.  Every matrix is a 2-D leaf — the experts'
    too, one leaf an expert and matrix, under the expert's number in the
    whole layer (the program stacks the ones it holds)."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    spec = {"tok_emb": ((v, d), "embedding")}
    layers, dense = prefixes(cfg)
    for i, p in enumerate(layers):
        _attention_spec(spec, p, cfg)
        if i < dense:
            spec[p + "mlp.gate"] = ((d, f), "xavier")
            spec[p + "mlp.up"] = ((d, f), "xavier")
            spec[p + "mlp.down"] = ((f, d), "xavier")
        else:
            _expert_spec(spec, p, cfg)
    spec["ln_f.g"] = ((d,), "ones")
    spec["out_w"] = ((d, v), "xavier")
    spec["mtp.enorm.g"] = ((d,), "ones")
    spec["mtp.hnorm.g"] = ((d,), "ones")
    spec["mtp.eh_proj"] = ((2 * d, d), "xavier")
    _attention_spec(spec, "mtp.", cfg)
    _expert_spec(spec, "mtp.", cfg)
    spec["mtp.ln_f.g"] = ((d,), "ones")
    return spec


def frozen(name):
    """The routers' correction biases: no gradient, no Adam state."""
    return name.endswith("moe.bias")


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def rotary_pairs(x, theta):
    """``x`` [T, ..., D]: position = row; frequency j turns the pair
    ``(x[2j], x[2j + 1])`` in place."""
    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
             ).reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                      b * jnp.cos(angle) + a * jnp.sin(angle)],
                     -1).reshape(x.shape)


def attention(p, pre, h, cfg, block_rows, mm):
    """The heads' outputs [T, H * Dv], before Wo."""
    t = h.shape[0]
    nh, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    cq = rms_norm(mm(h, p[pre + "attn.q_a"]), p[pre + "attn.q_a_g"], eps)
    q = mm(cq, p[pre + "attn.q_b"]).reshape(t, nh, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotary_pairs(q[..., nope:], theta)],
                        -1)
    kva = mm(h, p[pre + "attn.kv_a"])
    ckv = rms_norm(kva[:, :kvr], p[pre + "attn.kv_a_g"], eps)
    kr = rotary_pairs(kva[:, kvr:], theta)                   # [T, rope]
    kv = mm(ckv, p[pre + "attn.kv_b"]).reshape(t, nh, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kr[:, None, :], (t, nh, rope))], -1)
    kh = k.transpose(1, 2, 0)                                # [H, Dk, T]
    vh = kv[..., nope:].transpose(1, 0, 2)                   # [H, T, Dv]
    scale = (nope + rope) ** -0.5

    def block(args):
        row0, qb = args
        rows = row0 + jnp.arange(qb.shape[0])
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        s = mm(qb.transpose(1, 0, 2), kh) * scale            # [H, R, T]
        pr = jax.nn.softmax(jnp.where(causal[None], s, NEG), -1)
        return mm(pr, vh).transpose(1, 0, 2).reshape(qb.shape[0], nh * dv)
    r = min(block_rows, t)
    n = t // r
    out = jax.lax.map(jax.checkpoint(block), (
        jnp.arange(n) * r, q.reshape(n, r, nh, nope + rope)))
    return out.reshape(t, nh * dv)


def gated_ffn(h2, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(h2, wg)) * mm(h2, wu), wd)


def route(p, pre, h2, cfg, mm):
    """(expert ids [T, k], weights [T, k]: the unbiased scores renormalised
    over the k, times the scaling factor)."""
    s = jax.nn.sigmoid(mm(h2, p[pre + "moe.router"]))
    _, idx = jax.lax.top_k(
        s + jax.lax.stop_gradient(p[pre + "moe.bias"]),
        cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, -1)
    return idx, cfg["routed_scaling_factor"] * top / (
        jnp.sum(top, -1, keepdims=True) + 1e-20)


def experts(p, pre, h2, cfg, share, mm, shared=True):
    """(the held experts' part of the layer's result and, with ``shared``,
    the shared expert's, [T, D]; the token-expert pairs routed to the held
    experts)."""
    held, first = share
    idx, c = route(p, pre, h2, cfg, mm)
    pairs = jnp.sum((idx >= first) & (idx < first + held)).astype(
        jnp.float32)
    mats = [jnp.stack([p[pre + "moe.e%d.%s" % (e, m)]
                       for e in range(first, first + held)])
            for m in ("gate", "up", "down")]

    def one(y, em):
        e, wg, wu, wd = em
        ce = jnp.sum(jnp.where(idx == e, c, 0.0), -1)        # 0: not routed
        return y + ce[:, None] * gated_ffn(h2, wg, wu, wd, mm), None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h2),
                        (first + jnp.arange(held), *mats))
    if shared:
        y = y + gated_ffn(h2, *(p[pre + "moe.shared." + m]
                                for m in ("gate", "up", "down")), mm)
    return y, pairs


def layer(p, pre, x, cfg, share, block_rows, mm=f32_matmul, dense=False,
          shared=True):
    """One block over ``x`` [T, D]: dense, or with the expert share
    ``(held, first)``.  Returns (x, pairs routed to the held experts)."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p[pre + "ln1.g"], eps)
    x = x + mm(attention(p, pre, h, cfg, block_rows, mm), p[pre + "attn.o"])
    h2 = rms_norm(x, p[pre + "ln2.g"], eps)
    if dense:
        return x + gated_ffn(h2, p[pre + "mlp.gate"], p[pre + "mlp.up"],
                             p[pre + "mlp.down"], mm), jnp.float32(0.0)
    y, pairs = experts(p, pre, h2, cfg, share, mm, shared)
    return x + y, pairs


# ---------------------------------------------------------------------------
# losses, gradient, Adam
# ---------------------------------------------------------------------------

def _head_loss_sum(p, x, gain, labels, cfg, block_rows, mm):
    x = rms_norm(x, p[gain], cfg["rms_norm_eps"])

    def rows_loss(xl):
        logp = jax.nn.log_softmax(mm(xl[0], p["out_w"]), -1)
        return -jnp.sum(jnp.take_along_axis(logp, xl[1][:, None], -1))
    r = min(block_rows, x.shape[0])
    return jnp.sum(jax.lax.map(jax.checkpoint(rows_loss), (
        x.reshape(-1, r, x.shape[1]), labels.reshape(-1, r))))


def loss_sums(p, tokens, labels, labels2, cfg, block_rows, mm=f32_matmul):
    """(sum of the next-token losses, sum of the module's next-but-one
    losses, pairs routed to the held experts over all expert layers) of one
    document ``tokens`` [T]."""
    share, eps = share_of(cfg), cfg["rms_norm_eps"]
    x = p["tok_emb"][tokens]
    layers, dense = prefixes(cfg)

    def block(pre, is_dense):
        return jax.checkpoint(lambda p, x: layer(
            p, pre, x, cfg, share, block_rows, mm, dense=is_dense))
    pairs = jnp.float32(0.0)
    for i, pre in enumerate(layers):
        x, n = block(pre, i < dense)(p, x)
        pairs = pairs + n
    main = _head_loss_sum(p, x, "ln_f.g", labels, cfg, block_rows, mm)
    joined = jnp.concatenate([
        rms_norm(p["tok_emb"][labels], p["mtp.enorm.g"], eps),
        rms_norm(x, p["mtp.hnorm.g"], eps)], -1)
    y, n = block("mtp.", False)(p, mm(joined, p["mtp.eh_proj"]))
    return main, _head_loss_sum(p, y, "mtp.ln_f.g", labels2, cfg, block_rows,
                                mm), pairs + n


def loss_and_grad(p, batch, cfg, block_rows, mm=f32_matmul):
    """(``L_main + w L_mtp``, ``L_mtp``, token-expert pairs routed to the
    held experts, the gradient of the first with respect to the trainable
    leaves): means over the batch's positions, one document at a time."""
    rows, t = batch["tok"].shape
    weight = cfg["mtp_loss_weight"]

    def make():
        def doc_loss(train, rest, tok, lbl, lbl2):
            main, mtp, pairs = loss_sums({**train, **rest}, tok, lbl, lbl2,
                                         cfg, block_rows, mm)
            mtp = mtp / (rows * t)
            return main / (rows * t) + weight * mtp, jnp.stack([mtp, pairs])

        def step(train, rest, tok, lbl, lbl2, loss, aux, grad):
            (l, a), g = jax.value_and_grad(doc_loss, has_aux=True)(
                train, rest, tok, lbl, lbl2)
            return loss + l, aux + a, jax.tree.map(jnp.add, grad, g)
        return jax.jit(step, donate_argnums=(7,))
    step = _cached(("latent_grad", _sizes(cfg), rows, t, mm), make)
    train = {n: v for n, v in p.items() if not frozen(n)}
    rest = {n: v for n, v in p.items() if frozen(n)}
    loss, aux = jnp.zeros((), jnp.float32), jnp.zeros((2,), jnp.float32)
    grad = jax.tree.map(jnp.zeros_like, train)
    for r in range(rows):
        loss, aux, grad = step(train, rest, batch["tok"][r], batch["lbl"][r],
                               batch["lbl2"][r], loss, aux, grad)
    return loss, aux[0], aux[1], grad


def adam_init(p):
    train = {n: v for n, v in p.items() if not frozen(n)}
    return {"m": jax.tree.map(jnp.zeros_like, train),
            "v": jax.tree.map(jnp.zeros_like, train), "t": 0}


def adam_step(p, grad, state, cfg):
    """Adam at a constant rate, epsilon outside the bias correction (as
    the program's ``adam`` op); frozen leaves pass through.  The
    gradient's and the old moments' buffers are given up to the results."""
    b1, b2, eps = cfg["adam_beta1"], cfg["adam_beta2"], cfg["adam_epsilon"]
    t = state["t"] + 1
    lr_t = cfg["learning_rate"] * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)

    def make():
        def upd(p, g, m, v, lr_t):
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            p = jax.tree.map(
                lambda w, a, b: w - lr_t * a / (jnp.sqrt(b) + eps), p, m, v)
            return p, m, v
        return jax.jit(upd, donate_argnums=(1, 2, 3))
    upd = _cached(("latent_adam", b1, b2, eps), make)
    train = {n: p[n] for n in grad}
    train, m, v = upd(train, grad, state["m"], state["v"], jnp.float32(lr_t))
    return {**p, **train}, {"m": m, "v": v, "t": t}
