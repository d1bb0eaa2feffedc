"""The plain reference of ``phi4_mini_flash``: Phi-4-mini-flash-reasoning
(config.json at huggingface.co/microsoft; arXiv:2507.06607, "Decoder-Hybrid-
Decoder Architecture for Efficient Reasoning with Long Generation": SambaY)
in straightforward ``jax.numpy`` float32 — a self-decoder of Mamba layers
and differential attention under a sliding window, one full-attention
layer, and a cross-decoder of gated memory units and cross-attention that
read ONE Mamba layer's scan output and ONE layer's keys and values — with
its gradients and Adam.

It imports nothing of ``paddle_tpu`` and takes nothing the program made:
weights come seeded from the generator.  The products' one switch
(``f32_matmul`` / ``lowp_matmul``) is the other decoders' reference's and
Adam the latent decoder's, imported, not written again.  No kernels, no
mixed precision: the recurrence is a ``lax.scan`` over time (in chunks of a
nested scan, each chunk rematerialised in the backward pass: its float32
residual is 1.3 GB otherwise), attention a masked softmax over whole rows
of scores.  Only to fit the chip, query rows (and the head's rows) are taken
in blocks of ``block_rows``, rematerialised in the backward pass; neither
changes a number.

For ``x`` [T, D] (one document), every layer ``l``: ``x += mixer_l(LN(x;
ln1))``; ``[g | u] = LN(x; ln2) W1``; ``x += (silu(g) * u) W2``.  LayerNorm
with scale and bias; no projection has a bias; no positional encoding.  The
mixers, by ``cfg["layer_kinds"]``:

* ``mamba``: ``[u | z] = h Win``; ``c_t = silu(b + sum_j w_j u_{t-3+j})``;
  ``[d | B | C] = c Wx``; ``delta = softplus(d Wdt + bdt)``; ``A =
  -exp(A_log)``; ``s_t = exp(delta_t (x) 1 * A) * s_{t-1} + (delta_t * c_t)
  (x) B_t``; ``y_t = s_t C_t + D * c_t``; out ``(y * silu(z)) Wout``.  ``y``
  is the memory ``M``.
* ``window`` / ``full``: ``[q | k | v] = h Wqkv``; query pair ``p`` = heads
  ``2p, 2p + 1`` over key/value pair ``r = p // 2`` = keys ``2r, 2r + 1`` and
  value ``[v_2r | v_2r+1]``; ``a_i = softmax_mask(q_i k_i^T / sqrt(Dh)) V``;
  ``o = (1 - lam0) rms(a_1 - lam a_2; sub.g)``; ``lam = exp(lq1 . lk1) -
  exp(lq2 . lk2) + lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)``; out
  ``concat(o) Wo``.  Mask ``t - window < s <= t`` or ``s <= t``.  The
  ``full`` layer's ``k``, ``v`` are ``K*``, ``V*``.
* ``cross``: ``q = h Wq``; the same over ``K*``, ``V*``, causal.
* ``gmu``: ``(M * silu(h Wg1)) Wg2``.

Then a final LayerNorm, logits ``x Emb^T`` (the tied table), the mean
next-token cross entropy.

``cfg["fault"]`` plants one fault (the generator's ``FAULTS``: what the
limits of ``correct`` stand against); a configuration has none.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.latent_moe_decoder import (      # noqa: F401
    adam_init, adam_step)
from benchmark.reference.sparse_moe_decoder import (      # noqa: F401
    NEG, _cached, _sizes, f32_matmul, lowp_matmul, rms_norm)

SCAN_CHUNK = 64


def sizes(cfg):
    """(D, E, N, R, K, query heads, K/V heads, Dh, FFN width)."""
    d = cfg["hidden_size"]
    return (d, cfg["mamba_expand"] * d, cfg["mamba_d_state"],
            cfg["mamba_dt_rank"], cfg["mamba_d_conv"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            d // cfg["num_attention_heads"], cfg["intermediate_size"])


def layer_numbers(cfg):
    first = cfg["first_layer"]
    return range(first, first + len(cfg["layer_kinds"]))


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def param_spec(cfg):
    """name -> (shape, init) in a fixed order; the inits are the
    generator's (``normal`` N(0, initializer_range), ``lambda`` N(0, 0.1),
    ``a_log`` log(1..N), ``dt_bias`` the inverse softplus of a step size
    log-uniform in [1e-3, 0.1], ``ones``, ``zeros``)."""
    d, e, n, r, k, nh, nkv, dh, f = sizes(cfg)
    spec = {"tok_emb": ((cfg["vocab_size"], d), "normal")}
    for l, kind in zip(layer_numbers(cfg), cfg["layer_kinds"]):
        p = "l%d." % l
        spec[p + "ln1.g"], spec[p + "ln1.b"] = ((d,), "ones"), ((d,), "zeros")
        if kind == "mamba":
            spec[p + "ssm.in"] = ((d, 2 * e), "normal")
            spec[p + "ssm.conv.w"] = ((k, e), "normal")
            spec[p + "ssm.conv.b"] = ((e,), "zeros")
            spec[p + "ssm.x"] = ((e, r + 2 * n), "normal")
            spec[p + "ssm.dt.w"] = ((r, e), "normal")
            spec[p + "ssm.dt.b"] = ((e,), "dt_bias")
            spec[p + "ssm.A_log"] = ((e, n), "a_log")
            spec[p + "ssm.D"] = ((e,), "ones")
            spec[p + "ssm.out"] = ((e, d), "normal")
        elif kind == "gmu":
            spec[p + "gmu.in"] = ((d, e), "normal")
            spec[p + "gmu.out"] = ((e, d), "normal")
        else:
            if kind == "cross":
                spec[p + "attn.q"] = ((d, nh * dh), "normal")
            else:
                spec[p + "attn.qkv"] = ((d, (nh + 2 * nkv) * dh), "normal")
            spec[p + "attn.o"] = ((nh * dh, d), "normal")
            for v in ("lq1", "lk1", "lq2", "lk2"):
                spec[p + "attn." + v] = ((dh,), "lambda")
            spec[p + "attn.sub.g"] = ((2 * dh,), "ones")
        spec[p + "ln2.g"], spec[p + "ln2.b"] = ((d,), "ones"), ((d,), "zeros")
        spec[p + "mlp.w1"] = ((d, 2 * f), "normal")
        spec[p + "mlp.w2"] = ((f, d), "normal")
    spec["ln_f.g"], spec["ln_f.b"] = ((d,), "ones"), ((d,), "zeros")
    return spec


def layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def conv(u, w, b, ahead=0):
    """``silu(b + sum_j w[j] * u[t - (K-1) + j + ahead])``, zeros outside;
    ``ahead`` is 0 (a planted fault reads one step ahead)."""
    k, t = w.shape[0], u.shape[0]
    up = jnp.pad(u, [(k - 1 - ahead, ahead), (0, 0)])
    return jax.nn.silu(b + sum(w[j] * up[j:j + t] for j in range(k)))


def scan(delta, c, a, bm, cm, d):
    """The recurrence over ``delta``, ``c`` [T, E], ``bm``, ``cm`` [T, N]:
    (``y`` [T, E], the final state [E, N])."""
    t, e = c.shape
    pad = -(-t // SCAN_CHUNK) * SCAN_CHUNK - t

    def chunks(v):
        return jnp.pad(v, [(0, pad), (0, 0)]).reshape(-1, SCAN_CHUNK,
                                                      v.shape[1])

    def step(s, inp):
        dl, ct, bt, cmt = inp
        s = jnp.exp(dl[:, None] * a) * s + (dl * ct)[:, None] * bt[None, :]
        return s, jnp.sum(s * cmt[None, :], -1)

    def chunk(s, inp):
        return jax.lax.scan(step, s, inp)
    s, ys = jax.lax.scan(jax.checkpoint(chunk),
                         jnp.zeros((e, a.shape[1]), jnp.float32),
                         tuple(chunks(v) for v in (delta, c, bm, cm)))
    return ys.reshape(-1, e)[:t] + d * c, s


def mamba(p, pre, h, cfg, mm):
    """(the mixer's output, the memory ``y``, the final state)."""
    d, e, n, r, k, nh, nkv, dh, f = sizes(cfg)
    uz = mm(h, p[pre + "ssm.in"])
    u, z = uz[:, :e], uz[:, e:]
    c = conv(u, p[pre + "ssm.conv.w"], p[pre + "ssm.conv.b"],
             1 if cfg.get("fault") == "conv_tap_ahead" else 0)
    dbc = mm(c, p[pre + "ssm.x"])
    delta = jax.nn.softplus(mm(dbc[:, :r], p[pre + "ssm.dt.w"])
                            + p[pre + "ssm.dt.b"])
    y, s = scan(delta, c, -jnp.exp(p[pre + "ssm.A_log"]), dbc[:, r:r + n],
                dbc[:, r + n:], p[pre + "ssm.D"])
    gated = y * jax.nn.silu(z)
    memory = gated if cfg.get("fault") == "memory_after_gate" else y
    return mm(gated, p[pre + "ssm.out"]), memory, s


def differential(p, pre, layer, q, k, v, cfg, window, block_rows, mm):
    """``q`` [T, H, Dh], ``k``, ``v`` [T, Hkv, Dh] -> ([T, H * Dh] before
    Wo, lam)."""
    d, e, n, r, kw, nh, nkv, dh, f = sizes(cfg)
    t = q.shape[0]
    pairs, kv_pairs = nh // 2, nkv // 2
    g = pairs // kv_pairs
    vv = v.reshape(t, kv_pairs, 2 * dh).transpose(1, 0, 2)   # [R, T, 2Dh]
    lam0 = lambda_init(layer)
    lam = jnp.exp(jnp.sum(p[pre + "attn.lq1"] * p[pre + "attn.lk1"])) \
        - jnp.exp(jnp.sum(p[pre + "attn.lq2"] * p[pre + "attn.lk2"])) + lam0
    if cfg.get("fault") == "lambda_dropped":
        lam = lam * 0.0
    keys = [k[:, i::2].transpose(1, 2, 0) for i in (0, 1)]   # [R, Dh, T]

    def block(args):
        row0, qb = args                                      # [rows, H, Dh]
        rows = row0 + jnp.arange(qb.shape[0])
        cols = jnp.arange(t)
        valid = cols[None, :] <= rows[:, None]
        if window is not None:
            valid = valid & (rows[:, None] - cols[None, :] < window)
        out = []
        for i in (0, 1):
            qi = qb[:, i::2].reshape(-1, kv_pairs, g, dh).transpose(
                1, 2, 0, 3).reshape(kv_pairs, -1, dh)        # [R, g*rows, Dh]
            s = mm(qi, keys[i]).reshape(kv_pairs, g, -1, t) * dh ** -0.5
            pr = jax.nn.softmax(jnp.where(valid[None, None], s, NEG), -1)
            out.append(mm(pr.reshape(kv_pairs, -1, t), vv))  # [R, g*rows, 2Dh]
        o = rms_norm(out[0] - lam * out[1], p[pre + "attn.sub.g"],
                     cfg["layer_norm_eps"]) * (1.0 - lam0)
        return o.reshape(kv_pairs, g, -1, 2 * dh).transpose(
            2, 0, 1, 3).reshape(-1, nh * dh)
    rws = min(block_rows, t)
    out = jax.lax.map(jax.checkpoint(block), (
        jnp.arange(t // rws) * rws, q.reshape(t // rws, rws, nh, dh)))
    return out.reshape(t, nh * dh), lam


def layer(p, l, kind, x, shared, cfg, block_rows, mm):
    """One layer over ``x`` [T, D]; ``shared`` holds what earlier layers
    wrote for later ones (``k``, ``v``, ``memory``, ``state``, ``lams``)."""
    d, e, n, r, kw, nh, nkv, dh, f = sizes(cfg)
    pre, eps, t = "l%d." % l, cfg["layer_norm_eps"], x.shape[0]
    h = layer_norm(x, p[pre + "ln1.g"], p[pre + "ln1.b"], eps)
    if kind == "mamba":
        out, shared["memory"], shared["state"] = mamba(p, pre, h, cfg, mm)
    elif kind == "gmu":
        out = mm(shared["memory"] * jax.nn.silu(mm(h, p[pre + "gmu.in"])),
                 p[pre + "gmu.out"])
    else:
        if kind == "cross":
            q = mm(h, p[pre + "attn.q"]).reshape(t, nh, dh)
            k, v = shared["k"], shared["v"]
            if cfg.get("fault") == "cross_own_keys":
                # its OWN input through the source layer's K/V projection
                kv = mm(h, shared["w_kv"])
                k, v = (kv[:, i * nkv * dh:(i + 1) * nkv * dh].reshape(
                    t, nkv, dh) for i in (0, 1))
        else:
            qkv = mm(h, p[pre + "attn.qkv"])
            q = qkv[:, :nh * dh].reshape(t, nh, dh)
            k, v = (qkv[:, (nh + i * nkv) * dh:(nh + (i + 1) * nkv) * dh]
                    .reshape(t, nkv, dh) for i in (0, 1))
            if kind == "full":
                shared.update(k=k, v=v,
                              w_kv=p[pre + "attn.qkv"][:, nh * dh:])
        o, lam = differential(
            p, pre, l, q, k, v, cfg,
            cfg["sliding_window"] if kind == "window" else None,
            block_rows, mm)
        shared["lams"].append(lam)
        out = mm(o, p[pre + "attn.o"])
    x = x + out
    gu = mm(layer_norm(x, p[pre + "ln2.g"], p[pre + "ln2.b"], eps),
            p[pre + "mlp.w1"])
    return x + mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], p[pre + "mlp.w2"])


def doc_sums(p, tokens, labels, cfg, block_rows, mm=f32_matmul):
    """Over one document ``tokens`` [T]: (the sum of the tokens' cross
    entropy, the Mamba layer's final state [E, N], [mean square of that
    state, mean square of the memory, the attention layers' mean lambda])."""
    x = p["tok_emb"][tokens]
    shared = {"lams": []}
    for l, kind in zip(layer_numbers(cfg), cfg["layer_kinds"]):
        x = jax.checkpoint(
            lambda p, x, sh, l=l, kind=kind: (
                layer(p, l, kind, x, sh, cfg, block_rows, mm), sh))(
            p, x, shared)
        x, shared = x
    x = layer_norm(x, p["ln_f.g"], p["ln_f.b"], cfg["layer_norm_eps"])
    table = p["tok_emb"]
    if cfg.get("fault") == "head_untied":
        table = jax.lax.stop_gradient(table)

    def rows_loss(xl):
        logp = jax.nn.log_softmax(mm(xl[0], table.T), -1)
        return -jnp.sum(jnp.take_along_axis(logp, xl[1][:, None], -1))
    rws = min(block_rows, x.shape[0])
    loss = jnp.sum(jax.lax.map(jax.checkpoint(rows_loss), (
        x.reshape(-1, rws, x.shape[1]), labels.reshape(-1, rws))))
    stats = jnp.stack([jnp.mean(jnp.square(shared["state"])),
                       jnp.mean(jnp.square(shared["memory"])),
                       sum(shared["lams"]) / len(shared["lams"])])
    return loss, shared["state"], stats


def loss_and_grad(p, batch, cfg, block_rows, mm=f32_matmul):
    """(the mean loss, the documents' final states [rows, E, N], the step's
    counters [scan_state_rms, memory_rms, diff_lambda], the gradient of the
    loss), one document at a time."""
    rows, t = batch["tok"].shape

    def make():
        def doc_loss(p, tok, lbl):
            loss, state, stats = doc_sums(p, tok, lbl, cfg, block_rows, mm)
            return loss / (rows * t), (state, stats / rows)

        def step(p, tok, lbl, loss, stats, grad):
            (l, (state, st)), g = jax.value_and_grad(
                doc_loss, has_aux=True)(p, tok, lbl)
            return (loss + l, stats + st, state,
                    jax.tree.map(jnp.add, grad, g))
        return jax.jit(step, donate_argnums=(5,))
    step = _cached(("hybrid_grad", _sizes(cfg), rows, t, mm), make)
    loss = jnp.zeros((), jnp.float32)
    stats = jnp.zeros((3,), jnp.float32)
    grad = jax.tree.map(jnp.zeros_like, p)
    states = []
    for r in range(rows):
        loss, stats, state, grad = step(p, batch["tok"][r], batch["lbl"][r],
                                        loss, stats, grad)
        states.append(state)
    stats = jnp.concatenate([jnp.sqrt(stats[:2]), stats[2:]])
    return loss, jnp.stack(states), stats, grad
