"""Required operations and least bytes of one training step of the
decoder-hybrid-decoder: Mamba layers, differential attention under a window
and over all causal pairs, gated memory units, a tied head.

"Required" is what the forward and backward passes need, nothing
recomputed: every matrix's products (backward = 2x forward; the tied table
is used once as a product, by the head — the lookup is no product);
attention over the pairs that COUNT — a window layer's ``t - window < s <=
t``, a full or cross layer's ``s <= t`` — with keys ``head_dim`` wide and
values twice that, BOTH softmax maps of a differential pair; the
recurrence's elementwise operations.  So a kernel that skips blocks outside
the window can reach 100% and none can pass it.
"""

from benchmark.flops.keye_vl2_30b_a3b import kernel_floor_seconds  # noqa: F401


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def window_pairs(seq, window):
    """(query, key) pairs with ``t - window < s <= t``."""
    w = min(window, seq)
    return causal_pairs(w) + (seq - w) * w


def _sizes(cfg):
    d = cfg["hidden_size"]
    return (d, cfg["mamba_expand"] * d, cfg["mamba_d_state"],
            cfg["mamba_dt_rank"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], d // cfg["num_attention_heads"],
            cfg["intermediate_size"])


def mixer_matrix_params(cfg, kind):
    d, e, n, r, nh, nkv, dh, f = _sizes(cfg)
    return {"mamba": d * 2 * e + e * (r + 2 * n) + r * e + e * d,
            "window": d * (nh + 2 * nkv) * dh + nh * dh * d,
            "full": d * (nh + 2 * nkv) * dh + nh * dh * d,
            "cross": 2 * d * nh * dh,
            "gmu": 2 * d * e}[kind]


def matrix_params(cfg):
    """Parameters that are operands of a product a step: every layer's
    mixer and FFN matrices, and the tied table once (the head)."""
    d, e, n, r, nh, nkv, dh, f = _sizes(cfg)
    return sum(mixer_matrix_params(cfg, k) + 3 * d * f
               for k in cfg["layer_kinds"]) + cfg["vocab_size"] * d


def trainable_params(cfg):
    d, e, n, r, nh, nkv, dh, f = _sizes(cfg)
    small = {"mamba": cfg["mamba_d_conv"] * e + 3 * e + e * n,
             "gmu": 0}
    return matrix_params(cfg) + sum(
        4 * d + small.get(k, 4 * dh + 2 * dh) for k in cfg["layer_kinds"]) \
        + 2 * d


def attention_pairs(cfg, seq):
    """The (query, key) pairs that count, summed over the attention layers."""
    return sum(window_pairs(seq, cfg["sliding_window"]) if k == "window"
               else causal_pairs(seq)
               for k in cfg["layer_kinds"] if k in ("window", "full", "cross"))


def attention_flops(cfg, rows, seq):
    """Forward + backward FLOPs of all the attention layers' kernels: per
    pair and query head QK over ``head_dim`` and PV over ``2 head_dim``
    (each query head is one softmax map of a differential pair), and their
    four gradient products."""
    d, e, n, r, nh, nkv, dh, f = _sizes(cfg)
    return 3 * rows * attention_pairs(cfg, seq) * nh * (2 * dh + 2 * 2 * dh)


def attention_least_bytes(cfg, rows, seq, itemsize=2):
    """Bytes the attention kernels move at least, forward + backward: per
    layer and map Q and K (``head_dim``), V and O (twice that), and their
    four gradients, once each."""
    d, e, n, r, nh, nkv, dh, f = _sizes(cfg)
    layers = sum(k in ("window", "full", "cross") for k in cfg["layer_kinds"])
    per_map = (nh // 2) * 3 * dh + (nkv // 2) * 3 * dh  # q + o; k + v
    return layers * 2 * 2 * rows * seq * per_map * itemsize


def scan_flops(cfg, rows, seq):
    """The recurrence's elementwise operations, forward (the decay's product
    and exponential, the state's multiply-add, the input's product, the
    read-out's multiply-add: 7 a state element a step) and backward (the
    adjoint's two multiply-adds, dA's, dB's, dC's, d-delta's two, dx's: 16),
    over every Mamba layer."""
    d, e, n, r, nh, nkv, dh, f = _sizes(cfg)
    layers = sum(k == "mamba" for k in cfg["layer_kinds"])
    return layers * 23 * rows * seq * e * n


def scan_least_bytes(cfg, rows, seq):
    """What ANY implementation of the scan and its gradient must read and
    write, float32: ``c``, ``delta``, ``dy`` in and ``y``, ``dc``,
    ``d-delta`` out ([T, E] each), ``B``, ``C`` in and ``dB``, ``dC`` out
    ([T, N]), ``c`` and ``delta`` a second time for the backward, ``A`` and
    ``D`` and their gradients once."""
    d, e, n, r, nh, nkv, dh, f = _sizes(cfg)
    layers = sum(k == "mamba" for k in cfg["layer_kinds"])
    return layers * 4 * (rows * seq * (8 * e + 6 * n) + 2 * (e * n + e))


def required_flops(cfg, rows, seq):
    return 3 * 2 * rows * seq * matrix_params(cfg) \
        + attention_flops(cfg, rows, seq) + scan_flops(cfg, rows, seq)


def least_bytes(cfg):
    """Bytes a step moves at least: float32 parameters read and written
    once, Adam's two moments read and written once."""
    return trainable_params(cfg) * 4 * 6


def step_floor_seconds(cfg, rows, seq, peaks, chips=1):
    """(least seconds one step can take on ``chips`` chips, which bound
    sets it)."""
    compute = required_flops(cfg, rows, seq) / (chips * peaks["bf16_flops"])
    memory = least_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")
