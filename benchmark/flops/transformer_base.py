"""Required operations and least bytes of one training step of the
encoder-decoder Transformer, from the configuration's shapes.

"Required" is what the forward and backward passes need for the real
(non-padding) tokens: matrix products only, attention over valid keys
(causal self-attention over the lower triangle), backward = 2x forward,
nothing recomputed.  "Padded" is the same count with every row at the
padded length and full (unmasked) score matrices — what a dense step at
that shape executes at least; 6.06e12 at 256 x 64, the figure the ISSUE
quotes.  Both are lower bounds of what the chip executes, so a share of
the roofline taken from them cannot pass 100%.
"""


def _macs_per_row(cfg, s, t, causal_half):
    d, di, v, n = (cfg["d_model"], cfg["d_inner"], cfg["vocab_size"],
                   cfg["n_layer"])
    enc = s * n * (4 * d * d + 2 * d * di) + n * 2 * s * s * d
    self_scores = t * (t + 1) // 2 if causal_half else t * t
    dec = (t * n * (4 * d * d + 2 * d * d + 2 * d * di)   # self qkvo, cross qo
           + s * n * 2 * d * d                            # cross k, v
           + n * 2 * self_scores * d + n * 2 * t * s * d)
    return enc + dec + t * d * v


def required_flops(cfg, src_lens, tgt_lens):
    """FLOPs one step requires for rows of these real lengths."""
    macs = sum(_macs_per_row(cfg, int(s), int(t), True)
               for s, t in zip(src_lens, tgt_lens))
    return 3 * 2 * macs


def padded_flops(cfg, rows, seq):
    return 3 * 2 * rows * _macs_per_row(cfg, seq, seq, False)


def param_count(cfg):
    d, di, v, n = (cfg["d_model"], cfg["d_inner"], cfg["vocab_size"],
                   cfg["n_layer"])
    ffn = 2 * d * di + di + d
    enc = 4 * d * d + ffn + 4 * d
    dec = 8 * d * d + ffn + 6 * d
    return 2 * v * d + n * (enc + dec) + d * v + v


def least_bytes(cfg):
    """Bytes a step moves at least: float32 parameters read and written
    once, Adam's two moments read and written once (activations can in
    principle stay on chip)."""
    return param_count(cfg) * 4 * 6


def step_floor_seconds(cfg, src_lens, tgt_lens, peaks, chips=1):
    """(least seconds one step can take on ``chips`` chips, which bound
    sets it)."""
    compute = required_flops(cfg, src_lens, tgt_lens) / (
        chips * peaks["bf16_flops"])
    memory = least_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")
