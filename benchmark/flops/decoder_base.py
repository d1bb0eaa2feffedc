"""Least bytes and required operations of one decode tick of the
decoder-only LM, from the configuration's shapes.

A tick reads every layer weight and the output projection once (float32
as served), and the keys and values of each active slot's context once.
Token and position tables are touched a row per slot and left out.  The
bound is a lower one: the context counted per slot is what the reader
can prove it holds (its prompt)."""


def weight_bytes(cfg):
    d, di, v, n = (cfg["d_model"], cfg["d_inner"], cfg["vocab_size"],
                   cfg["n_layer"])
    layer = 4 * d * d + 2 * d * di + di + d + 4 * d
    return (n * layer + d * v + v) * 4


def kv_bytes_per_token(cfg):
    return cfg["n_layer"] * 2 * cfg["d_model"] * 4


def tick_least_bytes(cfg, context_tokens):
    """``context_tokens``: total tokens of context over the active slots."""
    return weight_bytes(cfg) + context_tokens * kv_bytes_per_token(cfg)


def tick_required_flops(cfg, active, context_tokens):
    d, di, v, n = (cfg["d_model"], cfg["d_inner"], cfg["vocab_size"],
                   cfg["n_layer"])
    per_slot = n * (4 * d * d + 2 * d * di) + d * v
    return 2 * (active * per_slot + n * 2 * context_tokens * d)


def tick_floor_seconds(cfg, active, context_tokens, peaks):
    memory = tick_least_bytes(cfg, context_tokens) / peaks["hbm_bytes_per_s"]
    compute = tick_required_flops(cfg, active, context_tokens) / peaks[
        "bf16_flops"]
    return max(memory, compute), ("memory" if memory >= compute
                                  else "compute")
