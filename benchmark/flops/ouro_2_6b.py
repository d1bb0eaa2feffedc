"""Required operations and least bytes of one training step of the looped
dense decoder, from the configuration's shapes: ``num_hidden_layers``
sandwich-norm blocks applied ``total_ut_steps`` times over the same weights,
and per pass the head and the exit gate.

"Required" is what the forward and backward passes need, nothing
recomputed: every application of a block pays its products again (the
weights are shared, the work is not), attention over ALL causal (query,
key) pairs with keys and values ``head_dim`` wide, the head once a pass,
backward = 2x forward (every matrix is trained).  A weight is READ by every
application but updated once: the least bytes count it, its summed
gradient and Adam's moments once.
"""

from benchmark.flops.keye_vl2_30b_a3b import kernel_floor_seconds  # noqa: F401


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def passes(cfg):
    return cfg["total_ut_steps"]


def applications(cfg):
    """Applications of a block a step: every layer, every pass."""
    return passes(cfg) * cfg["num_hidden_layers"]


def block_params(cfg):
    d, hd = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"]
    return 4 * d * hd + 3 * d * cfg["intermediate_size"]


def attention_flops(cfg, rows, seq):
    """Forward + backward FLOPs of ONE application's attention kernels
    over all causal pairs: QK and PV over ``head_dim``, and their four
    gradient products."""
    return 3 * 2 * rows * causal_pairs(seq) * cfg["num_attention_heads"] \
        * 2 * cfg["head_dim"]


def attention_least_bytes(cfg, rows, seq, itemsize=2):
    """Bytes ONE application's attention moves at least, forward +
    backward: Q, K, V, O and their four gradients once each, in the
    products' precision."""
    return 2 * rows * seq * cfg["num_attention_heads"] * 4 \
        * cfg["head_dim"] * itemsize


def pass_flops(cfg, rows, seq):
    """FLOPs ONE pass requires, forward + backward: the stack's products
    and attention, the head, the gate's product."""
    d = cfg["hidden_size"]
    per_token = cfg["num_hidden_layers"] * block_params(cfg) \
        + d * cfg["vocab_size"] + d
    return 3 * 2 * rows * seq * per_token \
        + cfg["num_hidden_layers"] * attention_flops(cfg, rows, seq)


def required_flops(cfg, rows, seq):
    """FLOPs one step requires (the last pass's gate enters no loss)."""
    return passes(cfg) * pass_flops(cfg, rows, seq) \
        - 3 * 2 * rows * seq * cfg["hidden_size"]


def trainable_params(cfg):
    d = cfg["hidden_size"]
    return (2 * cfg["vocab_size"] * d
            + cfg["num_hidden_layers"] * (block_params(cfg) + 4 * d)
            + d + d + 1)


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
