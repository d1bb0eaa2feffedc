"""Required operations and least bytes of one training step of the
window / full attention mixture-of-experts decoder, from the configuration's
shapes and the step's own expert counter.

"Required" is what the forward and backward passes need, nothing
recomputed: every matrix's products (backward = 2x forward); attention over
the (query, key) pairs that COUNT — a window layer's ``t - window < s <= t``,
a full layer's ``s <= t``, worked out here from the length and the window,
not from the program's counter — a pair as ``flops/keye_vl2_30b_a3b.py``
counts one (QK and PV forward, their four gradient products backward); the
experts over the token-expert pairs COMPUTED here (the counter the step
reports).  So a kernel that skips the blocks outside the window, or a grouped
product that pads nothing, can reach 100% and none can pass it.
"""

from benchmark.flops.keye_vl2_30b_a3b import kernel_floor_seconds  # noqa: F401
from benchmark.flops.phi4_mini_flash import (              # noqa: F401
    causal_pairs, window_pairs)

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def mixers(cfg):
    """``"window"`` or ``"full"`` for each of the ``num_hidden_layers``
    leading published layers, from ``layer_types``."""
    return [KINDS[k] for k in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"])


def attention_pairs(cfg, seq):
    """The (query, key) pairs that count in one row, summed over the
    layers."""
    return sum(window_pairs(seq, cfg["sliding_window"]) if k == "window"
               else causal_pairs(seq) for k in mixers(cfg))


def attention_flops(cfg, pairs):
    """Forward + backward FLOPs of attention over ``pairs`` (query, key)
    pairs: QK and PV forward (2 products), their four gradient products
    backward, every query head."""
    _, h, _, dh, _ = _sizes(cfg)
    return 3 * 2 * 2 * pairs * h * dh


def attention_least_bytes(cfg, rows, seq, itemsize=2):
    """Bytes ONE layer's attention moves at least, forward + backward: Q,
    K, V, O read or written once each way and dQ, dK, dV, dO once, in the
    products' precision."""
    _, h, hk, dh, _ = _sizes(cfg)
    qo = rows * seq * h * dh * itemsize
    kv = rows * seq * hk * dh * itemsize
    return 2 * (2 * qo + 2 * kv)


def expert_flops(cfg, pairs):
    """Forward + backward FLOPs of the grouped products over ``pairs``
    token-expert pairs (all layers' pairs together): three products an
    expert forward, two gradient products each backward."""
    d, _, _, _, f = _sizes(cfg)
    return 3 * 2 * 3 * pairs * d * f


def expert_least_bytes(cfg, itemsize=2):
    """Bytes the grouped products of ALL layers move at least: every held
    expert matrix read once forward and once backward in the products'
    precision, its float32 gradient written once."""
    d, _, _, _, f = _sizes(cfg)
    mats = cfg["num_hidden_layers"] * cfg["num_experts_held"] * 3 * d * f
    return mats * (2 * itemsize + 4)


def layer_matrix_params(cfg):
    """q, k, v, o and the router of one layer."""
    d, h, hk, dh, _ = _sizes(cfg)
    return d * h * dh + 2 * d * hk * dh + h * dh * d + d * cfg["num_experts"]


def trainable_params(cfg):
    d, _, _, _, f = _sizes(cfg)
    layer = layer_matrix_params(cfg) + 2 * d \
        + cfg["num_experts_held"] * 3 * d * f
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d


def required_flops(cfg, rows, seq, expert_pairs):
    """FLOPs one step requires: ``expert_pairs`` token-expert pairs over
    all layers (the counter's, or ``expected_expert_pairs``)."""
    d, tok = cfg["hidden_size"], rows * seq
    products = tok * (cfg["num_hidden_layers"] * layer_matrix_params(cfg)
                      + d * cfg["vocab_size"])
    return (3 * 2 * products
            + attention_flops(cfg, rows * attention_pairs(cfg, seq))
            + expert_flops(cfg, expert_pairs))


def expected_expert_pairs(cfg, rows, seq):
    """Pairs over all layers under uniform routing."""
    return (cfg["num_hidden_layers"] * rows * seq
            * cfg["num_experts_per_tok"] * cfg["num_experts_held"]
            // cfg["num_experts"])


def least_bytes(cfg):
    """Bytes a step moves at least: float32 parameters read and written
    once, Adam's two moments read and written once."""
    return trainable_params(cfg) * 4 * 6


def step_floor_seconds(cfg, rows, seq, expert_pairs, peaks, chips=1):
    """(least seconds one step can take on ``chips`` chips, which bound
    sets it)."""
    compute = required_flops(cfg, rows, seq, expert_pairs) / (
        chips * peaks["bf16_flops"])
    memory = least_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")


def mixed_attention_floor_seconds(cfg, rows, seq, peaks):
    """The least time all the layers' attention could take, forward and
    backward: each layer's own floor (operations over the pairs that count
    under ITS mask against the matrix unit's peak, its least bytes against
    the memory's), summed."""
    return sum(kernel_floor_seconds(
        attention_flops(cfg, rows * (
            window_pairs(seq, cfg["sliding_window"]) if k == "window"
            else causal_pairs(seq))),
        attention_least_bytes(cfg, rows, seq), peaks) for k in mixers(cfg))
