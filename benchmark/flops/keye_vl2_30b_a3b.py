"""Required operations and least bytes of one training step of the
sparse-attention mixture-of-experts decoder, from the configuration's
shapes and the step's own counters.

"Required" is what the forward and backward passes need, nothing
recomputed: attention over the SELECTED (query, key) pairs only, the
experts over the token-expert pairs COMPUTED here (the counter the step
reports), backward = 2x forward for everything that is trained; the frozen
indexer has no backward at all.  So a kernel that skips unselected blocks,
or a grouped product that pads nothing, can reach 100% and none can pass it.
"""


def selected_pairs(seq, topk):
    """(query, key) pairs a causal row of ``seq`` positions selects."""
    return sum(min(t + 1, topk) for t in range(seq))


def _sizes(cfg):
    sa = cfg["sa_config"]
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            sa["indexer_num_heads"], sa["indexer_head_dim"],
            cfg["moe_intermediate_size"])


def attention_flops(cfg, pairs):
    """Forward + backward FLOPs of ONE layer's attention over ``pairs``
    selected (query, key) pairs: QK and PV forward (2 products), their four
    gradient products backward."""
    _, h, _, dh, _, _, _ = _sizes(cfg)
    return 3 * 2 * 2 * pairs * h * dh


def attention_least_bytes(cfg, rows, seq, itemsize=2):
    """Bytes ONE layer's attention moves at least, forward + backward: Q,
    K, V, O read or written once each way and dQ, dK, dV, dO once, in the
    products' precision, and the packed selection read twice."""
    _, h, hk, dh, _, _, _ = _sizes(cfg)
    qo = rows * seq * h * dh * itemsize
    kv = rows * seq * hk * dh * itemsize
    return 2 * (2 * qo + 2 * kv) + 2 * rows * seq * seq // 8


def expert_flops(cfg, pairs):
    """Forward + backward FLOPs of the grouped products over ``pairs``
    token-expert pairs (all layers' pairs together): three products an
    expert forward, two gradient products each backward."""
    d, _, _, _, _, _, f = _sizes(cfg)
    return 3 * 2 * 3 * pairs * d * f


def expert_least_bytes(cfg, itemsize=2):
    """Bytes the grouped products of ALL layers move at least: every held
    expert matrix read once forward and once backward in the products'
    precision, its float32 gradient written once."""
    d, _, _, _, _, _, f = _sizes(cfg)
    mats = cfg["num_hidden_layers"] * cfg["num_local_experts"] * 3 * d * f
    return mats * (2 * itemsize + 4)


def trainable_params(cfg):
    d, h, hk, dh, _, _, f = _sizes(cfg)
    layer = (d * h * dh + 2 * d * hk * dh + h * dh * d + 2 * dh + 2 * d
             + d * cfg["num_experts"] + cfg["num_local_experts"] * 3 * d * f)
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d


def frozen_params(cfg):
    d, _, _, _, hi, di, _ = _sizes(cfg)
    return cfg["num_hidden_layers"] * (d * hi * di + d * di + 2 * di + d * hi)


def required_flops(cfg, rows, seq, expert_pairs):
    """FLOPs one step requires: ``expert_pairs`` token-expert pairs over
    all layers (the counter's, or ``expected_expert_pairs``)."""
    d, h, hk, dh, hi, di, _ = _sizes(cfg)
    n, tok = cfg["num_hidden_layers"], rows * seq
    proj = tok * (d * h * dh + 2 * d * hk * dh + h * dh * d
                  + d * cfg["num_experts"])
    index = tok * d * (hi * di + di + hi) \
        + rows * (seq * (seq + 1) // 2) * hi * di            # forward only
    head = tok * d * cfg["vocab_size"]
    pairs = rows * selected_pairs(seq, cfg["sa_config"]["topk"])
    return (3 * 2 * (n * proj + head) + 2 * n * index
            + n * attention_flops(cfg, pairs)
            + expert_flops(cfg, expert_pairs))


def expected_expert_pairs(cfg, rows, seq):
    """Pairs over all layers under uniform routing."""
    return (cfg["num_hidden_layers"] * rows * seq
            * cfg["num_experts_per_tok"] * cfg["num_local_experts"]
            // cfg["num_experts"])


def least_bytes(cfg):
    """Bytes a step moves at least: float32 parameters read and written
    once, Adam's two moments read and written once; the frozen indexer
    read once."""
    return trainable_params(cfg) * 4 * 6 + frozen_params(cfg) * 4


def step_floor_seconds(cfg, rows, seq, expert_pairs, peaks, chips=1):
    """(least seconds one step can take on ``chips`` chips, which bound
    sets it)."""
    compute = required_flops(cfg, rows, seq, expert_pairs) / (
        chips * peaks["bf16_flops"])
    memory = least_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")


def kernel_floor_seconds(flops, nbytes, peaks):
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
