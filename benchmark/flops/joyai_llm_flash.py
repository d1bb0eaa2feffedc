"""Required operations and least bytes of one training step of the
latent-attention mixture-of-experts decoder with its multi-token-prediction
module, from the configuration's shapes and the step's own counters.

"Required" is what the forward and backward passes need, nothing
recomputed: attention over ALL causal (query, key) pairs with keys
``qk_head_dim`` and values ``v_head_dim`` wide, the routed experts over the
token-expert pairs COMPUTED here (the counter the step reports), the shared
expert and every projection over every token, backward = 2x forward (every
matrix is trained; the router's bias is a vector).  The module is one more
expert block, one more 2D -> D product and a second pass through the head.
"""

from benchmark.flops.keye_vl2_30b_a3b import kernel_floor_seconds  # noqa: F401


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def blocks(cfg):
    """(dense blocks, expert blocks with the module's) of the step."""
    dense = cfg["first_k_dense_replace"]
    return dense, (cfg["num_hidden_layers"] - dense
                   + cfg["num_nextn_predict_layers"])


def attention_params(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dk, rope, dv = (cfg["qk_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    return (d * qr + qr * h * dk + d * (kvr + rope)
            + kvr * h * (dk - rope + dv) + h * dv * d)


def attention_flops(cfg, rows, seq):
    """Forward + backward FLOPs of ONE block's attention kernels over all
    causal pairs: QK over ``qk_head_dim``, PV over ``v_head_dim``, and
    their four gradient products."""
    return 3 * 2 * rows * causal_pairs(seq) * cfg["num_attention_heads"] * (
        cfg["qk_head_dim"] + cfg["v_head_dim"])


def attention_least_bytes(cfg, rows, seq, itemsize=2):
    """Bytes ONE block's attention moves at least, forward + backward: Q,
    K, V, O and their four gradients once each, in the products'
    precision."""
    return 2 * rows * seq * cfg["num_attention_heads"] * 2 * (
        cfg["qk_head_dim"] + cfg["v_head_dim"]) * itemsize


def expert_flops(cfg, pairs):
    """Forward + backward FLOPs of the grouped products over ``pairs``
    token-expert pairs (all expert blocks' pairs together)."""
    return 3 * 2 * 3 * pairs * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def expert_least_bytes(cfg, itemsize=2):
    """Bytes the grouped products of ALL expert blocks move at least:
    every held matrix read once forward and once backward in the products'
    precision, its float32 gradient written once."""
    mats = blocks(cfg)[1] * cfg["n_routed_experts_held"] * 3 \
        * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return mats * (2 * itemsize + 4)


def _per_token_params(cfg):
    """Matrix entries every token passes, all blocks, the module's
    projection and the head's two passes (the routed experts apart)."""
    d, f, fe = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    dense, expert = blocks(cfg)
    shared = cfg["n_shared_experts"] * 3 * d * fe
    return ((dense + expert) * attention_params(cfg) + dense * 3 * d * f
            + expert * (d * cfg["n_routed_experts"] + shared)
            + cfg["num_nextn_predict_layers"] * 2 * d * d
            + (1 + cfg["num_nextn_predict_layers"]) * d * cfg["vocab_size"])


def trainable_params(cfg):
    d = cfg["hidden_size"]
    dense, expert = blocks(cfg)
    gains = (dense + expert) * (2 * d + cfg["q_lora_rank"]
                                + cfg["kv_lora_rank"]) \
        + d + cfg["num_nextn_predict_layers"] * 3 * d
    routed = expert * cfg["n_routed_experts_held"] * 3 * d \
        * cfg["moe_intermediate_size"]
    # the head is ONE matrix however often it is passed; the embedding too
    return (_per_token_params(cfg) + routed + gains
            - cfg["num_nextn_predict_layers"] * d * cfg["vocab_size"]
            + cfg["vocab_size"] * d)


def frozen_params(cfg):
    return blocks(cfg)[1] * cfg["n_routed_experts"]


def required_flops(cfg, rows, seq, expert_pairs):
    """FLOPs one step requires: ``expert_pairs`` token-expert pairs over
    all expert blocks (the counter's, or ``expected_expert_pairs``)."""
    dense, expert = blocks(cfg)
    return (3 * 2 * rows * seq * _per_token_params(cfg)
            + (dense + expert) * attention_flops(cfg, rows, seq)
            + expert_flops(cfg, expert_pairs))


def expected_expert_pairs(cfg, rows, seq):
    """Pairs over all expert blocks under uniform routing."""
    return (blocks(cfg)[1] * rows * seq * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts_held"] // cfg["n_routed_experts"])


def least_bytes(cfg):
    """Bytes a step moves at least: float32 parameters read and written
    once, Adam's two moments read and written once; the frozen biases read
    once."""
    return trainable_params(cfg) * 4 * 6 + frozen_params(cfg) * 4


def step_floor_seconds(cfg, rows, seq, expert_pairs, peaks, chips=1):
    """(least seconds one step can take on ``chips`` chips, which bound
    sets it)."""
    compute = required_flops(cfg, rows, seq, expert_pairs) / (
        chips * peaks["bf16_flops"])
    memory = least_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")
