"""Required operations and least bytes of one training step of the
delta-attention / latent-attention mixture-of-experts decoder, from the
configuration's shapes and the step's own counters.

"Required" is what the forward and backward passes need, nothing recomputed:
every projection, the dense FFN, the shared expert, the router and the head
over every token, the routed experts over the token-expert pairs COMPUTED
here (the counter the step reports), backward = 2x forward (every matrix is
trained; the router's bias, the decays' ``A_log`` and ``dt_bias`` and the
short convolutions' taps are vectors); the latent block's attention over ALL
causal (query, key) pairs with 192-wide keys and 128-wide values; and the
delta rule's chunk products at the configuration's chunk ``C``, a chunk and
head: ``A`` and ``P`` (``C x C x Dk`` each), ``W = T (K * exp(G))`` and ``U0
= T V`` (``C x C x Dk``, ``C x C x Dv``), ``W S``, ``(Q * exp(G)) S`` and the
state's update (``C x Dk x Dv`` each) and ``P U`` (``C x C x Dv``) — the
chunked form is what the rule costs on a matrix unit; the token-by-token
recurrence needs fewer operations and no matrix unit could run it.  The
``C x C`` inverse and the channel-by-channel decays of a sub-block are
elementwise work and are not counted.
"""

from benchmark.flops.joyai_llm_flash import (              # noqa: F401
    causal_pairs, expert_flops)
from benchmark.flops.keye_vl2_30b_a3b import kernel_floor_seconds  # noqa: F401
# which mixer each layer has is read off the configuration ONCE, beside the
# plain reference that is written layer by layer from it
from benchmark.reference.linear_latent_decoder import mixers    # noqa: F401


def blocks(cfg):
    """(dense blocks, expert blocks) of the step."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def delta_matrix_params(cfg):
    """Matrix entries of ONE delta-attention mixer: q, k, v, o, the two
    low-rank gates (inner width a head's) and beta's product."""
    d, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    n, dh = lin["num_heads"], lin["head_dim"]
    return 4 * d * n * dh + 2 * (d * dh + dh * n * dh) + d * n


def delta_vector_params(cfg):
    """... and its vectors: three convolutions' taps, ``A_log``, ``dt_bias``
    and the head-wise norm's gain."""
    lin = cfg["linear_attn_config"]
    n, dh = lin["num_heads"], lin["head_dim"]
    return 3 * lin["short_conv_kernel_size"] * n * dh + n + n * dh + dh


def latent_matrix_params(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv, kvr = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                           cfg["v_head_dim"], cfg["kv_lora_rank"])
    return (d * h * (nope + rope) + d * (kvr + rope) + kvr * h * (nope + dv)
            + h * dv * d)


def _mixer_matrix_params(cfg):
    kinds = mixers(cfg)
    return kinds.count("kda") * delta_matrix_params(cfg) \
        + kinds.count("mla") * latent_matrix_params(cfg)


def per_token_params(cfg):
    """Matrix entries every token passes: the mixers, the dense FFN, the
    routers, the shared experts and the head (the routed experts apart)."""
    d, f, fe = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    dense, expert = blocks(cfg)
    return (_mixer_matrix_params(cfg) + dense * 3 * d * f
            + expert * (d * cfg["num_experts"]
                        + cfg["num_shared_experts"] * 3 * d * fe)
            + d * cfg["vocab_size"])


def trainable_params(cfg):
    d = cfg["hidden_size"]
    kinds = mixers(cfg)
    dense, expert = blocks(cfg)
    vectors = kinds.count("kda") * delta_vector_params(cfg) \
        + kinds.count("mla") * cfg["kv_lora_rank"] + len(kinds) * 2 * d + d
    routed = expert * cfg["num_experts_held"] * 3 * d \
        * cfg["moe_intermediate_size"]
    return per_token_params(cfg) + routed + vectors + cfg["vocab_size"] * d


def frozen_params(cfg):
    return blocks(cfg)[1] * cfg["num_experts"]


def attention_flops(cfg, rows, seq):
    """Forward + backward FLOPs of ONE latent block's attention kernels over
    all causal pairs: QK over 192, PV over 128, and their four gradient
    products."""
    return 3 * 2 * rows * causal_pairs(seq) * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def attention_least_bytes(cfg, rows, seq, itemsize=2):
    """Bytes ONE latent block's attention moves at least, forward +
    backward: Q, K, V, O and their four gradients once each, in the
    products' precision."""
    return 2 * rows * seq * cfg["num_attention_heads"] * 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) * itemsize


def rule_flops(cfg, rows, seq):
    """Forward + backward FLOPs of ONE delta-attention layer's chunk
    products (the module's text lists them); a last chunk that is not full
    counts whole."""
    lin, c = cfg["linear_attn_config"], cfg["delta_rule_chunk"]
    dk = dv = lin["head_dim"]
    per_chunk = c * c * (3 * dk + 2 * dv) + 3 * c * dk * dv
    return 3 * 2 * rows * -(-seq // c) * lin["num_heads"] * per_chunk


def rule_least_bytes(cfg, rows, seq, itemsize=2):
    """Bytes ANY implementation of ONE layer's ``gated_delta_rule`` op reads
    and writes, forward + backward, each array once and in the dtype the op
    is handed it: ``q``, ``k``, ``v``, the gate's and the output gate's
    pre-activations in, their five gradients out, ``itemsize`` bytes an
    element (what the products and convolutions leave: bf16 under mixed
    precision); ``out`` out and its gradient in, float32; ``beta`` in and
    its gradient out, float32, a value a head.  The op's vectors (``ALog``,
    ``DtBias``, the gain) are not counted."""
    lin = cfg["linear_attn_config"]
    n, dh = lin["num_heads"], lin["head_dim"]
    return rows * seq * (n * dh * (10 * itemsize + 2 * 4) + 2 * n * 4)


def expert_least_bytes(cfg, itemsize=2):
    """Bytes the grouped products of ALL expert blocks move at least: every
    held matrix read once forward and once backward in the products'
    precision, its float32 gradient written once."""
    mats = blocks(cfg)[1] * cfg["num_experts_held"] * 3 \
        * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return mats * (2 * itemsize + 4)


def required_flops(cfg, rows, seq, expert_pairs):
    """FLOPs one step requires: ``expert_pairs`` token-expert pairs over all
    expert blocks (the counter's, or ``expected_expert_pairs``)."""
    kinds = mixers(cfg)
    return (3 * 2 * rows * seq * per_token_params(cfg)
            + kinds.count("mla") * attention_flops(cfg, rows, seq)
            + kinds.count("kda") * rule_flops(cfg, rows, seq)
            + expert_flops(cfg, expert_pairs))


def expected_expert_pairs(cfg, rows, seq):
    """Pairs over all expert blocks under uniform routing."""
    return (blocks(cfg)[1] * rows * seq * cfg["num_experts_per_token"]
            * cfg["num_experts_held"] // cfg["num_experts"])


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
