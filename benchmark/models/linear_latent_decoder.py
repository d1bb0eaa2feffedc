"""Builds a delta-attention / latent-attention mixture-of-experts decoder
configuration through the program's normal path:
``paddle_tpu.models.sparse_moe_decoder.linear_latent_decoder_lm`` into a
Fluid ``Program`` — Kimi Delta Attention blocks on ``layers.gated_delta_rule``
behind ``layers.causal_conv1d``, a NoPE latent-attention block without a query
rank on ``layers.fused_attention``, the routed experts' share —
``optimizer.Adam(...).minimize``, ``contrib.mixed_precision`` and one
``fluid.Executor.run`` a step.

The object is :mod:`benchmark.models.sparse_moe_decoder`'s — the same scope
handling, the same stacking of the reference's 2-D expert leaves into the
program's ``[held, ., .]`` parameters, the same ``step(feed)`` for the set-up
checks and the window — over another program.  ``step`` fetches the loss, the
step's counters (``LINEAR_STEP_STATS``) and the first delta-attention layer's
final state, left on the device."""

import paddle_tpu as fluid
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import sparse_moe_decoder as smd

from benchmark import harness
from benchmark.models import sparse_moe_decoder as base

# a program without this family of blocks cannot run the configuration: it
# fails HERE, when the generator loads this module, before the plain
# reference's time on the chip is spent
_linear_latent_decoder_lm = smd.linear_latent_decoder_lm


class TrainModel(base.TrainModel):
    def __init__(self, cfg, traffic, devices):
        self.cfg = cfg
        self.first = cfg.get("first_local_expert", 0)
        seq, lin = traffic["seq"], cfg["linear_attn_config"]
        mixers = harness.load_module("flops", cfg["flops"]).mixers
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = cfg["program_seed"]
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tok, lbl = (fluid.layers.data(n, shape=[seq, 1], dtype="int64")
                        for n in ("tok", "lbl"))
            loss, stats, state = _linear_latent_decoder_lm(
                tok, lbl, cfg["vocab_size"], cfg["hidden_size"],
                tuple(mixers(cfg)), cfg["first_k_dense_replace"],
                smd.DeltaSizes(lin["num_heads"], lin["head_dim"],
                               lin["short_conv_kernel_size"],
                               lin["head_dim"], cfg["delta_rule_chunk"]),
                smd.LatentSizes(
                    cfg["num_attention_heads"], cfg["q_lora_rank"],
                    cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                    cfg["qk_rope_head_dim"], cfg["v_head_dim"]),
                cfg["intermediate_size"],
                (cfg["num_experts_held"], cfg["num_experts"], self.first),
                cfg["moe_intermediate_size"], cfg["num_experts_per_token"],
                cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
                route_scale=cfg["routed_scaling_factor"],
                rms_eps=cfg["rms_norm_eps"], expert_tile=cfg["expert_tile"])
            opt = fluid.optimizer.Adam(
                learning_rate=cfg["learning_rate"], beta1=cfg["adam_beta1"],
                beta2=cfg["adam_beta2"], epsilon=cfg["adam_epsilon"])
            if cfg["precision"] == "bf16_amp":
                opt = mixed_precision.decorate(opt)
            elif cfg["precision"] != "float32":
                raise ValueError("unknown precision %r" % cfg["precision"])
            opt.minimize(loss)
        self.main, self._startup = main, startup
        self._fetch = [loss, stats, state]
        self._place = (fluid.TPUPlace(devices[0].id)
                       if devices[0].platform == "tpu" else fluid.CPUPlace())
        self.reset()


def build_train(cfg, traffic, devices):
    return TrainModel(cfg, traffic, devices)
