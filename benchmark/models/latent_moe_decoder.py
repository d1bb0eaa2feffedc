"""Builds a latent-attention mixture-of-experts decoder configuration with
its multi-token-prediction module through the program's normal path:
``paddle_tpu.models.sparse_moe_decoder.latent_decoder_lm`` into a Fluid
``Program``, ``optimizer.Adam(...).minimize``, ``contrib.mixed_precision``
and one ``fluid.Executor.run`` a step.

The object is :mod:`benchmark.models.sparse_moe_decoder`'s — the same
scope handling, the same stacking of the reference's 2-D expert leaves into
the program's ``[held, ., .]`` parameters, the same ``step(feed)`` for the
set-up checks and the window — over another program and with a third feed,
the next-but-one token.  ``step`` fetches the loss (``L_main + w L_mtp``)
and the step's counters (the last is ``L_mtp``), left on the device."""

import paddle_tpu as fluid
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import sparse_moe_decoder as smd

from benchmark.models import sparse_moe_decoder as base

# a program without this family of blocks cannot run the configuration: it
# fails HERE, when the generator loads this module, before the plain
# reference's two minutes on the chip are spent
_latent_decoder_lm = smd.latent_decoder_lm


class TrainModel(base.TrainModel):
    def __init__(self, cfg, traffic, devices):
        self.cfg = cfg
        self.first = cfg.get("first_local_expert", 0)
        seq = traffic["seq"]
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = cfg["program_seed"]
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tok, lbl, lbl2 = (fluid.layers.data(n, shape=[seq, 1],
                                                dtype="int64")
                              for n in ("tok", "lbl", "lbl2"))
            loss, stats = _latent_decoder_lm(
                tok, lbl, lbl2, cfg["vocab_size"], cfg["num_hidden_layers"],
                cfg["first_k_dense_replace"], cfg["hidden_size"],
                smd.LatentSizes(
                    cfg["num_attention_heads"], cfg["q_lora_rank"],
                    cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                    cfg["qk_rope_head_dim"], cfg["v_head_dim"]),
                cfg["intermediate_size"],
                (cfg["n_routed_experts_held"], cfg["n_routed_experts"],
                 self.first),
                cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
                cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
                route_scale=cfg["routed_scaling_factor"],
                mtp_weight=cfg["mtp_loss_weight"],
                rope_theta=float(cfg["rope_theta"]),
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
        self._fetch = [loss, stats]
        self._place = (fluid.TPUPlace(devices[0].id)
                       if devices[0].platform == "tpu" else fluid.CPUPlace())
        self.reset()

    def make_feed(self, batch):
        return {n: batch[n][..., None] for n in ("tok", "lbl", "lbl2")}


def build_train(cfg, traffic, devices):
    return TrainModel(cfg, traffic, devices)
