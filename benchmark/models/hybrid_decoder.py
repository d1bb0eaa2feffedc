"""Builds a decoder-hybrid-decoder configuration through the program's normal
path: ``paddle_tpu.models.hybrid_decoder.hybrid_decoder_lm`` into a Fluid
``Program`` — Mamba layers on ``layers.selective_scan``, differential
attention on two ``layers.fused_attention`` calls a layer (one with a
``window``), a cross-decoder that reads the full layer's keys and values and
the Mamba layer's scan output, the head the embedding table transposed —
``optimizer.Adam(...).minimize``, ``contrib.mixed_precision`` and one
``fluid.Executor.run`` a step.

The object is :mod:`benchmark.models.sparse_moe_decoder`'s — the same scope
handling, the same ``step(feed)`` for the set-up checks and the window — over
another program.  ``step`` fetches the loss, the step's counters
(``HYBRID_STEP_STATS``) and the Mamba layer's final state, left on the
device."""

import paddle_tpu as fluid
from paddle_tpu.contrib import mixed_precision
# a program without this kind of block cannot run the configuration: it
# fails HERE, when the generator loads this module, before the plain
# reference's time on the chip is spent
from paddle_tpu.models import hybrid_decoder as hd

from benchmark.models import sparse_moe_decoder as base


def sizes_of(cfg):
    d = cfg["hidden_size"]
    return hd.HybridSizes(
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"],
        ffn_width=cfg["intermediate_size"], window=cfg["sliding_window"],
        ssm_width=cfg["mamba_expand"] * d, ssm_state=cfg["mamba_d_state"],
        conv_width=cfg["mamba_d_conv"], dt_rank=cfg["mamba_dt_rank"])


class TrainModel(base.TrainModel):
    def __init__(self, cfg, traffic, devices):
        self.cfg = cfg
        seq = traffic["seq"]
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = cfg["program_seed"]
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tok, lbl = (fluid.layers.data(n, shape=[seq, 1], dtype="int64")
                        for n in ("tok", "lbl"))
            loss, stats, state = hd.hybrid_decoder_lm(
                tok, lbl, cfg["vocab_size"], tuple(cfg["layer_kinds"]),
                cfg["first_layer"], cfg["hidden_size"], sizes_of(cfg),
                norm_eps=cfg["layer_norm_eps"])
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
