"""Builds a looped dense decoder configuration through the program's normal
path: ``paddle_tpu.models.looped_decoder.looped_decoder_lm`` into a Fluid
``Program`` (the passes unrolled: every application of a block its own run
of ops over the same parameters), ``optimizer.Adam(...).minimize`` — whose
backward sums a weight's ``total_ut_steps`` gradients —
``contrib.mixed_precision`` and one ``fluid.Executor.run`` a step.

The object is :mod:`benchmark.models.sparse_moe_decoder`'s — the same
scope handling, the same ``step(feed)`` for the set-up checks and the
window — over another program.  ``step`` fetches the exit-gated loss and
the step's counters (the passes' mean cross entropy, then their mean exit
mass), left on the device."""

import paddle_tpu as fluid
from paddle_tpu.contrib import mixed_precision
# a program without this kind of block cannot run the configuration: it
# fails HERE, when the generator loads this module, before the plain
# reference's time on the chip is spent
from paddle_tpu.models import looped_decoder as ld

from benchmark.models import sparse_moe_decoder as base


class TrainModel(base.TrainModel):
    def __init__(self, cfg, traffic, devices):
        self.cfg = cfg
        seq = traffic["seq"]
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = cfg["program_seed"]
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tok, lbl = (fluid.layers.data(n, shape=[seq, 1], dtype="int64")
                        for n in ("tok", "lbl"))
            loss, stats = ld.looped_decoder_lm(
                tok, lbl, cfg["vocab_size"], cfg["num_hidden_layers"],
                cfg["total_ut_steps"], cfg["hidden_size"],
                cfg["num_attention_heads"], cfg["head_dim"],
                cfg["intermediate_size"], exit_beta=cfg["exit_beta"],
                rope_theta=float(cfg["rope_theta"]),
                rms_eps=cfg["rms_norm_eps"])
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


def build_train(cfg, traffic, devices):
    return TrainModel(cfg, traffic, devices)
