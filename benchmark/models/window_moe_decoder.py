"""Builds a window / full attention mixture-of-experts decoder configuration
through the program's normal path:
``paddle_tpu.models.sparse_moe_decoder.window_moe_decoder_lm`` into a Fluid
``Program`` — grouped-query blocks whose kind differs by layer (``layer_types``:
a 1024-key window under the plain rotation, or every causal key under YaRN's
rotation with its attention factor, both ``layers.rotary_embedding`` and
``layers.fused_attention``), the routed experts' share —
``optimizer.Adam(...).minimize``, ``contrib.mixed_precision`` and one
``fluid.Executor.run`` a step.

The object is :mod:`benchmark.models.sparse_moe_decoder`'s — the same scope
handling, the same stacking of the reference's 2-D expert leaves into the
program's ``[held, ., .]`` parameters, the same ``step(feed)`` for the set-up
checks and the window — over another program.  ``step`` fetches the loss, the
step's counters (``WINDOW_STEP_STATS``) and the attention half's output of the
first layer of each kind, left on the device."""

import paddle_tpu as fluid
from paddle_tpu import registry
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import sparse_moe_decoder as smd

from benchmark import harness
from benchmark.models import sparse_moe_decoder as base

# a program without this family of blocks cannot run the configuration: it
# fails HERE, when the generator loads this module, before the plain
# reference's time on the chip is spent
_window_moe_decoder_lm = smd.window_moe_decoder_lm

_SCALING = {"factor": "factor",
            "original_length": "original_max_position_embeddings",
            "beta_fast": "beta_fast", "beta_slow": "beta_slow"}


def rope_of(params):
    """``(theta, freq_scaling, scale)`` of one kind's ``rope_parameters``."""
    if params["rope_type"] == "default":
        return float(params["rope_theta"]), None, 1.0
    if params["rope_type"] != "yarn":
        raise ValueError("unknown rope_type %r" % (params["rope_type"],))
    return (float(params["rope_theta"]),
            {k: params[src] for k, src in _SCALING.items()},
            float(params["attention_factor"]))


class TrainModel(base.TrainModel):
    def __init__(self, cfg, traffic, devices):
        self.cfg = cfg
        self.first = cfg.get("first_local_expert", 0)
        seq, ropes = traffic["seq"], cfg["rope_parameters"]
        mixers = harness.load_module("flops", cfg["flops"]).mixers(cfg)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = cfg["program_seed"]
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tok, lbl = (fluid.layers.data(n, shape=[seq, 1], dtype="int64")
                        for n in ("tok", "lbl"))
            loss, stats, contexts = _window_moe_decoder_lm(
                tok, lbl, cfg["vocab_size"], cfg["hidden_size"],
                tuple(mixers), cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                cfg["sliding_window"],
                {"window": rope_of(ropes["sliding_attention"]),
                 "full": rope_of(ropes["full_attention"])},
                (cfg["num_experts_held"], cfg["num_experts"], self.first),
                cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
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
        # the first layer of each kind: what ``mixer_context_gap`` compares
        self._fetch = [loss, stats] + [
            contexts[mixers.index(k)] for k in ("window", "full")
            if k in mixers]
        # the trace's names of the window layers' attention ops and of
        # their gradients (``registry.fluid_scope_name``)
        self.window_scopes = [
            registry.fluid_scope_name(op) for op in main.global_block().ops
            if op.type in ("fused_attention", "fused_attention_grad")
            and op.attr("window") is not None]
        self._place = (fluid.TPUPlace(devices[0].id)
                       if devices[0].platform == "tpu" else fluid.CPUPlace())
        self.reset()


def build_train(cfg, traffic, devices):
    return TrainModel(cfg, traffic, devices)
