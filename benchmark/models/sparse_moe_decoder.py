"""Builds a sparse-attention mixture-of-experts decoder configuration
through the program's normal path: ``paddle_tpu.models.sparse_moe_decoder``
into a Fluid ``Program``, ``optimizer.Adam(...).minimize``,
``contrib.mixed_precision`` and one ``fluid.Executor.run`` a step.

The object it returns is the one the set-up checks AND the window drive:
``step(feed)`` is the timed call, and what it fetches (the loss, the step's
counters and the first layer's packed key mask, all left on the device) is
the same in both, so the window runs the executable the checks ran.

The reference names every matrix as a 2-D leaf; the program holds its
experts as stacked ``[held, ., .]`` parameters.  ``set_weights`` stacks the
leaves, ``state`` hands them back one leaf an expert."""

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import sparse_moe_decoder as smd


def _expert_leaf(name):
    """``l0.moe.e3.gate`` -> (``l0.moe.gate``, 3); None for other leaves."""
    parts = name.split(".")
    if len(parts) == 4 and parts[1] == "moe" and parts[2][0] == "e":
        return "%s.moe.%s" % (parts[0], parts[3]), int(parts[2][1:])
    return None


class TrainModel:
    def __init__(self, cfg, traffic, devices):
        self.cfg = cfg
        self.first = cfg.get("first_local_expert", 0)
        sa = cfg["sa_config"]
        seq = traffic["seq"]
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = cfg["program_seed"]
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tok = fluid.layers.data("tok", shape=[seq, 1], dtype="int64")
            lbl = fluid.layers.data("lbl", shape=[seq, 1], dtype="int64")
            loss, stats, selected = smd.decoder_lm(
                tok, lbl, cfg["vocab_size"], cfg["num_hidden_layers"],
                cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                (cfg["num_local_experts"], cfg["num_experts"], self.first),
                cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
                sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
                rope_theta=float(cfg["rope_theta"]),
                rms_eps=cfg["rms_norm_eps"],
                expert_tile=cfg["expert_tile"])
            opt = fluid.optimizer.Adam(
                learning_rate=cfg["learning_rate"], beta1=cfg["adam_beta1"],
                beta2=cfg["adam_beta2"], epsilon=cfg["adam_epsilon"])
            if cfg["precision"] == "bf16_amp":
                opt = mixed_precision.decorate(opt)
            elif cfg["precision"] != "float32":
                raise ValueError("unknown precision %r" % cfg["precision"])
            opt.minimize(loss)
        self.main, self._startup = main, startup
        self._fetch = [loss, stats, selected]
        self._place = (fluid.TPUPlace(devices[0].id)
                       if devices[0].platform == "tpu" else fluid.CPUPlace())
        self.reset()

    def release(self):
        """Let go of every array the program holds on the device (between
        seeds the readings need the room for the reference's run)."""
        self._exe = self.scope = None

    def reset(self):
        """(Re)make every persistable in a new scope as the startup program
        does, on an executor of its own, and a new executor for the steps
        (the compiled step is found again by the program's fingerprint)."""
        self.release()
        self.scope = fluid.Scope()
        with fluid.scope_guard(self.scope):
            fluid.Executor(self._place).run(self._startup)
        self._exe = fluid.Executor(self._place)

    def set_weights(self, weights):
        """Put the benchmark's seeded weights under the program's names;
        an expert's 2-D leaves go into the stacked parameters, in the
        order of the experts' numbers."""
        stacks = {}
        for ref, arr in weights.items():
            leaf = _expert_leaf(ref)
            if leaf is None:
                self._set(ref, jnp.asarray(arr))
            else:
                stacks.setdefault(leaf[0], {})[leaf[1] - self.first] = arr
        for name, parts in stacks.items():
            self._set(name, jnp.stack([parts[i] for i in range(len(parts))]))

    def _set(self, name, arr):
        cur = self.scope.find_var(name)
        if cur is None or tuple(cur.shape) != tuple(arr.shape):
            raise ValueError("no program variable %s of shape %s"
                             % (name, arr.shape))
        self.scope.set_var(name, arr)

    def make_feed(self, batch):
        return {"tok": batch["tok"][..., None], "lbl": batch["lbl"][..., None]}

    def step(self, feed):
        """One training step; returns (loss, stats [4], the first layer's
        packed key mask) as device arrays, without waiting for them."""
        with fluid.scope_guard(self.scope):
            return self._exe.run(self.main, feed=feed,
                                 fetch_list=self._fetch, return_numpy=False)

    def state(self, names, suffix=""):
        """{reference leaf name: the program's array} for ``names``;
        ``suffix`` ``"_moment1_0"`` reads Adam's first moment."""
        out = {}
        for ref in names:
            leaf = _expert_leaf(ref)
            if leaf is None:
                out[ref] = self.scope.find_var(ref + suffix)
            else:
                out[ref] = self.scope.find_var(leaf[0] + suffix)[
                    leaf[1] - self.first]
        return out

    def close(self):
        self.release()


def build_train(cfg, traffic, devices):
    return TrainModel(cfg, traffic, devices)
