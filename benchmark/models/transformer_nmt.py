"""Builds ``transformer_base`` through the program's normal path:
``paddle_tpu.models.transformer`` + ``fluid.Executor`` on one chip, or
``fluid.ParallelExecutor`` on a data-parallel mesh.  Construction follows
``chip_smoke.build_transformer`` (copied, not imported).

The object it returns is the one the set-up checks AND the window drive:
``step(feed)`` is the timed call."""

import paddle_tpu as fluid
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import transformer as tfm
from paddle_tpu.parallel import make_mesh


def name_map(cfg):
    """reference leaf name -> program variable name."""
    n = cfg["n_layer"]
    names = {"src_emb": "src_word_emb", "tgt_emb": "tgt_word_emb",
             "out_w": "dec_logits.w_0", "out_b": "dec_logits.b_0"}

    def attn(ref, prog):
        for w in "qkvo":
            names[ref + w] = "%s_%s.w_0" % (prog, w)

    def norm(ref, index):
        names[ref + "_g"] = "layer_norm_%d.w_0" % index
        names[ref + "_b"] = "layer_norm_%d.b_0" % index

    def feed(ref, prog):
        for a, b in (("fc1_w", "fc1.w_0"), ("fc1_b", "fc1.b_0"),
                     ("fc2_w", "fc2.w_0"), ("fc2_b", "fc2.b_0")):
            names[ref + a] = "%s_%s" % (prog, b)
    for i in range(n):
        e = "enc.%d." % i
        attn(e + "attn.", "enc%d_attn" % i)
        norm(e + "ln1", 2 * i)
        feed(e + "ffn.", "enc%d_ffn" % i)
        norm(e + "ln2", 2 * i + 1)
    for i in range(n):
        e = "dec.%d." % i
        attn(e + "self.", "dec%d_self" % i)
        norm(e + "ln1", 2 * n + 3 * i)
        attn(e + "cross.", "dec%d_cross" % i)
        norm(e + "ln2", 2 * n + 3 * i + 1)
        feed(e + "ffn.", "dec%d_ffn" % i)
        norm(e + "ln3", 2 * n + 3 * i + 2)
    return names


class TrainModel:
    def __init__(self, cfg, seq, devices, mesh_axes):
        self.cfg = cfg
        self.names = name_map(cfg)
        fluid.set_flags({"FLAGS_fast_prng": cfg["prng"] == "rbg"})
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = cfg["program_seed"]
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            src = fluid.layers.data("src_word", shape=[1], dtype="int64",
                                    lod_level=1)
            tgt = fluid.layers.data("tgt_word", shape=[1], dtype="int64",
                                    lod_level=1)
            lbl = fluid.layers.data("lbl_word", shape=[1], dtype="int64",
                                    lod_level=1)
            loss, _ = tfm.transformer(
                src, tgt, lbl, seq, seq, cfg["vocab_size"],
                cfg["vocab_size"], n_layer=cfg["n_layer"],
                n_head=cfg["n_head"], d_model=cfg["d_model"],
                d_inner=cfg["d_inner"], dropout_rate=cfg["dropout"],
                label_smooth_eps=cfg["label_smooth_eps"])
            opt = fluid.optimizer.Adam(
                learning_rate=fluid.layers.noam_decay(
                    cfg["d_model"], cfg["warmup_steps"]),
                beta1=cfg["adam_beta1"], beta2=cfg["adam_beta2"],
                epsilon=cfg["adam_epsilon"])
            if cfg["precision"] == "bf16_amp":
                opt = mixed_precision.decorate(opt)
            elif cfg["precision"] != "float32":
                raise ValueError("unknown precision %r" % cfg["precision"])
            opt.minimize(loss)
        self.main, self.loss, self._startup = main, loss, startup
        self.scope = fluid.Scope()
        place = (fluid.TPUPlace(devices[0].id)
                 if devices[0].platform == "tpu" else fluid.CPUPlace())
        self._place = place
        self.reset()
        self.mesh = None
        if mesh_axes:
            shape = tuple(mesh_axes.values())
            self.mesh = make_mesh(shape, tuple(mesh_axes), devices=devices)
            self._exe = None
        else:
            self._exe = fluid.Executor(place)
        self._pe = None

    def reset(self):
        """(Re)make every persistable — parameters, Adam state, the step
        counters — as the startup program does.  It runs on an executor of
        its own: each run() folds the executor's step counter into the
        PRNG key."""
        with fluid.scope_guard(self.scope):
            fluid.Executor(self._place).run(self._startup)

    def set_weights(self, weights):
        """Put the benchmark's seeded weights under the program's names
        (optimizer state stays as the startup program made it: zeros)."""
        for ref, arr in weights.items():
            cur = self.scope.find_var(self.names[ref])
            if cur is None or tuple(cur.shape) != tuple(arr.shape):
                raise ValueError("no program variable %s of shape %s"
                                 % (self.names[ref], arr.shape))
            self.scope.set_var(self.names[ref], arr)

    def make_feed(self, batch):
        return {"src_word": batch["src"][..., None],
                "src_word@LEN": batch["src_len"],
                "tgt_word": batch["tgt"][..., None],
                "tgt_word@LEN": batch["tgt_len"],
                "lbl_word": batch["lbl"][..., None],
                "lbl_word@LEN": batch["tgt_len"]}

    def step(self, feed):
        """One training step; returns the loss as a device array without
        waiting for it."""
        if self.mesh is None:
            with fluid.scope_guard(self.scope):
                (loss,) = self._exe.run(self.main, feed=feed,
                                        fetch_list=[self.loss],
                                        return_numpy=False)
            return loss
        if self._pe is None:
            self._pe = fluid.ParallelExecutor(
                loss_name=self.loss.name, main_program=self.main,
                mesh=self.mesh, build_strategy=fluid.BuildStrategy(),
                scope=self.scope)
        with self.mesh:
            (loss,) = self._pe.run(feed=feed, fetch_list=[self.loss],
                                   return_numpy=False)
        return loss

    def state(self, suffix=""):
        """{reference leaf name: the program's array}; ``suffix``
        ``"_moment1_0"`` reads Adam's first moment."""
        return {ref: self.scope.find_var(name + suffix)
                for ref, name in self.names.items()}

    def close(self):
        self._exe = self._pe = None
        self.scope = None


def build_train(cfg, traffic, devices):
    return TrainModel(cfg, traffic["seq"], devices, traffic.get("mesh"))
