"""Builds ``decoder_base`` through the program's normal path:
``serving.build_decoder_lm`` + ``serving.GenerationEngine`` (paged cache,
greedy sampling, no quantisation, no speculation)."""

import paddle_tpu as fluid
from paddle_tpu import serving

PREFIX = "bm"


def name_map(cfg):
    names = {"tok_emb": PREFIX + "_tok_emb", "pos_emb": PREFIX + "_pos_emb",
             "out_w": PREFIX + "_logits.w_0", "out_b": PREFIX + "_logits.b_0"}
    for i in range(cfg["n_layer"]):
        ref, prog = "l.%d." % i, "%s_l%d" % (PREFIX, i)
        for w in "qkvo":
            names[ref + "attn." + w] = "%s_%s.w_0" % (prog, w)
        for k in (1, 2):
            names[ref + "ln%d_g" % k] = "%s_ln%d.scale" % (prog, k)
            names[ref + "ln%d_b" % k] = "%s_ln%d.bias" % (prog, k)
            names[ref + "ffn.fc%d_w" % k] = "%s_fc%d.w_0" % (prog, k)
            names[ref + "ffn.fc%d_b" % k] = "%s_fc%d.b_0" % (prog, k)
    return names


def build_engine(cfg, traffic, device, weights):
    """A started GenerationEngine whose scope holds ``weights``."""
    place = (fluid.TPUPlace(device.id) if device.platform == "tpu"
             else fluid.CPUPlace())
    spec = serving.build_decoder_lm(
        vocab_size=cfg["vocab_size"], max_len=cfg["max_len"],
        slots=traffic["slots"], n_layer=cfg["n_layer"],
        n_head=cfg["n_head"], d_model=cfg["d_model"],
        d_inner=cfg["d_inner"], dtype=cfg["precision"], prefix=PREFIX,
        seed=cfg["program_seed"], paged=True, page_size=cfg["page_size"])
    scope = fluid.Scope()
    spec.init_scope(fluid.Executor(place), scope)
    for ref, name in name_map(cfg).items():
        cur = scope.find_var(name)
        if cur is None or tuple(cur.shape) != tuple(weights[ref].shape):
            raise ValueError("no program variable %s of shape %s"
                             % (name, weights[ref].shape))
        scope.set_var(name, weights[ref])
    engine = serving.GenerationEngine(
        spec, place=place, scope=scope,
        max_new_tokens=traffic["output"]["hi"],
        timeout_s=traffic["timeout_s"],
        bucket_bounds=list(traffic["buckets"]), record_logits=False)
    return engine, spec
