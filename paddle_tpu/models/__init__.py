"""Model zoo mirroring the reference benchmark suite's model set
(``benchmark/fluid/models/``: mnist, vgg, resnet, se_resnext,
machine_translation, stacked_dynamic_lstm) — built from the paddle_tpu
layers DSL, TPU-first (bfloat16-friendly, MXU-sized matmuls/convs).

Beside them the decoder family the benchmark trains, three kinds of block:
``sparse_moe_decoder`` holds the two pre-norm kinds with routed experts
(grouped-query attention over indexer-selected keys; latent attention with
a multi-token-prediction module) and everything the kinds share — the
bias-free projection, the dense SiLU-gated FFN's products, the untied
head's per-token cross entropy, the step counters' declaration;
``looped_decoder`` the sandwich-norm kind, one stack run several times over
the same weights under an exit gate, which shares all four.
"""

from . import (alexnet, ctr_dnn, googlenet, looped_decoder,  # noqa: F401
               machine_translation, mnist, resnet, se_resnext,
               simnet_bow, smallnet, sparse_moe_decoder,
               stacked_dynamic_lstm, transformer, vgg)
