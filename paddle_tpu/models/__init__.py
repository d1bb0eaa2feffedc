"""Model zoo mirroring the reference benchmark suite's model set
(``benchmark/fluid/models/``: mnist, vgg, resnet, se_resnext,
machine_translation, stacked_dynamic_lstm) — built from the paddle_tpu
layers DSL, TPU-first (bfloat16-friendly, MXU-sized matmuls/convs).

Beside them the decoder family the benchmark trains, four kinds of block:
``sparse_moe_decoder`` holds the two pre-norm kinds with routed experts
(grouped-query attention over indexer-selected keys; latent attention with
a multi-token-prediction module) and everything the kinds share — the
bias-free projection, the dense SiLU-gated FFN's products, the untied
head's per-token cross entropy, the step counters' declaration;
``looped_decoder`` the sandwich-norm kind, one stack run several times over
the same weights under an exit gate, which shares all four;
``hybrid_decoder`` the decoder-hybrid-decoder kind — state-space layers,
differential attention under a window, and a cross-decoder that reads one
layer's keys, values and scan output — with LayerNorm and tied tables.
"""

from . import (alexnet, ctr_dnn, googlenet, hybrid_decoder,  # noqa: F401
               looped_decoder,
               machine_translation, mnist, resnet, se_resnext,
               simnet_bow, smallnet, sparse_moe_decoder,
               stacked_dynamic_lstm, transformer, vgg)
