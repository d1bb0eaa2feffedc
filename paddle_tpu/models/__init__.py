"""Model zoo mirroring the reference benchmark suite's model set
(``benchmark/fluid/models/``: mnist, vgg, resnet, se_resnext,
machine_translation, stacked_dynamic_lstm) — built from the paddle_tpu
layers DSL, TPU-first (bfloat16-friendly, MXU-sized matmuls/convs).
"""

from . import (alexnet, ctr_dnn, googlenet,  # noqa: F401
               machine_translation, mnist, resnet, se_resnext,
               simnet_bow, smallnet, sparse_moe_decoder,
               stacked_dynamic_lstm, transformer, vgg)
