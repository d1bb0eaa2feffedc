"""Op library: importing this package registers every op with the registry.

The TPU-native analog of the reference's ``paddle/fluid/operators/``
(~314 registered op types): kernels are pure JAX functions that trace into
the program-level jit, with Pallas bodies for selected hot ops.
"""

from . import (  # noqa: F401
    activation,
    attention,
    control_flow,
    conv,
    creation,
    crf,
    ctc,
    detection,
    elementwise,
    fused_conv_bn,
    gated_delta_rule,
    kv_cache,
    loss,
    manipulation,
    math,
    metric,
    moe,
    norm,
    optimizer_ops,
    pipeline_region,
    pool,
    quantize,
    random,
    sampled_loss,
    reduction,
    rnn,
    selected_rows,
    sequence,
    sparse_select,
    state_space,
)
