"""ResNet-50, plain: He et al., "Deep Residual Learning for Image
Recognition" (arXiv:1512.03385), table 1, 50-layer column.

    conv 64@7x7/2 pad 3 -> BN -> ReLU -> maxpool 3x3/2 pad 1
    stages of (3, 4, 6, 3) bottlenecks, widths 64/128/256/512 (x4 out):
      1x1 -> BN -> ReLU -> 3x3 -> BN -> ReLU -> 1x1 -> BN, + shortcut, ReLU
    global average pool -> fc classes

Departure from the paper, as the program makes it: the "v1.5" placement of
the stride (on the 3x3 convolution of a stage's first block, not its first
1x1), and that convolution padded as TensorFlow's SAME pads (at stride 2 on
an even side: nothing before, one after), not by one on both sides. Batch norm normalises with the statistics of the whole batch
(momentum 0.9, epsilon 1e-5); convolutions carry no bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops as O

DROPOUT_SITES = ()
HAS_BATCH_STATS = True
STAGES = (3, 4, 6, 3)   # the paper's; a test may hand over fewer blocks


def _block(p, s, x, stride, *, ops, train):
    new = {}

    def bn(name, v):
        y, new[name] = O.batch_norm(v, p[name], s[name], train=train)
        return y

    y = jax.nn.relu(bn("bn1", ops.conv(x, p["conv1"]["kernel"], 1, "VALID")))
    y = jax.nn.relu(bn("bn2", ops.conv(y, p["conv2"]["kernel"], stride,
                                       "SAME")))
    y = bn("bn3", ops.conv(y, p["conv3"]["kernel"], 1, "VALID"))
    if "conv_proj" in p:
        x = bn("bn_proj", ops.conv(x, p["conv_proj"]["kernel"], stride,
                                   "VALID"))
    return jax.nn.relu(y + x), new


def forward(params, stats, x, *, ops: O.Ops, train: bool, masks=None,
            dropout_rate: float = 0.0, remat: bool = True):
    """`x`: (b, 224, 224, 3) float32 normalised rows, the whole batch at
    once (batch norm couples its rows). With `remat` each bottleneck is
    rematerialised in the backward pass so that float32 activations of the
    full batch fit one chip; whoever counts the operations a step needs
    turns it off. Returns (float32 logits, new running statistics)."""
    new = {}
    x = ops.conv(x, params["conv_init"]["kernel"], 2, ((3, 3), (3, 3)))
    x, new["bn_init"] = O.batch_norm(x, params["bn_init"], stats["bn_init"],
                                     train=train)
    x = O.max_pool(jax.nn.relu(x), 3, 2, ((1, 1), (1, 1)))
    for stage in range(len(STAGES)):
        blocks = sum(1 for k in params if k.startswith(f"stage{stage + 1}_"))
        for block in range(blocks):
            name = f"stage{stage + 1}_block{block + 1}"
            stride = 2 if stage > 0 and block == 0 else 1
            fn = lambda p, s, v, stride=stride: _block(
                p, s, v, stride, ops=ops, train=train)
            if remat:
                fn = jax.checkpoint(fn)
            x, new[name] = fn(params[name], stats[name], x)
    x = jnp.mean(x, axis=(1, 2))
    head = params["head"]
    return ops.dense(x, head["kernel"]) + head["bias"], new
