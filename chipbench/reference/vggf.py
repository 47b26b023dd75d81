"""VGG-F / CNN-F, plain: Chatfield et al., "Return of the Devil in the
Details" (BMVC 2014, arXiv:1405.3531), table 1 row CNN-F.

    conv1 64@11x11/4 -> ReLU -> LRN -> maxpool 3x3/2 (ceil)
    conv2 256@5x5 pad 2 -> ReLU -> LRN -> maxpool 3x3/2
    conv3..5 256@3x3 pad 1 -> ReLU; maxpool 3x3/2 after conv5
    fc6 4096 -> ReLU -> dropout; fc7 4096 -> ReLU -> dropout; fc8 classes

Departures from the paper, as the program makes them: LRN in the
AlexNet-paper parameterisation (radius 2, bias 2, alpha 1e-4, beta 0.75).
The stem is the plain 11x11 stride-4 convolution on (224, 224, 3) rows;
the program's space-to-depth stem has to equal it.
"""

from __future__ import annotations

import jax

from . import ops as O

#: dropout sites in call order: (flax auto-name, width)
DROPOUT_SITES = (("Dropout_0", 4096), ("Dropout_1", 4096))
HAS_BATCH_STATS = False


def forward(params, stats, x, *, ops: O.Ops, train: bool, masks=None,
            dropout_rate: float = 0.5):
    """`x`: (b, 224, 224, 3) float32 normalised rows. `masks`: one keep
    mask per dropout site, or None. Returns (float32 logits, stats)."""
    def conv(name, v, stride, padding):
        p = params[name]
        return ops.conv(v, p["kernel"], stride, padding) + p["bias"]

    def dense(name, v):
        p = params[name]
        return ops.dense(v, p["kernel"]) + p["bias"]

    masks = masks if (train and masks is not None) else (None, None)
    x = O.ceil_pool_3x3s2(O.lrn(jax.nn.relu(conv("conv1", x, 4, "VALID"))))
    x = O.ceil_pool_3x3s2(O.lrn(jax.nn.relu(conv("conv2", x, 1, "SAME"))))
    x = jax.nn.relu(conv("conv3", x, 1, "SAME"))
    x = jax.nn.relu(conv("conv4", x, 1, "SAME"))
    x = O.ceil_pool_3x3s2(jax.nn.relu(conv("conv5", x, 1, "SAME")))
    x = x.reshape(x.shape[0], -1)
    x = O.dropout(jax.nn.relu(dense("fc6", x)), masks[0], dropout_rate)
    x = O.dropout(jax.nn.relu(dense("fc7", x)), masks[1], dropout_rate)
    return dense("fc8", x), stats
