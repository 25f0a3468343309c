"""Plain building blocks of the reference models, in `jax.numpy`/`lax`.

Nothing here imports the program. The initialisers draw from the same
`jax.random` streams as the models they stand for (the seed defines the
starting weights), and convolutions are `lax.conv_general_dilated` with
the program's padding convention written out: a kernel of size k pads
(k-1)//2 before and k-1-(k-1)//2 after, at every stride.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def conv_init(key, shape):
    """He-normal filter, (kh, kw, cin, cout)."""
    fan_in = shape[0] * shape[1] * shape[2]
    return jax.random.normal(key, shape) * np.sqrt(2.0 / fan_in)


def dense_init(key, shape):
    """Normal / sqrt(fan_in), (fan_in, fan_out)."""
    return (jax.random.normal(key, shape) * (1.0 / np.sqrt(shape[0]))
            ).astype(jnp.float32)


def conv(x, w, stride, precision):
    """NHWC x HWIO convolution with the program's padding."""
    kh, kw = w.shape[0], w.shape[1]
    pad = [((kh - 1) // 2, kh - 1 - (kh - 1) // 2),
           ((kw - 1) // 2, kw - 1 - (kw - 1) // 2)]
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def conv_out(size, k, stride):
    """Spatial output size of `conv` (ceil(size / stride))."""
    return (size - 1) // stride + 1


def dense(x, w, precision):
    return jnp.dot(x, w.astype(x.dtype), precision=precision)


def cross_entropy(logits, y):
    """Mean over the batch of -log softmax(logits)[y]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - ll)
