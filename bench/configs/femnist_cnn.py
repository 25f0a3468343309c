"""Plain reference of the LEAF FEMNIST CNN, and its operation count.

conv5x5(32) - relu - maxpool2 - conv5x5(64) - relu - maxpool2 -
dense(384) - relu - dense(classes), every size read from
`femnist_cnn.json`. `init` draws the program's weights from the seed's
key: four subkeys for c1, c2, fc1 and fc2, biases zero.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.core import nn


def init(key, cfg):
    k, (c1, c2), hid = cfg["conv_kernel"], cfg["conv_channels"], cfg["hidden"]
    h, w, cin = cfg["input_shape"]
    flat = (h // 4) * (w // 4) * c2
    ks = jax.random.split(key, 4)
    return {
        "c1": nn.conv_init(ks[0], (k, k, cin, c1)),
        "c2": nn.conv_init(ks[1], (k, k, c1, c2)),
        "fc1": nn.dense_init(ks[2], (flat, hid)),
        "b1": jnp.zeros((hid,)),
        "fc2": nn.dense_init(ks[3], (hid, cfg["num_classes"])),
        "b2": jnp.zeros((cfg["num_classes"],)),
    }


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def apply(p, x, cfg, precision):
    h = _pool(jax.nn.relu(nn.conv(x, p["c1"], 1, precision)))
    h = _pool(jax.nn.relu(nn.conv(h, p["c2"], 1, precision)))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(nn.dense(h, p["fc1"], precision) + p["b1"].astype(h.dtype))
    return nn.dense(h, p["fc2"], precision) + p["b2"].astype(h.dtype)


def forward_macs(cfg) -> int:
    """Multiply-adds of one sample's forward pass (convs and denses)."""
    k, (c1, c2), hid = cfg["conv_kernel"], cfg["conv_channels"], cfg["hidden"]
    h, w, cin = cfg["input_shape"]
    conv1 = h * w * k * k * cin * c1
    conv2 = (h // 2) * (w // 2) * k * k * c1 * c2
    flat = (h // 4) * (w // 4) * c2
    return conv1 + conv2 + flat * hid + hid * cfg["num_classes"]
