"""The paper's own federated models (Table 2):

  FEMNIST      — CNN,    ~1.2M params, 62-way character classification
  Sentiment140 — LSTM,   ~4.8M params, binary sentiment
  iNaturalist  — ResNet, ~11.2M params (ResNet-18-ish), 1010 classes

These are the models actually trained in the FL accuracy experiments
(Tables 4/5/6, Fig. 5). Pure JAX, same (init, apply, loss) convention as
transformer.py so the FL trainer is model-agnostic.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import Params, _dense_init


@dataclasses.dataclass(frozen=True)
class SmallModelSpec:
    name: str
    init: Callable[[jax.Array], Params]
    apply: Callable[[Params, jax.Array], jax.Array]
    input_shape: tuple[int, ...]
    num_classes: int
    input_dtype: str = "float32"

    def loss(self, params: Params, batch: dict) -> jax.Array:
        logits = self.apply(params, batch["x"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
        return jnp.mean(logz - ll)

    def accuracy(self, params: Params, batch: dict) -> jax.Array:
        logits = self.apply(params, batch["x"])
        return jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# FEMNIST CNN (LEAF benchmark CNN, as used by Marfoq et al. [58])
# ---------------------------------------------------------------------------


def _conv_init(key, shape):  # (H, W, Cin, Cout)
    fan_in = shape[0] * shape[1] * shape[2]
    return jax.random.normal(key, shape) * np.sqrt(2.0 / fan_in)


#: Fewest filter input channels lowered to the native convolution. Under
#: the per-silo vmap a conv is feature-grouped, one group a silo; on a
#: TPU v5e a 1-channel group sums in another order than an ungrouped
#: conv (ulps of f32), which the next layer's bf16 operand rounding
#: turns into a first-loss drift, while 32-channel groups match bit for
#: bit. Narrower filters keep im2col + matmul: one patch-extraction conv
#: (a constant 0/1 filter, so the silo axis folds into its batch and it
#: stays ungrouped) feeds one dot with the learned filter.
_NATIVE_MIN_CIN = 8


def _conv(x, w, stride=1):
    """SAME conv (pad (k-1)//2 before, k-1-(k-1)//2 after; ceil(H/stride)
    out) in float32 at the default matmul precision.

    A filter of `_NATIVE_MIN_CIN` or more input channels is one
    `lax.conv_general_dilated`, a grouped conv under the per-silo vmap;
    its gradients cost about what im2col's do on XLA:CPU and far less on
    the TPU. Narrower filters (FEMNIST's first conv, the ResNet stem)
    build im2col patches with `lax.conv_general_dilated_patches` at the
    highest precision (each patch element is one input element times 1,
    so the patches are the input itself) and multiply them by the
    flattened filter at the default precision. The patches order their
    features (c, di, dj), so the filter is flattened in that order; for
    one input channel that is the tap order (di, dj).
    """
    kh, kw, cin, cout = w.shape
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    pads = ((ph, kh - 1 - ph), (pw, kw - 1 - pw))
    if cin >= _NATIVE_MIN_CIN:
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), pads,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)  # (B, Ho, Wo, C*kh*kw)
    return patches @ w.transpose(2, 0, 1, 3).reshape(kh * kw * cin, cout)


def femnist_cnn_init(key) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "c1": _conv_init(ks[0], (5, 5, 1, 32)),
        "c2": _conv_init(ks[1], (5, 5, 32, 64)),
        "fc1": _dense_init(ks[2], (7 * 7 * 64, 384)),
        "b1": jnp.zeros((384,)),
        "fc2": _dense_init(ks[3], (384, 62)),
        "b2": jnp.zeros((62,)),
    }


def _maxpool2(x):
    """2x2 max pool via reshape (reduce_window's backward pass,

    SelectAndScatter, is pathologically slow on CPU XLA)."""
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def femnist_cnn_apply(p: Params, x: jax.Array) -> jax.Array:
    """x (B, 28, 28, 1) -> logits (B, 62)."""
    h = jax.nn.relu(_conv(x, p["c1"]))
    h = _maxpool2(h)
    h = jax.nn.relu(_conv(h, p["c2"]))
    h = _maxpool2(h)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ p["fc1"] + p["b1"])
    return h @ p["fc2"] + p["b2"]


# ---------------------------------------------------------------------------
# Sentiment140 LSTM
# ---------------------------------------------------------------------------

_S140_VOCAB = 15_000
_S140_EMBED = 300  # GloVe-300, the standard Sent140 embedding
_S140_HIDDEN = 256
_S140_SEQ = 32


def lstm_init(key) -> Params:
    ks = jax.random.split(key, 4)
    d, h = _S140_EMBED, _S140_HIDDEN
    return {
        "embed": jax.random.normal(ks[0], (_S140_VOCAB, d)) * 0.02,
        "wx": _dense_init(ks[1], (d, 4 * h)),
        "wh": _dense_init(ks[2], (h, 4 * h)),
        "b": jnp.zeros((4 * h,)),
        "out": _dense_init(ks[3], (h, 2)),
        "out_b": jnp.zeros((2,)),
    }


def lstm_apply(p: Params, tokens: jax.Array) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, 2)."""
    x = jnp.take(p["embed"], tokens, axis=0)  # (B,S,D)
    h_dim = _S140_HIDDEN

    def step(carry, xt):
        h, c = carry
        gates = xt @ p["wx"] + h @ p["wh"] + p["b"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), None

    b = x.shape[0]
    carry = (jnp.zeros((b, h_dim)), jnp.zeros((b, h_dim)))
    (h, _), _ = jax.lax.scan(step, carry, jnp.swapaxes(x, 0, 1))
    return h @ p["out"] + p["out_b"]


# ---------------------------------------------------------------------------
# iNaturalist ResNet (ResNet-18-ish, ~11.2M params)
# ---------------------------------------------------------------------------


def _bn_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _bn(p, x):  # instance-free "batch" norm: normalized over N,H,W
    mean = x.mean(axis=(0, 1, 2), keepdims=True)
    var = x.var(axis=(0, 1, 2), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def _block_init(key, cin, cout, stride):
    ks = jax.random.split(key, 3)
    p = {
        "c1": _conv_init(ks[0], (3, 3, cin, cout)),
        "bn1": _bn_init(cout),
        "c2": _conv_init(ks[1], (3, 3, cout, cout)),
        "bn2": _bn_init(cout),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(ks[2], (1, 1, cin, cout))
    return p


def _block_apply(p, x, stride):
    h = jax.nn.relu(_bn(p["bn1"], _conv(x, p["c1"], stride)))
    h = _bn(p["bn2"], _conv(h, p["c2"]))
    sc = _conv(x, p["proj"], stride) if "proj" in p else x
    return jax.nn.relu(h + sc)


_RESNET_STAGES = [(64, 1), (128, 2), (256, 2), (512, 2)]
_INAT_CLASSES = 1010


def resnet_init(key) -> Params:
    ks = jax.random.split(key, 12)
    p: Params = {"stem": _conv_init(ks[0], (3, 3, 3, 64)), "bn0": _bn_init(64)}
    cin = 64
    ki = 1
    for si, (cout, stride) in enumerate(_RESNET_STAGES):
        for bi in range(2):
            p[f"s{si}b{bi}"] = _block_init(ks[ki], cin, cout,
                                           stride if bi == 0 else 1)
            cin = cout
            ki += 1
    p["fc"] = _dense_init(ks[ki], (512, _INAT_CLASSES))
    p["fc_b"] = jnp.zeros((_INAT_CLASSES,))
    return p


def resnet_apply(p: Params, x: jax.Array) -> jax.Array:
    """x (B, 32, 32, 3) -> logits (B, 1010)."""
    h = jax.nn.relu(_bn(p["bn0"], _conv(x, p["stem"])))
    for si, (cout, stride) in enumerate(_RESNET_STAGES):
        for bi in range(2):
            h = _block_apply(p[f"s{si}b{bi}"], h, stride if bi == 0 else 1)
    h = h.mean(axis=(1, 2))
    return h @ p["fc"] + p["fc_b"]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

FEMNIST_CNN = SmallModelSpec("femnist_cnn", femnist_cnn_init,
                             femnist_cnn_apply, (28, 28, 1), 62)
SENT140_LSTM = SmallModelSpec("sent140_lstm", lstm_init, lstm_apply,
                              (_S140_SEQ,), 2, input_dtype="int32")
INAT_RESNET = SmallModelSpec("inat_resnet", resnet_init, resnet_apply,
                             (32, 32, 3), _INAT_CLASSES)

SMALL_MODELS = {m.name: m for m in (FEMNIST_CNN, SENT140_LSTM, INAT_RESNET)}


def param_count(params: Params) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
