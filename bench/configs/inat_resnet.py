"""Plain reference of the repo's iNaturalist ResNet-18, and its cost.

stem conv3x3 - norm - relu, then `stages` of `blocks_per_stage` basic
blocks (conv3x3 - norm - relu - conv3x3 - norm, plus a 1x1 projection
where the width or stride changes), global mean pool, dense(classes).
The norm normalises over each batch's samples and positions with the
batch's own statistics. `init` draws the program's weights from the
seed's key: twelve subkeys (stem, eight blocks, head), three per block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.core import nn


def _norm_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _blocks(cfg):
    """(cin, cout, stride) of every basic block, in order."""
    out, cin = [], cfg["stem_channels"]
    for cout, stride in cfg["stages"]:
        for b in range(cfg["blocks_per_stage"]):
            out.append((cin, cout, stride if b == 0 else 1))
            cin = cout
    return out


def init(key, cfg):
    ks = jax.random.split(key, 12)
    c0 = cfg["stem_channels"]
    p = {"stem": nn.conv_init(ks[0], (3, 3, cfg["input_shape"][2], c0)),
         "bn0": _norm_init(c0)}
    blocks = _blocks(cfg)
    per_stage = cfg["blocks_per_stage"]
    for i, (cin, cout, stride) in enumerate(blocks):
        bk = jax.random.split(ks[1 + i], 3)
        blk = {"c1": nn.conv_init(bk[0], (3, 3, cin, cout)),
               "bn1": _norm_init(cout),
               "c2": nn.conv_init(bk[1], (3, 3, cout, cout)),
               "bn2": _norm_init(cout)}
        if stride != 1 or cin != cout:
            blk["proj"] = nn.conv_init(bk[2], (1, 1, cin, cout))
        p[f"s{i // per_stage}b{i % per_stage}"] = blk
    p["fc"] = nn.dense_init(ks[1 + len(blocks)],
                            (blocks[-1][1], cfg["num_classes"]))
    p["fc_b"] = jnp.zeros((cfg["num_classes"],))
    return p


def _norm(p, x, eps):
    mean = x.mean(axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2), keepdims=True)
    return ((x - mean) * lax.rsqrt(var + eps) * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def apply(p, x, cfg, precision):
    eps = cfg["bn_eps"]
    h = jax.nn.relu(_norm(p["bn0"], nn.conv(x, p["stem"], 1, precision), eps))
    per_stage = cfg["blocks_per_stage"]
    for i, (_, _, stride) in enumerate(_blocks(cfg)):
        blk = p[f"s{i // per_stage}b{i % per_stage}"]
        y = jax.nn.relu(_norm(blk["bn1"], nn.conv(h, blk["c1"], stride,
                                                  precision), eps))
        y = _norm(blk["bn2"], nn.conv(y, blk["c2"], 1, precision), eps)
        sc = nn.conv(h, blk["proj"], stride, precision) if "proj" in blk \
            else h
        h = jax.nn.relu(y + sc)
    h = h.mean(axis=(1, 2))
    return nn.dense(h, p["fc"], precision) + p["fc_b"].astype(h.dtype)


def forward_macs(cfg) -> int:
    """Multiply-adds of one sample's forward pass (convs and the head)."""
    hw, _, cin = cfg["input_shape"]
    c0 = cfg["stem_channels"]
    macs = hw * hw * 9 * cin * c0
    for ci, co, stride in _blocks(cfg):
        out = nn.conv_out(hw, 3, stride)
        macs += out * out * 9 * ci * co          # c1
        macs += out * out * 9 * co * co          # c2
        if stride != 1 or ci != co:
            macs += out * out * ci * co          # 1x1 projection
        hw = out
    return macs + _blocks(cfg)[-1][1] * cfg["num_classes"]
