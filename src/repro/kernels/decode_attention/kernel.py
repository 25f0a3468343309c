"""Flash-decode Pallas TPU kernel: one token vs a long KV cache.

Serving decode is HBM-bound: the whole cache streams through once per
token (§Roofline decode rows). The kernel keeps the (group, hd) query
tile resident in VMEM and streams (block_s, hd) cache tiles with an
online softmax, so cache bytes are read EXACTLY once and no (S,)-sized
score vector ever hits HBM. Grid: (batch*kv_heads, s_blocks), s
innermost so the running max/denominator live in VMEM scratch.

GPU flash-decoding splits the sequence across SMs and tree-combines
partial softmaxes; on TPU a single core's sequential grid makes the
combine implicit (scratch carries), and the cross-chip split is done at
the GSPMD level instead (sequence-sharded caches + psum — see
launch/sharding.py decode specs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, block_s: int, seq: int):
    si = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)      # (g, hd)
    k = k_ref[0].astype(jnp.float32)      # (block_s, hd)
    v = v_ref[0].astype(jnp.float32)
    length = len_ref[pl.program_id(0)]

    pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (block_s, 1), 0)
    ok = (pos < length) & (pos < seq)     # (block_s, 1)
    k = jnp.where(ok, k, 0.0)
    v = jnp.where(ok, v, 0.0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s / np.sqrt(q.shape[-1])          # (g, block_s)
    s = jnp.where(ok[:, 0][None, :], s, NEG_INF)

    m_prev = m_ref[...]                    # (g, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    safe = m_new > NEG_INF / 2
    alpha = jnp.where(safe, jnp.exp(m_prev - m_new), 0.0)
    p = jnp.exp(s - jnp.where(safe, m_new, 0.0))
    p = jnp.where(ok[:, 0][None, :], p, 0.0)

    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q, k, v, lengths, *, block_s: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q (B, Hq, hd), k/v (B, Hkv, S, hd), lengths (B,) -> (B, Hq, hd)."""
    b, hq, hd = q.shape
    _, hkv, s, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    block_s = min(block_s, s)
    ns = pl.cdiv(s, block_s)

    qr = q.reshape(b, hkv, g, hd).reshape(b * hkv, g, hd)
    kr = k.reshape(b * hkv, s, hd)
    vr = v.reshape(b * hkv, s, hd)
    lens = jnp.repeat(lengths.astype(jnp.int32), hkv)  # (B*Hkv,)

    # lengths ride in SMEM as a scalar prefetch: a (1,) VMEM block per
    # grid row is not a tile the TPU lowering accepts
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, ns),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda h, si, _: (h, 0, 0)),
            pl.BlockSpec((1, block_s, hd), lambda h, si, _: (h, si, 0)),
            pl.BlockSpec((1, block_s, hd), lambda h, si, _: (h, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda h, si, _: (h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),    # running max
                        pltpu.VMEM((g, 1), jnp.float32),    # denominator
                        pltpu.VMEM((g, hd), jnp.float32)],  # accumulator
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, seq=s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, hd), q.dtype),
        interpret=interpret,
    )(lens, qr, kr, vr)
    return out.reshape(b, hq, hd)

