"""Flash attention Pallas TPU kernel (GQA / causal / window / prefix).

TPU adaptation (see DESIGN.md §3): classic FlashAttention is a CUDA
shared-memory algorithm; on TPU the same insight — never materialize the
(Sq, Sk) score matrix in HBM — maps to VMEM tiling with the MXU doing
(block_q, hd) x (hd, block_k) matmuls. The grid is
(batch*kv_heads, q_blocks, k_blocks) with the K dimension INNERMOST:
TPU grid steps execute sequentially per core, so the online-softmax
running max/denominator live in VMEM scratch across k-steps and the
output tile is rescaled in place. GQA is handled by loading the q tile
as (group*block_q, hd) — all query heads sharing a kv head ride in the
same MXU tile, which keeps the systolic array fed at kv_heads < 8.

Block sizes default to 128x128 (MXU-aligned; hd is 64..256 and padded
by Mosaic when needed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, seq_q: int,
                  seq_k: int, causal: bool, window: int, prefix: int,
                  group: int):
    """One (kv-head, q-block, k-block) grid step.

    q_ref   (group, block_q, hd)  queries of all heads sharing this kv head
    k_ref   (block_k, hd)
    v_ref   (block_k, hd)
    o_ref   (group, block_q, hd)  output tile (written on last k step)
    m/l/acc scratch: running max (group, block_q), denom (group, block_q),
            accumulator (group, block_q, hd); persist across the k grid
            dimension (sequential on TPU).
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (g, bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    # Zero padded K/V rows: out-of-bounds tile reads are garbage (NaN on
    # some backends) and 0 * NaN = NaN would poison acc through p @ v.
    kvalid = (ki * block_k +
              jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)) < seq_k
    k = jnp.where(kvalid, k, 0.0)
    v = jnp.where(kvalid, v, 0.0)

    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale  # (g, bq, bk)

    # absolute positions
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ok = kpos < seq_k  # padding guard
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= (qpos - kpos) < window
    if prefix > 0:
        ok |= (qpos < prefix) & (kpos < prefix)
    ok &= qpos < seq_q
    s = jnp.where(ok[None], s, NEG_INF)

    m_prev = m_ref[...]                      # (g, bq)
    m_cur = jnp.max(s, axis=-1)              # (g, bq)
    m_new = jnp.maximum(m_prev, m_cur)
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1.
    safe = m_new > NEG_INF / 2
    alpha = jnp.where(safe, jnp.exp(m_prev - m_new), 0.0)
    p = jnp.exp(s - jnp.where(safe, m_new, 0.0)[..., None])
    p = jnp.where(ok[None], p, 0.0)

    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
        p, v, (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "prefix",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    prefix: int = 0, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q (B, Hq, Sq, hd), k/v (B, Hkv, Sk, hd) -> (B, Hq, Sq, hd)."""
    b, hq, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = 1.0 / np.sqrt(hd)

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    # (B*Hkv, group, Sq, hd) so one grid step sees every q head of its
    # kv head.
    qr = q.reshape(b, hkv, group, sq, hd).reshape(b * hkv, group, sq, hd)
    kr = k.reshape(b * hkv, sk, hd)
    vr = v.reshape(b * hkv, sk, hd)

    grid = (b * hkv, nq, nk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
            seq_q=sq, seq_k=sk, causal=causal, window=window, prefix=prefix,
            group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, group, block_q, hd),
                         lambda h, qi, ki: (h, 0, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, qi, ki: (h, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, qi, ki: (h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, group, block_q, hd),
                               lambda h, qi, ki: (h, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, sq, hd), q.dtype),
        scratch_shapes=_scratch(group, block_q, hd),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hkv, group, sq, hd).reshape(b, hq, sq, hd)


def _scratch(group: int, block_q: int, hd: int):
    return [
        pltpu.VMEM((group, block_q), jnp.float32),      # m: running max
        pltpu.VMEM((group, block_q), jnp.float32),      # l: denominator
        pltpu.VMEM((group, block_q, hd), jnp.float32),  # acc: unnormalized
    ]
