"""Pure-jnp oracles for the gossip kernels.

`gossip_combine_ref`: out = sum_k a[k] * w[k] (fixed-K stacked form).
`edge_aggregate_ref`: the DPASGD aggregation over an arbitrary directed
edge list via `segment_sum` — exactly the lowering the per-round
oracle (`fl/dpasgd.py`) uses per leaf, applied to one flat buffer. With its edges dst-sorted by a
stable sort, the CSR kernel sums in the same order and matches this to
a few fp32 ulp (FMA contraction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gossip_combine_ref(weights: jax.Array, coeffs: jax.Array) -> jax.Array:
    """weights (K, T), coeffs (K,) -> (T,). fp32 accumulation."""
    acc = jnp.einsum("k,kt->t", coeffs.astype(jnp.float32),
                     weights.astype(jnp.float32))
    return acc.astype(weights.dtype)


def edge_aggregate_ref(w: jax.Array, buf: jax.Array, coeffs: jax.Array,
                       dst: jax.Array, diag: jax.Array) -> jax.Array:
    """w (N, T), buf (2E, T), coeffs (2E,), dst (2E,) int, diag (N,).

    out[i] = diag[i] * w[i] + sum_{e: dst[e]==i} coeffs[e] * buf[e].
    Destinations with no incoming edges get diag[i] * w[i] only.
    """
    n = w.shape[0]
    wf = w.astype(jnp.float32)
    contrib = jax.ops.segment_sum(
        coeffs.astype(jnp.float32)[:, None] * buf.astype(jnp.float32),
        dst, num_segments=n)
    out = diag.astype(jnp.float32)[:, None] * wf + contrib
    return out.astype(w.dtype)


def dense_edge_aggregate(w: jax.Array, buf: jax.Array, cmat: jax.Array,
                         diag: jax.Array) -> jax.Array:
    """Uniform in-degree lowering: buf (N*d, T) dst-sorted, cmat (N, d).

    Reshapes the sorted buffers to (N, d, T) and accumulates densely in
    ascending row order — no scatter, same accumulation order as
    `edge_aggregate_ref` up to FMA fusion. Only valid when every
    destination has exactly d incoming edges (any ring overlay: d=2).
    """
    n, d = cmat.shape
    bm = buf.reshape(n, d, -1).astype(jnp.float32)
    acc = cmat[:, 0, None] * bm[:, 0]
    for j in range(1, d):
        acc = acc + cmat[:, j, None] * bm[:, j]
    out = diag.astype(jnp.float32)[:, None] * w.astype(jnp.float32) + acc
    return out.astype(w.dtype)
