"""Fused gossip-combine / edge-aggregation Pallas TPU kernels.

Two entry points over the same idea — stream every model buffer through
VMEM exactly once per aggregation:

`gossip_combine` (fixed-K stacked form)
    w_i <- sum_k a[k] * w[k]   for a small static neighbour count K
    (the ring-overlay production path: K = 3). One grid step per
    `block_t` tile loads a (K, block_t) slab, reduces over K in fp32,
    writes a (block_t,) tile.

`edge_aggregate` (CSR form, DESIGN.md §9)
    out[i] = diag[i] * w[i] + sum_{e in row i} coeff[e] * buf[e]
    over ALL N destination silos of a round plan at once. Edges arrive
    sorted by destination with `row_ptr` offsets (classic CSR); the
    grid walks T in `block_t` tiles, each step holding the full-height
    (N, block_t) w/out tiles and the (2E, block_t) buffer slab in VMEM,
    and the kernel loops over destinations and their CSR rows inside
    the tile in fp32 — one HBM pass over the edge buffers per
    aggregation, replacing a per-leaf `segment_sum` stack (dozens of
    small HBM-bound ops).
    Rows may be empty (isolated destinations aggregate only their own
    diag-scaled weights — the paper's isolated-node mechanism).

Accumulation order matches `jax.ops.segment_sum` over dst-sorted edges
(ascending edge index within a row, `diag * w` added last), so the
kernel and the reference lowering differ by a few ulp at most, where a
compiler contracts a multiply and an add into an FMA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _combine_kernel(w_ref, a_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)          # (K, block_t)
    a = a_ref[...].astype(jnp.float32)          # (K, 1)
    o_ref[...] = jnp.sum(w * a, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def gossip_combine(weights: jax.Array, coeffs: jax.Array, *,
                   block_t: int = 65536, interpret: bool = False) -> jax.Array:
    """weights (K, T), coeffs (K,) -> (T,)."""
    k, t = weights.shape
    if t == 0:
        # Degenerate models (or empty leaves) have nothing to combine;
        # the padded-grid path below would divide by a zero block.
        return jnp.zeros((0,), weights.dtype)
    block_t = min(block_t, t)
    pad = (-t) % block_t
    if pad:
        # Zero-fill keeps the tail tile's extra columns inert: they are
        # multiplied and written but sliced off before returning.
        weights = jnp.pad(weights, ((0, 0), (0, pad)))
    tp = t + pad
    out = pl.pallas_call(
        _combine_kernel,
        grid=(tp // block_t,),
        in_specs=[
            pl.BlockSpec((k, block_t), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, tp), weights.dtype),
        interpret=interpret,
    )(weights, coeffs[:, None])
    return out[0, :t]


# ---------------------------------------------------------------------------
# CSR edge aggregation
# ---------------------------------------------------------------------------


def _edge_agg_kernel(row_ptr_ref, coeff_ref, diag_ref, w_ref, buf_ref, o_ref,
                     *, lanes: int):
    # w_ref/o_ref hold every destination's (N, block_t) tile and buf_ref
    # the (2E, block_t) slab, so no block has a 1-row second-minor dim.
    # Destinations and their CSR rows are walked in-kernel, `lanes`
    # columns at a time so a row's accumulator stays in a few vregs.
    n = o_ref.shape[0]

    def chunk(c, carry):
        cols = pl.ds(pl.multiple_of(c * lanes, lanes), lanes)

        def dest(i, carry):
            def edge(e, acc):
                row = buf_ref[pl.ds(e, 1), cols].astype(jnp.float32)
                return acc + coeff_ref[e] * row

            acc = jax.lax.fori_loop(row_ptr_ref[i], row_ptr_ref[i + 1], edge,
                                    jnp.zeros((1, lanes), jnp.float32))
            own = diag_ref[i] * w_ref[pl.ds(i, 1), cols].astype(jnp.float32)
            o_ref[pl.ds(i, 1), cols] = (own + acc).astype(o_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, n, dest, carry)

    jax.lax.fori_loop(0, o_ref.shape[1] // lanes, chunk, 0)


#: Narrowest column chunk Mosaic loads from a dynamic row: a (1, 128)
#: row is laid out in (8, 128) tiles and refused ("dynamic load with
#: unaligned indices"); from 256 lanes up it packs into whole vregs.
_MIN_LANES = 256


def _padded_rows(r: int) -> int:
    return -(-r // 8) * 8          # VMEM tiles are 8 sublanes high


def block_vmem_bytes(n: int, e2: int, block_t: int) -> int:
    """VMEM one grid step holds: Pallas double-buffers every operand, so
    two copies each of the w, out and buf tiles — 2 * (2*N + 2E) rows,
    each padded to 8 sublanes, of `block_t` fp32 columns."""
    return 2 * (2 * _padded_rows(n) + _padded_rows(e2)) * 4 * block_t


def _pick_block_t(t: int, n: int, e2: int, block_t: int,
                  vmem_budget: int = 12 << 20) -> int:
    """Largest tile of `_MIN_LANES` multiples whose blocks fit the budget
    (`block_vmem_bytes`; the budget sits under v5e's 16 MiB default
    scoped-VMEM limit)."""
    col_bytes = block_vmem_bytes(n, e2, 1)
    block_t = min(block_t, t)
    while block_t > _MIN_LANES and col_bytes * block_t > vmem_budget:
        block_t //= 2
    block_t = max(block_t // _MIN_LANES * _MIN_LANES, _MIN_LANES)
    if col_bytes * block_t > vmem_budget:
        # even the minimum tile cannot hold the (2E, block_t) slab
        raise ValueError(
            f"edge_aggregate: N={n} silos and 2E={e2} directed edges need "
            f"{col_bytes * block_t / 2**20:.1f} MiB of VMEM at the minimum "
            f"tile; use the segment_sum reference lowering for graphs "
            f"this dense")
    return block_t


def _pick_lanes(block_t: int) -> int:
    """Inner column chunk: the widest of 1024/512/256 dividing the tile."""
    return next(c for c in (1024, 512, 256) if block_t % c == 0)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def edge_aggregate(w: jax.Array, buf: jax.Array, coeffs: jax.Array,
                   row_ptr: jax.Array, diag: jax.Array, *,
                   block_t: int = 65536, interpret: bool = False) -> jax.Array:
    """CSR aggregation over dst-sorted edges.

    w (N, T); buf (2E, T) sorted by destination; coeffs (2E,) f32 in the
    same order; row_ptr (N+1,) int32; diag (N,) f32. Returns (N, T):
    out[i] = diag[i] * w[i] + sum_{row_ptr[i] <= e < row_ptr[i+1]}
    coeffs[e] * buf[e], accumulated in fp32.
    """
    n, t = w.shape
    e2 = buf.shape[0]
    if t == 0:
        return jnp.zeros((n, 0), w.dtype)
    if e2 == 0:
        return (diag[:, None].astype(jnp.float32) *
                w.astype(jnp.float32)).astype(w.dtype)
    if t < _MIN_LANES:
        # models this small pad up to one chunk (the pad is inert: its
        # columns are computed and sliced off)
        pad = ((0, 0), (0, _MIN_LANES - t))
        return edge_aggregate(jnp.pad(w, pad), jnp.pad(buf, pad), coeffs,
                              row_ptr, diag, block_t=block_t,
                              interpret=interpret)[:, :t]
    block_t = _pick_block_t(t, n, e2, block_t)
    # Ragged grid: Pallas masks the tail tile itself (reads beyond T are
    # don't-cares that stay in the tail columns elementwise; writes are
    # clipped) — no host-side jnp.pad, so the per-round scan never makes
    # an HBM copy of the (2E, T) buffers just to round T up.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(pl.cdiv(t, block_t),),
        in_specs=[
            pl.BlockSpec((n, block_t), lambda j, *_: (0, j)),    # w tile
            pl.BlockSpec((e2, block_t), lambda j, *_: (0, j)),   # buf slab
        ],
        out_specs=pl.BlockSpec((n, block_t), lambda j, *_: (0, j)),
    )
    out = pl.pallas_call(
        functools.partial(_edge_agg_kernel, lanes=_pick_lanes(block_t)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, t), w.dtype),
        interpret=interpret,
    )(row_ptr.astype(jnp.int32), coeffs.astype(jnp.float32),
      diag.astype(jnp.float32), w, buf)
    return out
