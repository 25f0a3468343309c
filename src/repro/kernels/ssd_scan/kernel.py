"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation: the Mamba2 CUDA kernel leans on warp-level scans; on TPU
we use the SSD *dual form* — per chunk a (Q,Q) attention-like matmul
(MXU work) plus a rank-N recurrent state carried in VMEM scratch across
the chunk grid dimension (sequential on TPU). This keeps all per-chunk
operands in VMEM: for Q=256, P=64, N=128 the working set is
Q*(P+2N) + Q*Q + P*N floats ~= 0.6 MB, far under the ~16 MB VMEM budget,
and every matmul has MXU-aligned contracting dims.

Grid: (batch*heads, num_chunks), chunks innermost. B/C are shared across
heads (Mamba2 single-group), so their index_map folds the head away.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, o_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)        # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)  # (1, Q)
    A = a_ref[pl.program_id(0)]                # scalar (SMEM)
    B = b_ref[0, 0].astype(jnp.float32)        # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)        # (Q, N)

    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iq >= ik

    def to_col(row):   # (1, Q) -> (Q, 1), exact: one nonzero per sum
        return jnp.sum(jnp.where(iq == ik, row, 0.0), axis=1, keepdims=True)

    def to_row(col):   # (Q, 1) -> (1, Q), exact likewise
        return jnp.sum(jnp.where(iq == ik, col, 0.0), axis=0, keepdims=True)

    # within-chunk cumulative decay as a masked row reduction: the
    # cumsum primitive and (Q, 1) <-> (1, Q) transposes do not lower
    a_row = dt_row * A                                            # (1, Q)
    acs = jnp.sum(jnp.where(causal, a_row, 0.0), axis=1,
                  keepdims=True)                                  # (Q, 1)
    dtx = x * to_col(dt_row)                                      # (Q, P)

    # within-chunk dual form
    gap = jnp.where(causal, acs - to_row(acs), -jnp.inf)  # mask BEFORE exp
    decay = jnp.exp(gap)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(scores * decay, dtx,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # contribution of the carried inter-chunk state
    state = state_ref[...]                       # (P, N)
    y_inter = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(acs)             # (Q, P)

    o_ref[0, 0] = (y_diag + y_inter).astype(o_ref.dtype)

    # state update: decay whole chunk + inject dt-weighted inputs
    total = acs[chunk - 1:chunk, :]              # (1, 1)
    to_end = jnp.exp(total - acs)                # (Q, 1)
    inj = jax.lax.dot_general(dtx * to_end, B,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = state * jnp.exp(total) + inj


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256,
             interpret: bool = False) -> jax.Array:
    """x (b,s,h,p), dt (b,s,h), A (h,), B/C (b,s,n) -> y (b,s,h,p)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, f"seq {s} % chunk {chunk} != 0 (pad upstream)"
    nc = s // chunk

    xr = jnp.moveaxis(x, 2, 1).reshape(b * h, nc, chunk, p)
    # dt as one (1, chunk) row per chunk: the block's last two dims then
    # equal the array's, which the TPU lowering requires
    dtr = jnp.moveaxis(dt, 2, 1).reshape(b * h, nc, 1, chunk)
    ar = jnp.broadcast_to(A[None, :], (b, h)).reshape(b * h)
    br = B.reshape(b, nc, chunk, n)
    cr = C.reshape(b, nc, chunk, n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,            # A: one f32 per (batch, head)
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bh, c, _: (bh, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bh, c, _: (bh, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bh, c, _, _h=h: (bh // _h, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bh, c, _, _h=h: (bh // _h, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda bh, c, _: (bh, c, 0, 0)),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, nc, chunk, p), x.dtype),
        interpret=interpret,
    )(ar.astype(jnp.float32), xr, dtr, br, cr)
    return jnp.moveaxis(out.reshape(b, h, s, p), 1, 2)
