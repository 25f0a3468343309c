"""Distributed gossip backends: the paper's aggregation over a mesh axis.

At production scale each SILO is a pod (or a slice of the `data` axis);
silo s holds a full model replica (sharded over `model` inside the
silo). One DPASGD aggregation is

    w_i <- A[i,i] w_i + sum_j A[i,j] what_j

with what_j fresh over strong edges and a stale buffer over weak edges.

Two lowerings (DESIGN.md §5):

  * `gossip_dense`   — all_gather over the silo axis + weighted sum.
    Paper-faithful semantics, but moves N * |model| bytes per round no
    matter the state. This is the BASELINE the HLO collective analysis
    measures.
  * `gossip_ring_ppermute` — the optimized backend: the overlay is the
    Christofides ring, so each silo only ever exchanges with ring
    neighbours; one `lax.ppermute` per active direction moves exactly
    |model| bytes along live edges. States with isolated nodes
    (inactive directions) move strictly fewer bytes — the paper's
    cycle-time win appears structurally in the lowered HLO.

Both run inside shard_map with a named silo axis. Weak-edge staleness is
carried by `buffers` (a pytree holding the last-received left/right
neighbour models), mirroring dpasgd.py's simulation-mode semantics.

The mesh-sharded flat runtime (fl/mesh.py, DESIGN.md §16) generalizes
these two lowerings from the ring overlay to ANY CSR edge structure:
`csr_gather_all` is the all_gather backend and `csr_gather_halo` the
ppermute backend — both fetch, for one shard, the (e_per, T) source
rows of its block of dst-sorted edges; everything downstream of the
fetch (buffer refresh + `edge_aggregate`) is shard-local and identical.
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp

Params = Any


def gossip_dense(params: Params, a_matrix: jax.Array, axis: str) -> Params:
    """w_i <- sum_j A[i,j] w_j via all_gather along `axis`.

    a_matrix: (N, N) consensus matrix (replicated).
    """
    idx = jax.lax.axis_index(axis)
    row = jax.lax.dynamic_index_in_dim(a_matrix, idx, axis=0,
                                       keepdims=False)  # (N,)

    def leaf(w):
        allw = jax.lax.all_gather(w, axis)  # (N, ...)
        return jnp.tensordot(row.astype(jnp.float32),
                             allw.astype(jnp.float32), axes=1).astype(w.dtype)

    return jax.tree.map(leaf, params)


def _ring_perms(n: int):
    right = [(i, (i + 1) % n) for i in range(n)]
    left = [(i, (i - 1) % n) for i in range(n)]
    return left, right


def gossip_ring_ppermute(params: Params, buffers: dict, *,
                         coeff_self: jax.Array, coeff_left: jax.Array,
                         coeff_right: jax.Array, axis: str,
                         active_left: bool, active_right: bool,
                         use_kernel: bool = False):
    """Ring-overlay gossip with per-edge ppermute + stale buffers.

    buffers: {"left": pytree, "right": pytree} — last weights received
    from the left/right ring neighbour. `active_*` are PYTHON bools
    (static per multigraph state): an inactive direction issues NO
    collective and aggregation reads the stale buffer instead.

    coeff_*: (N,) per-silo aggregation coefficients (row of the overlay
    MH matrix, gathered to each silo's own entries).

    Returns (new_params, new_buffers).
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    left_perm, right_perm = _ring_perms(n)

    def maybe_recv(w_leaf, buf_leaf, perm, active):
        if not active:
            return buf_leaf
        return jax.lax.ppermute(w_leaf, axis, perm)

    # receive fresh models over active directions (right perm sends my
    # model to my right neighbour => I RECEIVE my LEFT neighbour's model)
    recv_from_left = jax.tree.map(
        lambda w, b: maybe_recv(w, b, right_perm, active_right),
        params, buffers["left"])
    recv_from_right = jax.tree.map(
        lambda w, b: maybe_recv(w, b, left_perm, active_left),
        params, buffers["right"])

    cs = jax.lax.dynamic_index_in_dim(coeff_self, idx, keepdims=False)
    cl = jax.lax.dynamic_index_in_dim(coeff_left, idx, keepdims=False)
    cr = jax.lax.dynamic_index_in_dim(coeff_right, idx, keepdims=False)

    if use_kernel:
        # Pack the whole replica flat and combine in ONE kernel call
        # (one HBM pass over 3 * |model| bytes) instead of one
        # per-leaf kernel launch each; see repro/fl/flat.py.
        from repro.fl.flat import make_flat_spec, ravel, unravel
        from repro.kernels.gossip_combine.ops import gossip_combine
        spec = make_flat_spec(params)
        stacked = jnp.stack([ravel(spec, params),
                             ravel(spec, recv_from_left),
                             ravel(spec, recv_from_right)])
        coeffs = jnp.stack([cs, cl, cr]).astype(jnp.float32)
        new = unravel(spec, gossip_combine(stacked, coeffs))
    else:
        def leaf(w, lw, rw):
            acc = (cs.astype(jnp.float32) * w.astype(jnp.float32) +
                   cl.astype(jnp.float32) * lw.astype(jnp.float32) +
                   cr.astype(jnp.float32) * rw.astype(jnp.float32))
            return acc.astype(w.dtype)

        new = jax.tree.map(leaf, params, recv_from_left, recv_from_right)

    return new, {"left": recv_from_left, "right": recv_from_right}


def ring_coefficients(n: int) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Overlay MH coefficients of an n-ring: every node has degree 2,

    so every neighbour weight is 1/3 and self 1/3. For n == 2 the ring
    degenerates to a single pair (degree 1): 1/2, 1/2, 0."""
    if n == 2:
        return (jnp.full((n,), 0.5), jnp.full((n,), 0.5), jnp.zeros((n,)))
    third = jnp.full((n,), 1.0 / 3.0)
    return third, third, third


def init_ring_buffers(params: Params) -> dict:
    """Stale buffers start as the silo's own weights (identical init)."""
    return {"left": jax.tree.map(jnp.copy, params),
            "right": jax.tree.map(jnp.copy, params)}


# ---------------------------------------------------------------------------
# CSR cross-shard edge-source gather (the mesh runtime's collectives)
# ---------------------------------------------------------------------------
#
# Both backends run inside shard_map on a 1-D silo-axis mesh where shard
# p holds rows [p*per, (p+1)*per) of the global (Np, T) param matrix and
# the contiguous block of dst-sorted edges whose destinations it owns.
# They return the (e_per, T) matrix of SOURCE rows for this shard's
# edges; per-shard index tables arrive pre-sliced (the caller passes the
# (D, ·) table through shard_map with a silo-axis in_spec, so each body
# sees only its own (1, ·) row).


def csr_gather_all(w: jax.Array, src_global: jax.Array,
                   axis: str) -> jax.Array:
    """all_gather backend: materialize the full (Np, T) matrix, then a
    static row gather. Moves Np*T elements per shard regardless of how
    many edges actually cross shard boundaries — the baseline.

    w (per, T) this shard's rows; src_global (e_per,) GLOBAL src row of
    each of this shard's edges (pad edges may point anywhere valid).
    """
    w_all = jax.lax.all_gather(w, axis, axis=0, tiled=True)  # (Np, T)
    return w_all[src_global]


def csr_gather_halo(w: jax.Array, send_idx: Sequence[jax.Array],
                    perms: Sequence[Sequence[tuple[int, int]]],
                    gather_idx: jax.Array, axis: str) -> jax.Array:
    """ppermute halo backend: move ONLY the rows that cross a shard
    boundary. One ppermute per active shard-offset o: every shard sends
    its send_idx[o] rows to shard (p+o) % D simultaneously, then the
    needed rows are picked from the virtual concat

        [ my rows (per) | halo from offset o1 | halo from offset o2 | … ]

    via a per-shard static `gather_idx` derived once from the CSR
    structure at plan-build time (fl/mesh.py). States whose strong edges
    stay within shards move strictly fewer bytes — the multigraph's
    cycle-time win appears structurally in the lowered HLO, exactly as
    `gossip_ring_ppermute` did for the ring special case.

    send_idx[k] (H_k,) LOCAL rows this shard contributes to offset k's
    exchange; perms[k] the offset's (src, dst) shard pairs; gather_idx
    (e_per,) index into the virtual concat for each of my edges.
    """
    parts = [w]
    for idx_k, perm_k in zip(send_idx, perms):
        parts.append(jax.lax.ppermute(w[idx_k], axis, perm_k))
    stacked = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return stacked[gather_idx]


def fabric_rows_per_round(backend: str, *, halo_rows: int, num_shards: int,
                          rows_padded: int) -> int:
    """Total param rows the gather backend moves across the fabric per
    round, summed over all shards — the obs layer's `fabric_bytes`
    column divides into this times the flat row size.

    "halo" ships each shard's boundary-crossing rows only (`halo_rows`
    per shard, from `HaloPlan`); "all_gather" materializes the full
    padded matrix on every shard.
    """
    if backend == "halo":
        return num_shards * halo_rows
    if backend == "all_gather":
        return num_shards * rows_padded
    raise ValueError(f"unknown gossip backend {backend!r}")
