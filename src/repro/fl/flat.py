"""Flat-parameter packing for the FL runtime (DESIGN.md §9).

The simulation keeps N silo replicas and 2E edge buffers of the same
model. Stored as pytrees this means every aggregation/refresh op runs
once per leaf — dozens of small HBM-bound dispatches per round. This
module packs a pytree into ONE contiguous fp32 vector (and a stacked
pytree into one `(N, T)` matrix) with an exact unravel spec, so the hot
path streams a single buffer:

    spec = make_flat_spec(params)           # from one replica
    flat = ravel(spec, params)              # (T,)
    back = unravel(spec, flat)              # == params (bitwise in f32)
    mat  = ravel_stacked(spec, stacked)     # leaves (N, ...) -> (N, T)

Unravel is slices + reshapes only, so taking `jax.grad` through
`loss(unravel(spec, v))` yields the flat gradient with no extra
arithmetic — local SGD, buffer refresh and edge aggregation all become
single-array ops.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Any


#: Float dtypes `pin_dtype` knows the matching unsigned-integer width
#: for. (float8 variants are absent on purpose: no FL runtime trains
#: them and their XLA:CPU lowering promotes through f32 anyway.)
_PIN_UINT_OF = {
    jnp.dtype(jnp.float16): jnp.uint16,
    jnp.dtype(jnp.bfloat16): jnp.uint16,
    jnp.dtype(jnp.float32): jnp.uint32,
    jnp.dtype(jnp.float64): jnp.uint64,
}


def pin_dtype(x: jax.Array, step: jax.Array) -> jax.Array:
    """Pin ``x`` to its rounded floating-point value across layouts.

    XLA:CPU lets LLVM contract ``a*b + c`` into an FMA, and whether it
    fires depends on how the surrounding computation was fused — the
    flat `(N, T)` runtime and the per-leaf pytree oracle got
    DIFFERENT contractions for the momentum update, so the two drifted
    by ulps (the one gap in the flat-vs-oracle bitwise equivalence).
    `jax.default_matmul_precision` only pins dot precision and the
    obvious barriers are erased before LLVM sees them
    (`lax.optimization_barrier` does not survive elementwise fusion,
    and identity `reduce_precision`/double-bitcasts are simplified
    away), so this helper routes the value through an integer xor with
    an *opaque zero* — ``step >> 31`` for a non-negative traced int32
    ``step`` is always 0 at runtime, but the compiler cannot prove it,
    so the product must be rounded before the add. Apply it to the
    multiply feeding an add/sub and the pattern is pinned to
    mul-then-add in every layout.

    Works for every float dtype with a known uint bitcast width
    (f16/bf16/f32/f64). The opaque zero is derived in uint32 FIRST and
    only then narrowed: casting a large ``step`` (>= 2**15) straight to
    uint16 could set the shifted-out high bit and the xor would flip a
    real mantissa bit. Other dtypes pass through unchanged.
    """
    uint = _PIN_UINT_OF.get(x.dtype)
    if uint is None:
        return x
    zero = lax.shift_right_logical(step.astype(jnp.uint32), jnp.uint32(31))
    u = lax.bitcast_convert_type(x, uint) ^ zero.astype(uint)
    return lax.bitcast_convert_type(u, x.dtype)


#: Backwards-compatible alias — the FL runtimes train f32 and every
#: existing call site predates the bf16/fp64 generalization.
pin_f32 = pin_dtype


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Layout of a pytree inside one flat vector."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    offsets: tuple[int, ...]   # start of each leaf in the flat vector
    size: int                  # T — total number of elements
    dtype: Any                 # storage dtype of the flat buffer

    def __len__(self) -> int:
        return self.size


def make_flat_spec(tree: Params, dtype=jnp.float32) -> FlatSpec:
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    offsets = tuple(int(o) for o in np.cumsum([0] + sizes[:-1]))
    return FlatSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    offsets=offsets, size=int(sum(sizes)), dtype=dtype)


def ravel(spec: FlatSpec, tree: Params) -> jax.Array:
    """Pytree -> (T,) in spec order."""
    leaves = spec.treedef.flatten_up_to(tree)
    return jnp.concatenate(
        [jnp.asarray(l).astype(spec.dtype).reshape(-1) for l in leaves])


def unravel(spec: FlatSpec, flat: jax.Array) -> Params:
    """(T,) -> pytree (leaf dtypes restored)."""
    leaves = []
    for shape, dt, off in zip(spec.shapes, spec.dtypes, spec.offsets):
        n = int(np.prod(shape)) if shape else 1
        leaves.append(
            jax.lax.dynamic_slice_in_dim(flat, off, n).reshape(shape)
            .astype(dt))
    return jax.tree.unflatten(spec.treedef, leaves)


def ravel_stacked(spec: FlatSpec, tree: Params) -> jax.Array:
    """Pytree with leading stack axis on every leaf -> (N, T)."""
    leaves = spec.treedef.flatten_up_to(tree)
    n = jax.tree.leaves(tree)[0].shape[0]
    return jnp.concatenate(
        [jnp.asarray(l).astype(spec.dtype).reshape(n, -1) for l in leaves],
        axis=1)


def unravel_stacked(spec: FlatSpec, flat: jax.Array) -> Params:
    """(N, T) -> pytree with leading axis N on every leaf."""
    n = flat.shape[0]
    leaves = []
    for shape, dt, off in zip(spec.shapes, spec.dtypes, spec.offsets):
        cnt = int(np.prod(shape)) if shape else 1
        leaves.append(
            jax.lax.dynamic_slice_in_dim(flat, off, cnt, axis=1)
            .reshape((n,) + shape).astype(dt))
    return jax.tree.unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# mesh-aware layout (DESIGN.md §16)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshFlatSpec:
    """FlatSpec + how its buffers shard over a silo-axis device mesh.

    The (N, T) param/opt matrix is row-sharded in contiguous blocks
    (shard p owns silo rows [p*per, (p+1)*per), N padded up to
    `rows_padded` = D*per) and the (2E, T) edge-buffer matrix is
    DST-sharded: each shard owns the block of dst-sorted edge rows its
    silos aggregate into, padded to `edges_padded` = D*e_per. Both pads
    sit at the end of each shard's block so shard_map sees equal-sized
    blocks; pad rows are inert by construction (fl/mesh.py).
    """

    spec: FlatSpec
    axis: str
    num_shards: int
    rows_padded: int      # Np = D * per
    edges_padded: int     # E_pad = D * e_per

    def partition_of(self, shape: tuple[int, ...]):
        """PartitionSpec for one state leaf: silo-sharded iff its
        leading axis is the padded row/edge axis, replicated otherwise
        (e.g. the optimizer's step scalar)."""
        from repro.launch.sharding import fl_leaf_spec
        return fl_leaf_spec(shape, self.rows_padded, self.edges_padded,
                            axis=self.axis)

    def sharding_of(self, mesh, shape: tuple[int, ...]):
        return jax.sharding.NamedSharding(mesh, self.partition_of(shape))

    def shard_tree(self, mesh, tree: Params) -> Params:
        """device_put every leaf with its NamedSharding — this is what
        pins the (N, T)/(2E, T) buffers onto the mesh."""
        return jax.tree.map(
            lambda x: jax.device_put(x, self.sharding_of(mesh, x.shape)),
            tree)
