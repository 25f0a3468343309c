"""Production mesh builders + the FL silo mesh.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is the FL SILO axis: each pod is one cross-silo federated
participant holding a full model replica (DESIGN.md §3/§5).

`fl_mesh` is the flat FL runtime's mesh (DESIGN.md §16): a 1-D mesh
with a named ``silo`` axis over however many devices the host exposes;
`silo_assignment` maps a `networks/zoo.py` network's silos onto mesh
coordinates in contiguous blocks (shard p owns silo rows
``[p*per, (p+1)*per)``, padded at the top end so every shard holds the
same number of rows — shard_map needs equal blocks).

Defined as FUNCTIONS so importing this module never touches jax device
state. The dry-run process sets xla_force_host_platform_device_count
BEFORE any jax import (see dryrun.py); ordinary processes (tests,
benches) see 1 device and build 1-shard meshes unless launched with the
flag themselves.
"""

from __future__ import annotations

import dataclasses

import numpy as np


# ---------------------------------------------------------------------------
# production meshes (dry-run / serving)
# ---------------------------------------------------------------------------


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — launch "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    dev = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


def make_debug_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Reduced mesh for CI-scale dry-run tests (8 host devices)."""
    import jax

    n = int(np.prod(shape))
    dev = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


# ---------------------------------------------------------------------------
# FL silo mesh (DESIGN.md §16)
# ---------------------------------------------------------------------------

FL_AXIS = "silo"


def fl_mesh(num_shards: int | None = None, *, axis: str = FL_AXIS):
    """1-D device mesh with a named silo axis for the sharded FL runtime.

    ``num_shards=None`` takes every device the host exposes (1 in an
    ordinary CPU process; 8 under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
    """
    import jax

    devices = jax.devices()
    d = len(devices) if num_shards is None else int(num_shards)
    if d < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if d > len(devices):
        raise RuntimeError(
            f"fl_mesh({d}) needs {d} devices, have {len(devices)} — launch "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count={d}")
    return jax.sharding.Mesh(np.asarray(devices[:d]), (axis,))


@dataclasses.dataclass(frozen=True)
class SiloAssignment:
    """Contiguous-block mapping of N silos onto a D-shard silo axis.

    Shard p owns global rows ``[p*per_shard, (p+1)*per_shard)``; rows
    ``>= num_silos`` are inert padding (no edges reference them, their
    losses are sliced away, and the pad batch rows replicate silo 0 so
    every gradient stays finite).
    """

    num_silos: int
    num_shards: int
    axis: str = FL_AXIS

    @property
    def per_shard(self) -> int:
        return -(-self.num_silos // self.num_shards)  # ceil div

    @property
    def rows_padded(self) -> int:
        return self.per_shard * self.num_shards

    def shard_of(self, rows) -> np.ndarray:
        """Owning shard of each global row index."""
        return np.asarray(rows, np.int64) // self.per_shard

    def local_of(self, rows) -> np.ndarray:
        """Row index within the owning shard's block."""
        return np.asarray(rows, np.int64) % self.per_shard


def silo_assignment(num_silos: int, mesh_or_shards, *,
                    axis: str = FL_AXIS) -> SiloAssignment:
    """Map a network's silos onto a silo-axis mesh (or a shard count)."""
    if isinstance(mesh_or_shards, int):
        d = mesh_or_shards
    else:
        d = int(dict(zip(mesh_or_shards.axis_names,
                         mesh_or_shards.devices.shape))[axis])
    return SiloAssignment(num_silos=int(num_silos), num_shards=d, axis=axis)
