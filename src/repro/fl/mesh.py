"""Mesh-sharded flat FL runtime (DESIGN.md §16).

The flat runtime (fl/runtime.py) packs all N silo replicas into one
(N, T) matrix and the 2E directed-edge buffers into one dst-sorted
(2E, T) matrix, and runs a whole multigraph cycle as one jitted
`lax.scan`. This module runs the SAME cycle sharded over a 1-D device
mesh with a named ``silo`` axis, bit-for-bit equal to the single-device
program (which stays the oracle):

  * silos shard in contiguous blocks — shard p owns param rows
    ``[p*per, (p+1)*per)``, N padded at the top to ``Np = D*per``
    (launch/mesh.py `silo_assignment`);
  * edges are DST-sharded: because the flat runtime keeps edges sorted
    by destination, each shard's edges are one contiguous slice of the
    sorted order, padded per shard to ``e_per`` rows. Pad edges carry
    ``strong=False``, coefficient 0, and a local destination of ``per``
    — one past the shard's last row — so `segment_sum` DROPS them
    entirely (out-of-range ids contribute to no segment): they never
    touch the sums, not even as +0.0, which is what keeps the shard and
    oracle programs bit-identical;
  * per round, the source rows of each shard's edges are fetched by one
    of two `fl/gossip.py` collectives — `csr_gather_all` (all_gather
    baseline) or `csr_gather_halo` (ppermute halo exchange moving only
    boundary-crossing rows, derived here once from the CSR structure at
    plan-build time); refresh + `edge_aggregate` stay shard-local;
  * the whole-cycle scan body becomes ONE `shard_map` program inside
    one jit — still a single dispatch per cycle, and the cycle function
    keeps the single-device EXTERNAL signature
    ``cycle(state, batches, strong, coeffs, diag)`` with plan slices in
    the oracle's dst-sorted layout (padding/permuting happens inside
    the jit), so the controller's live-swap contract (zero recompiles
    on schedule swap) survives untouched.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.fl import flat as flatmod
from repro.fl import gossip
from repro.fl.runtime import (SCOPE_AGGREGATE, SCOPE_LOCAL_SGD,
                              SCOPE_REFRESH, FlatFLState, FlatRuntime)
from repro.kernels.gossip_combine.ref import edge_aggregate_ref
from repro.launch import mesh as meshmod
from repro.launch.sharding import fl_plan_specs

Params = Any


@dataclasses.dataclass(frozen=True)
class HaloPlan:
    """Static ppermute exchange plan, derived once from the CSR edges.

    For each active shard-offset o, every shard q sends the local rows
    ``send_idx[k][q]`` to shard ``(q+o) % D`` in one ppermute; a shard's
    needed source rows are then picked out of the virtual concat
    ``[own rows | halo(o1) | halo(o2) | …]`` by ``gather_idx``. Offsets
    nobody needs issue NO collective at all.
    """

    offsets: tuple[int, ...]            # active offsets, ascending
    send_idx: tuple[np.ndarray, ...]    # per offset: (D, H_o) local rows
    perms: tuple[tuple[tuple[int, int], ...], ...]
    gather_idx: np.ndarray              # (D, e_per) into the virtual concat

    @property
    def halo_rows(self) -> int:
        """Rows moved per shard per round (the ppermute traffic)."""
        return int(sum(t.shape[1] for t in self.send_idx))


@dataclasses.dataclass(frozen=True)
class MeshRuntime:
    """Sharded twin of `FlatRuntime`: same plan, mesh block layout.

    Forwards the oracle runtime's plan attributes so trainer/controller
    code treats both runtimes uniformly — callers keep passing plan
    slices in the single-device dst-sorted layout.
    """

    rt: FlatRuntime
    mesh: Any                 # jax.sharding.Mesh, 1-D silo axis
    axis: str
    assign: meshmod.SiloAssignment
    mspec: flatmod.MeshFlatSpec
    edge_counts: np.ndarray   # (D,) real edges per shard
    edge_perm: np.ndarray     # (E_pad,) -> sorted edge idx, sentinel 2E = pad
    dst_local: np.ndarray     # (D, e_per) int32; pad -> per (dropped)
    src_global: np.ndarray    # (D, e_per) int32 global src row; pad -> 0
    halo: HaloPlan

    # ---- FlatRuntime forwarding -------------------------------------
    @property
    def spec(self):
        return self.rt.spec

    @property
    def num_silos(self) -> int:
        return self.rt.num_silos

    @property
    def order(self):
        return self.rt.order

    @property
    def row_ptr(self):
        return self.rt.row_ptr

    @property
    def src_sorted(self):
        return self.rt.src_sorted

    @property
    def dst_sorted(self):
        return self.rt.dst_sorted

    @property
    def strong(self):
        return self.rt.strong

    @property
    def coeffs(self):
        return self.rt.coeffs

    @property
    def diag(self):
        return self.rt.diag

    @property
    def num_rounds_cycle(self) -> int:
        return self.rt.num_rounds_cycle

    def expand_pair_mask(self, pair_mask: np.ndarray) -> np.ndarray:
        return self.rt.expand_pair_mask(pair_mask)

    # ---- mesh geometry ----------------------------------------------
    @property
    def num_shards(self) -> int:
        return self.assign.num_shards

    @property
    def per_rows(self) -> int:
        return self.assign.per_shard

    @property
    def edges_per_shard(self) -> int:
        return int(self.dst_local.shape[1])


def _build_halo(counts: np.ndarray, src_global: np.ndarray, d: int,
                per: int) -> HaloPlan:
    """Derive the ppermute plan from each shard's edge source rows."""
    e_per = src_global.shape[1]
    # sends[o][q]: sorted unique local rows shard q ships to (q+o) % d
    sends: dict[int, list[np.ndarray]] = {}
    for o in range(1, d):
        per_sender = []
        for q in range(d):
            p = (q + o) % d
            srcs = src_global[p, :int(counts[p])]
            mine = np.unique(srcs[srcs // per == q]) % per
            per_sender.append(mine.astype(np.int32))
        if any(len(x) for x in per_sender):
            sends[o] = per_sender
    offsets = tuple(sorted(sends))
    send_idx = []
    for o in offsets:
        h = max(len(x) for x in sends[o])
        tbl = np.zeros((d, h), np.int32)  # short senders resend row 0
        for q, x in enumerate(sends[o]):
            tbl[q, :len(x)] = x
        send_idx.append(tbl)
    base = {}
    acc = per
    for o, tbl in zip(offsets, send_idx):
        base[o] = acc
        acc += tbl.shape[1]
    gather_idx = np.zeros((d, e_per), np.int32)
    for p in range(d):
        for k in range(int(counts[p])):
            s = int(src_global[p, k])
            q = s // per
            if q == p:
                gather_idx[p, k] = s % per
            else:
                o = (p - q) % d
                pos = int(np.searchsorted(sends[o][q], s % per))
                gather_idx[p, k] = base[o] + pos
    perms = tuple(tuple((q, (q + o) % d) for q in range(d)) for o in offsets)
    return HaloPlan(offsets=offsets, send_idx=tuple(send_idx), perms=perms,
                    gather_idx=gather_idx)


def block_layout(dst_sorted: np.ndarray, src_sorted: np.ndarray, d: int,
                 per: int) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                    np.ndarray]:
    """Per-shard edge tables for a contiguous block row layout.

    Returns (counts (D,), edge_perm (D*e_per,), dst_local (D, e_per),
    src_global (D, e_per)); pad edges get `edge_perm = 2E` (sentinel),
    local dst `per` (dropped by segment_sum), global src 0.
    """
    e2 = int(dst_sorted.shape[0])
    # dst-sorted => each shard's edges are one contiguous run
    bounds = np.searchsorted(dst_sorted, np.arange(d + 1) * per)
    counts = np.diff(bounds).astype(np.int64)
    e_per = int(counts.max()) if d > 0 and counts.size else 0
    edge_perm = np.full((d * e_per,), e2, np.int64)
    dst_local = np.full((d, e_per), per, np.int32)
    src_global = np.zeros((d, e_per), np.int32)
    for p in range(d):
        c, lo = int(counts[p]), int(bounds[p])
        edge_perm[p * e_per: p * e_per + c] = np.arange(lo, lo + c)
        dst_local[p, :c] = dst_sorted[lo:lo + c] - p * per
        src_global[p, :c] = src_sorted[lo:lo + c]
    return counts, edge_perm, dst_local, src_global


def make_mesh_runtime(rt: FlatRuntime, mesh=None, *,
                      axis: str = meshmod.FL_AXIS) -> MeshRuntime:
    """Lay the runtime's CSR plan out over a silo-axis mesh, host-side.

    ``mesh`` may be a Mesh, a shard count, or None (every device the
    host exposes). All index tables — block bounds, pad edges, the halo
    exchange — are derived here ONCE; nothing about the layout depends
    on which schedule the cycle later runs.
    """
    if mesh is None or isinstance(mesh, int):
        mesh = meshmod.fl_mesh(mesh, axis=axis)
    assign = meshmod.silo_assignment(rt.num_silos, mesh, axis=axis)
    d, per = assign.num_shards, assign.per_shard
    counts, edge_perm, dst_local, src_global = block_layout(
        rt.dst_sorted, rt.src_sorted, d, per)
    mspec = flatmod.MeshFlatSpec(spec=rt.spec, axis=axis, num_shards=d,
                                 rows_padded=assign.rows_padded,
                                 edges_padded=int(edge_perm.shape[0]))
    return MeshRuntime(rt=rt, mesh=mesh, axis=axis, assign=assign,
                       mspec=mspec, edge_counts=counts, edge_perm=edge_perm,
                       dst_local=dst_local, src_global=src_global,
                       halo=_build_halo(counts, src_global, d, per))


def init_mesh_state(init_params: Callable[[jax.Array], Params], opt,
                    mrt: MeshRuntime, key: jax.Array) -> FlatFLState:
    """Mirror of `init_flat_state` in padded mesh layout: pad rows get
    the same identical-init replica (their values are never read), and
    every array is device_put with its NamedSharding."""
    keys = jax.random.split(key, mrt.num_silos)
    p0 = init_params(keys[0])  # identical init across silos
    w0 = flatmod.ravel(mrt.spec, p0)
    w = jnp.broadcast_to(w0[None],
                         (mrt.mspec.rows_padded, mrt.spec.size)).copy()
    opt_state = opt.init(w)
    buffers = w[jnp.asarray(mrt.src_global.reshape(-1))]
    return mrt.mspec.shard_tree(mrt.mesh, FlatFLState(w, opt_state, buffers))


def gather_flat_state(mrt: MeshRuntime, state: FlatFLState) -> FlatFLState:
    """Mesh-layout state -> the oracle's single-device layout (host).

    Drops pad rows and maps the block-padded edge buffers back to the
    dst-sorted order; the result compares bit-for-bit against a
    single-device `FlatFLState` (tests/test_fl_mesh.py).
    """
    n = mrt.num_silos
    e2 = int(mrt.rt.dst_sorted.shape[0])
    real = np.flatnonzero(mrt.edge_perm < e2)  # ascending == sorted order
    w = np.asarray(jax.device_get(state.w))[:n]
    buffers = np.asarray(jax.device_get(state.buffers))[real]
    rows_padded = mrt.mspec.rows_padded

    def unpad(x):
        a = np.asarray(jax.device_get(x))
        if a.ndim >= 1 and a.shape[0] == rows_padded:
            return a[:n]
        return a

    opt_state = jax.tree.map(unpad, state.opt_state)
    return FlatFLState(jnp.asarray(w), jax.tree.map(jnp.asarray, opt_state),
                       jnp.asarray(buffers))


def make_mesh_cycle_fn(mrt: MeshRuntime, *, loss_fn, opt, lr_scale=1.0,
                       gossip_backend: str = "halo",
                       donate: bool | None = None,
                       metrics=None):
    """Sharded twin of `runtime.make_cycle_fn` — same external contract.

    Returns ``cycle(state, batches, strong, coeffs, diag)`` taking plan
    slices in the ORACLE's dst-sorted layout (``(R, 2E)``/``(R, N)``)
    and batches with leaves ``(R, u, N, b, ...)``; the pad/permute to
    mesh block layout happens inside the jit, so every existing caller
    (trainer loop, controller live-swap, TTA frontier) works unchanged
    and a schedule swap is still just new runtime arguments — zero
    recompiles, ``cycle.trace_count["count"]`` stays 1.

    gossip_backend: "halo" (ppermute exchange of boundary-crossing rows,
    the optimized path) or "all_gather" (full-matrix baseline). Both are
    bit-for-bit equal to the oracle: they differ only in how the same
    source rows reach the shard.

    metrics: `obs.MetricsSpec` — same contract as the flat runtime
    (third `(R, K)` output, Python-level branching, `metrics=None`
    traces the exact pre-obs program). Reductions here cross shards via
    psum/all_gather, so metric VALUES may differ from the flat
    runtime's by association order; the mesh appends one extra column,
    `fabric_bytes` — the physical collective traffic per round (halo
    rows or the all_gather matrix), which has no flat analogue.
    """
    if gossip_backend not in ("halo", "all_gather"):
        raise ValueError(f"unknown gossip backend {gossip_backend!r}")
    if donate is None:
        donate = jax.default_backend() != "cpu"
    mesh, axis = mrt.mesh, mrt.axis
    n, per = mrt.num_silos, mrt.per_rows
    rows_padded = mrt.mspec.rows_padded
    spec = mrt.spec
    plan_specs = fl_plan_specs(axis=axis)
    row_spec = P(axis, None)

    edge_perm = jnp.asarray(mrt.edge_perm)
    dst_local = jnp.asarray(mrt.dst_local)
    src_global = jnp.asarray(mrt.src_global)
    gather_idx = jnp.asarray(mrt.halo.gather_idx)
    send_tbls = tuple(jnp.asarray(t) for t in mrt.halo.send_idx)
    perms = mrt.halo.perms
    counter = {"count": 0}
    ms = metrics
    if ms is not None:
        from repro.fl.gossip import fabric_rows_per_round
        from repro.obs import metrics as obsmet
        e2 = int(mrt.rt.dst_sorted.shape[0])
        e_per = mrt.edges_per_shard
        row_bytes = float(spec.size * 4)
        fabric_bytes = fabric_rows_per_round(
            gossip_backend, halo_rows=mrt.halo.halo_rows,
            num_shards=mrt.num_shards,
            rows_padded=rows_padded) * row_bytes

    def flat_loss(w_row, batch):
        return loss_fn(flatmod.unravel(spec, w_row), batch)

    def body(w, os_, buf, batches, strong, coeffs, diag,
             dst_l, src_g, gath, *sends):
        # per-shard rows of the (D, ·) index tables arrive as (1, ·)
        dst_l, src_g, gath = dst_l[0], src_g[0], gath[0]
        sends = tuple(s[0] for s in sends)
        if ms is not None:
            # pads never contribute: mask rows >= n and edges whose
            # local dst is the `per` drop-sentinel before any reduction
            shard = jax.lax.axis_index(axis)
            row_mask = ((shard * per + jnp.arange(per)) < n
                        ).astype(jnp.float32)[:, None]
            edge_mask = (dst_l < per).astype(jnp.float32)

        def round_body(carry, xs):
            # same obs inertness contract as the flat runtime: the
            # `ms is not None` branches are Python-level, so with
            # metrics off this is the seed program op-for-op
            if ms is None:
                w, os_, buf = carry
            else:
                w, os_, buf, age = carry
                w0 = w
            batch, strong_r, coeffs_r, diag_r = xs

            def local_step(c, batch_u):
                w, os_ = c
                loss, grads = jax.vmap(
                    jax.value_and_grad(flat_loss))(w, batch_u)
                w, os_ = opt.update(w, grads, os_, lr_scale)
                if ms is None or not ms.grad_norm:
                    return (w, os_), loss
                gsq_u = jnp.sum(jnp.square(grads.astype(jnp.float32))
                                * row_mask)
                return (w, os_), (loss, gsq_u)

            with jax.named_scope(SCOPE_LOCAL_SGD):
                (w, os_), ys = jax.lax.scan(local_step, (w, os_), batch)
            if ms is None or not ms.grad_norm:
                losses = ys
            else:
                losses, gsq_u = ys

            # cross-shard fetch of this shard's edge SOURCE rows, then
            # shard-local refresh + aggregation (pad edges dropped by
            # segment_sum's out-of-range semantics)
            with jax.named_scope(SCOPE_REFRESH):
                if gossip_backend == "halo":
                    rows = gossip.csr_gather_halo(w, sends, perms, gath,
                                                  axis)
                else:
                    rows = gossip.csr_gather_all(w, src_g, axis)
                buf = jnp.where(strong_r[:, None], rows, buf)
            with jax.named_scope(SCOPE_AGGREGATE):
                w = edge_aggregate_ref(w, buf, coeffs_r, dst_l, diag_r)

            # Reported loss: mean over REAL silos only, at the oracle's
            # (u, N) reduce shape. The training STATE stays bit-exact;
            # this scalar may drift from the oracle by ~1 ulp on some
            # rounds because XLA's reduce-to-scalar emitter vectorizes
            # differently inside the two loop programs — a reporting
            # artifact, tolerated in tests (DESIGN.md §16).
            with jax.named_scope(SCOPE_LOCAL_SGD):
                la = jax.lax.all_gather(losses, axis, axis=1, tiled=True)
                loss = jnp.mean(la[:, :n])
            if ms is None:
                return (w, os_, buf), loss

            vals = {}
            if ms.grad_norm:
                vals["gsq"] = jax.lax.psum(jnp.sum(gsq_u), axis)
            if ms.param_norm:
                vals["psq"] = jax.lax.psum(
                    jnp.sum(jnp.square(w) * row_mask), axis)
            if ms.update_norm:
                vals["usq"] = jax.lax.psum(
                    jnp.sum(jnp.square(w - w0) * row_mask), axis)
            if ms.silo_loss:
                vals["silo_loss"] = jnp.mean(la[:, :n], axis=0)
            n_strong = jax.lax.psum(  # pads carry strong=False already
                jnp.sum(strong_r.astype(jnp.float32)), axis)
            age = jnp.where(strong_r, 0.0, age + 1.0)
            if ms.staleness:
                vals["stale_frac"] = 1.0 - n_strong / e2
                vals["buf_age"] = jax.lax.psum(
                    jnp.sum(age * edge_mask), axis) / e2
            if ms.traffic:
                vals["gossip_bytes"] = n_strong * row_bytes
                vals["fabric_bytes"] = jnp.float32(fabric_bytes)
            row = obsmet.assemble_row(ms, vals)
            return (w, os_, buf, age), (loss, row)

        carry = (w, os_, buf)
        if ms is not None:
            carry = carry + (jnp.zeros((e_per,), jnp.float32),)
        carry, ys = jax.lax.scan(round_body, carry,
                                 (batches, strong, coeffs, diag))
        if ms is None:
            return carry + (ys,)
        return carry[:3] + ys

    def cycle(state, batches, strong, coeffs, diag):
        counter["count"] += 1
        r = strong.shape[0]
        # oracle layout -> mesh block layout (inside the jit): appended
        # sentinel column = the pad edges' strong=False / coeff 0
        strong_p = jnp.concatenate(
            [strong, jnp.zeros((r, 1), strong.dtype)], 1)[:, edge_perm]
        coeffs_p = jnp.concatenate(
            [coeffs, jnp.zeros((r, 1), coeffs.dtype)], 1)[:, edge_perm]
        diag_p = diag if rows_padded == n else jnp.concatenate(
            [diag, jnp.ones((r, rows_padded - n), diag.dtype)], 1)

        def pad_batch(b):
            if rows_padded == n:
                return b
            tile = jnp.broadcast_to(  # pad silos re-train silo 0's batch
                b[:, :, :1], b.shape[:2] + (rows_padded - n,) + b.shape[3:])
            return jnp.concatenate([b, tile], axis=2)

        batches_p = jax.tree.map(pad_batch, batches)

        os_spec = jax.tree.map(lambda x: mrt.mspec.partition_of(x.shape),
                               state.opt_state)
        batch_spec = jax.tree.map(
            lambda b: P(None, None, axis, *([None] * (b.ndim - 3))),
            batches_p)
        table = plan_specs["table"]
        in_specs = (row_spec, os_spec, row_spec, batch_spec,
                    plan_specs["edge_rounds"], plan_specs["edge_rounds"],
                    plan_specs["diag_rounds"],
                    table, table, table, *([table] * len(send_tbls)))
        out_specs = (row_spec, os_spec, row_spec, P())
        if ms is not None:
            out_specs = out_specs + (P(),)  # metrics replicated
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        out = fn(state.w, state.opt_state, state.buffers,
                 batches_p, strong_p, coeffs_p, diag_p,
                 dst_local, src_global, gather_idx,
                 *send_tbls)
        if ms is None:
            w, os2, buf, losses = out
            return FlatFLState(w, os2, buf), losses
        w, os2, buf, losses, mets = out
        return FlatFLState(w, os2, buf), losses, mets

    jitted = jax.jit(cycle, donate_argnums=(0,) if donate else ())
    jitted.trace_count = counter
    if ms is not None:
        jitted.metric_columns = ms.columns(n, mesh=True)
    return jitted
