"""Whole-cycle flat-parameter FL runtime (DESIGN.md §9).

The only FL runtime the trainers run. Its oracle, the per-round step
over stacked pytrees in `fl/dpasgd.py`, dispatches one jitted step per
communication round and aggregates with a per-leaf `segment_sum` over
`(2E, ...)` buffers; it lives on for the tests alone. This runtime
removes both costs:

  * all N silo replicas live in ONE contiguous `(N, T)` fp32 buffer and
    the 2E directed-edge buffers in ONE `(2E, T)` buffer (repro/fl/flat),
    kept in dst-sorted CSR order so aggregation is a single array op
    (the `edge_aggregate` Pallas kernel on TPU, its `segment_sum` twin
    on CPU);
  * a full multigraph cycle of R rounds is ONE compiled dispatch:
    `lax.scan` over the `RoundPlan`'s `(R, ·)` strong/coeffs/diag arrays
    with the state donated, so a cycle has zero host round-trips and the
    cycle function traces/compiles exactly once for a given shape.

Semantics are bit-for-bit fp32-identical to R calls of that per-round
oracle (tests/test_flat_runtime.py): the stable dst-sort keeps
`segment_sum`'s accumulation order, and local SGD/refresh are the same
elementwise ops on a packed layout.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl import flat as flatmod
from repro.fl.dpasgd import RoundPlan
from repro.kernels.gossip_combine import ops as gossip_ops
from repro.kernels.gossip_combine.ref import (dense_edge_aggregate,
                                              edge_aggregate_ref)

Params = Any

# `jax.named_scope`s of a round's three stages (metadata only: the ops
# and their numbers do not change). Every op of the round body lies in
# one of them, so a profiler trace splits a round's device time by
# scope through each op's HLO `op_name`; the mesh runtime uses the same
# names.
SCOPE_LOCAL_SGD = "fl.local_sgd"   # the local-step scan, the loss mean
SCOPE_REFRESH = "fl.refresh"       # strong-edge buffer refresh
SCOPE_AGGREGATE = "fl.aggregate"   # edge_aggregate (or its twins)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FlatFLState:
    """Simulation state in packed layout.

    w (N, T) flat silo params; opt_state: flat-optimizer state pytree
    ((N, T) leaves + scalars); buffers (2E, T) edge buffers in
    DST-SORTED order (buffers[e] = last weights of src(e) seen by
    dst(e), h rounds stale over weak edges).
    """

    w: jax.Array
    opt_state: Any
    buffers: jax.Array

    def tree_flatten(self):
        return (self.w, self.opt_state, self.buffers), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclasses.dataclass(frozen=True)
class FlatRuntime:
    """Host-side compiled-plan bundle: flat layout + CSR edge order."""

    spec: flatmod.FlatSpec
    num_silos: int
    order: np.ndarray        # (2E,) original-edge -> sorted position perm
    row_ptr: np.ndarray      # (N+1,) int32 CSR offsets
    src_sorted: np.ndarray   # (2E,) int32
    dst_sorted: np.ndarray   # (2E,) int32 (non-decreasing)
    strong: np.ndarray       # (R, 2E) bool, sorted edge order
    coeffs: np.ndarray       # (R, 2E) f32, sorted edge order
    diag: np.ndarray         # (R, N) f32

    @property
    def num_rounds_cycle(self) -> int:
        return self.strong.shape[0]

    def expand_pair_mask(self, pair_mask: np.ndarray) -> np.ndarray:
        """Per-PAIR rounds mask -> this runtime's dst-sorted directed
        layout (pair e owns directed edges 2e, 2e+1). This is how the
        fault layer feeds degraded strong sets to the compiled cycle
        function: same CSR structure, different runtime argument —
        a silo whose edges all go weak simply reads stale buffers
        (and an all-crashed destination row aggregates over an empty
        CSR row, which `edge_aggregate` handles by construction).
        """
        from repro.faults.degrade import pair_rounds_to_directed
        return pair_rounds_to_directed(self.order, pair_mask)


def make_flat_runtime(plan: RoundPlan, template_params: Params,
                      num_silos: int) -> FlatRuntime:
    """Sort the plan's directed edges by destination once, host-side."""
    spec = flatmod.make_flat_spec(template_params)
    order, row_ptr = gossip_ops.csr_sort(plan.dst, num_silos)
    return FlatRuntime(
        spec=spec, num_silos=num_silos, order=order, row_ptr=row_ptr,
        src_sorted=plan.src[order].astype(np.int32),
        dst_sorted=plan.dst[order].astype(np.int32),
        strong=plan.strong[:, order],
        coeffs=plan.coeffs[:, order].astype(np.float32),
        diag=plan.diag.astype(np.float32))


def init_flat_state(init_params: Callable[[jax.Array], Params], opt,
                    rt: FlatRuntime, key: jax.Array) -> FlatFLState:
    """Mirror of dpasgd.init_fl_state in packed layout (bitwise equal)."""
    keys = jax.random.split(key, rt.num_silos)
    p0 = init_params(keys[0])  # identical init across silos
    w0 = flatmod.ravel(rt.spec, p0)
    w = jnp.broadcast_to(w0[None], (rt.num_silos, rt.spec.size)).copy()
    opt_state = opt.init(w)
    buffers = w[jnp.asarray(rt.src_sorted)]
    return FlatFLState(w, opt_state, buffers)


def make_cycle_fn(rt: FlatRuntime, *, loss_fn, opt, lr_scale=1.0,
                  aggregator: str | None = None,
                  donate: bool | None = None,
                  gossip: str | None = None,
                  metrics=None):
    """Build the once-compiled whole-cycle step.

    Returns `cycle(state, batches, strong, coeffs, diag) ->
    (state, losses)` where batches has leaves `(R, u, N, b, ...)` and
    the plan slices are `(R, 2E)/(R, N)` in the runtime's sorted edge
    order. R is whatever slice of the cycle the caller passes — the jit
    specializes per R and the attached `cycle.trace_count["count"]`
    records how often tracing actually ran (the whole point: once).

    metrics: an `obs.MetricsSpec` adds a third output — an `(R, K)`
    f32 matrix of per-round scalars (column names on the returned
    function's `metric_columns`) accumulated inside the same scan, so
    the cycle is still ONE dispatch. `metrics=None` (default) branches
    at Python level only and traces the EXACT pre-obs program: state
    stays bit-identical and `trace_count` semantics are untouched
    (DESIGN.md §17, tests/test_obs.py).

    Passing a `fl/mesh.py` MeshRuntime instead builds the SHARDED twin
    of this function (same external contract, shard_map program inside;
    `gossip` picks its cross-shard backend, default "halo").

    aggregator: "kernel" (Pallas `edge_aggregate`, interpret-mode off
    TPU), "reference" (`segment_sum` twin — bit-for-bit equal to the
    oracle's per-leaf lowering), or "dense" (uniform-in-degree overlays
    only, e.g. any ring: reshapes the sorted buffers to (N, d, T) and
    reduces densely — no scatter, ~4x faster on XLA:CPU, same
    accumulation order up to FMA fusion). Default: kernel on TPU,
    reference elsewhere.
    """
    from repro.fl import mesh as flmesh  # lazy: fl.mesh imports this module
    if isinstance(rt, flmesh.MeshRuntime):
        if aggregator not in (None, "reference"):
            raise ValueError("the mesh runtime aggregates per shard via "
                             f"segment_sum; aggregator={aggregator!r} is "
                             "single-device only")
        return flmesh.make_mesh_cycle_fn(
            rt, loss_fn=loss_fn, opt=opt, lr_scale=lr_scale,
            gossip_backend=gossip or "halo", donate=donate,
            metrics=metrics)
    if gossip is not None:
        raise ValueError("gossip= selects the MESH runtime's cross-shard "
                         "backend; pass a MeshRuntime to use it")
    if aggregator is None:
        aggregator = "kernel" if jax.default_backend() == "tpu" else \
            "reference"
    degrees = np.diff(rt.row_ptr)
    if aggregator == "dense":
        if degrees.size == 0 or (degrees != degrees[0]).any():
            raise ValueError("aggregator='dense' needs a uniform in-degree; "
                             f"got {degrees}")
        deg = int(degrees[0])
    if donate is None:
        # buffer donation is a no-op (plus a warning) on XLA:CPU
        donate = jax.default_backend() != "cpu"
    spec = rt.spec
    row_ptr = jnp.asarray(rt.row_ptr)
    dst_sorted = jnp.asarray(rt.dst_sorted)
    src_sorted = jnp.asarray(rt.src_sorted)
    counter = {"count": 0}
    ms = metrics
    if ms is not None:
        from repro.obs import metrics as obsmet
        e2 = int(rt.dst_sorted.shape[0])
        row_bytes = float(spec.size * 4)  # fp32 flat rows

    def flat_loss(w_row, batch):
        return loss_fn(flatmod.unravel(spec, w_row), batch)

    def round_body(carry, xs):
        # obs inertness contract: every `ms is not None` branch below
        # is resolved at TRACE time — with metrics off this body emits
        # the seed runtime's jaxpr op-for-op (tests/test_obs.py).
        if ms is None:
            w, os_, buf = carry
        else:
            w, os_, buf, age = carry
            w0 = w
        batches, strong_r, coeffs_r, diag_r = xs

        def local_step(c, batch_u):
            w, os_ = c
            loss, grads = jax.vmap(jax.value_and_grad(flat_loss))(w, batch_u)
            w, os_ = opt.update(w, grads, os_, lr_scale)
            if ms is None or not ms.grad_norm:
                return (w, os_), loss
            gsq_u = jnp.sum(jnp.square(grads.astype(jnp.float32)))
            return (w, os_), (loss, gsq_u)

        with jax.named_scope(SCOPE_LOCAL_SGD):
            (w, os_), ys = jax.lax.scan(local_step, (w, os_), batches)
        if ms is None or not ms.grad_norm:
            losses = ys
        else:
            losses, gsq_u = ys

        # buffer refresh on strong edges (fresh w_src), else keep stale
        with jax.named_scope(SCOPE_REFRESH):
            buf = jnp.where(strong_r[:, None], w[src_sorted], buf)

        # aggregation: w_i <- diag_i * w_i + sum_{e in row i} c_e * buf_e
        with jax.named_scope(SCOPE_AGGREGATE):
            if aggregator == "kernel":
                w = gossip_ops.edge_aggregate(w, buf, coeffs_r, row_ptr,
                                              diag_r)
            elif aggregator == "dense":
                w = dense_edge_aggregate(w, buf,
                                         coeffs_r.reshape(w.shape[0], deg),
                                         diag_r)
            else:
                w = edge_aggregate_ref(w, buf, coeffs_r, dst_sorted, diag_r)
        with jax.named_scope(SCOPE_LOCAL_SGD):
            loss = jnp.mean(losses)
        if ms is None:
            return (w, os_, buf), loss

        vals = {}
        if ms.grad_norm:
            vals["gsq"] = jnp.sum(gsq_u)
        if ms.param_norm:
            vals["psq"] = jnp.sum(jnp.square(w))
        if ms.update_norm:
            vals["usq"] = jnp.sum(jnp.square(w - w0))
        if ms.silo_loss:
            vals["silo_loss"] = jnp.mean(losses, axis=0)
        n_strong = jnp.sum(strong_r.astype(jnp.float32))
        age = jnp.where(strong_r, 0.0, age + 1.0)
        if ms.staleness:
            vals["stale_frac"] = 1.0 - n_strong / e2
            vals["buf_age"] = jnp.mean(age)
        if ms.traffic:
            vals["gossip_bytes"] = n_strong * row_bytes
        row = obsmet.assemble_row(ms, vals)
        return (w, os_, buf, age), (loss, row)

    def cycle(state, batches, strong, coeffs, diag):
        counter["count"] += 1
        carry = (state.w, state.opt_state, state.buffers)
        if ms is not None:
            # buffer age restarts each cycle call (documented: ages are
            # "rounds since refresh, within this dispatch")
            carry = carry + (jnp.zeros((e2,), jnp.float32),)
        out, ys = jax.lax.scan(
            round_body, carry, (batches, strong, coeffs, diag))
        w, os_, buf = out[:3]
        if ms is None:
            return FlatFLState(w, os_, buf), ys
        losses, mets = ys
        return FlatFLState(w, os_, buf), losses, mets

    jitted = jax.jit(cycle, donate_argnums=(0,) if donate else ())
    jitted.trace_count = counter
    if ms is not None:
        jitted.metric_columns = ms.columns(rt.num_silos)
    return jitted


def unpack_params(rt: FlatRuntime, state: FlatFLState) -> Params:
    """(N, T) -> stacked pytree with leading silo axis (oracle layout)."""
    return flatmod.unravel_stacked(rt.spec, state.w)


def unpack_buffers(rt: FlatRuntime, state: FlatFLState) -> Params:
    """Sorted (2E, T) -> stacked pytree in ORIGINAL edge order."""
    inv = np.argsort(rt.order)
    return flatmod.unravel_stacked(rt.spec, state.buffers[jnp.asarray(inv)])
