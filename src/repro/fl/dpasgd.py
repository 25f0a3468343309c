"""DPASGD with multigraph states (paper Eq. 2 / Eq. 6) — simulation mode.

N silos live on one host as a stacked pytree (leading silo axis); every
communication round is one jitted step (`fl_round_step`). The trainers
run the flat whole-cycle runtime (fl/runtime.py); this per-round step is
the tests' equivalence oracle for it. One round:

  1. u local SGD updates per silo (Eq. 2, lower branch) — vmap over the
     silo axis;
  2. buffer refresh: every STRONG pair of the current state exchanges
     fresh weights (both directions);
  3. aggregation (Eq. 6): w_i <- A[i,i] w_i + sum_j A[i,j] buf[j->i],
     where A is the Metropolis-Hastings matrix of the OVERLAY and
     buf[j->i] holds w_j(k-h) — fresh (h=0) if the edge was strong this
     round, stale otherwise. A node whose edges are all weak aggregates
     entirely from its stale buffers — it "does model aggregation
     without waiting for other nodes" (paper §1), which is exactly the
     isolated-node mechanism. Timing is accounted by core/simulator.py.

Static baselines (STAR/MST/RING/MATCHA) use the same step with per-round
(strong_mask, coeffs) of their own graphs, so every topology trains
through one code path.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import timing
from repro.core.consensus import metropolis_weights
from repro.core.graph import MultigraphState, SimpleGraph
from repro.core.topology import build_topology
from repro.core.delay import Workload
from repro.networks.zoo import NetworkSpec

Params = Any


@dataclasses.dataclass
class RoundPlan:
    """Static per-round aggregation plan (host-side, feeds the jitted step).

    Directed edges are indexed 0..2E-1 over the base graph; per round we
    provide which are strong, the aggregation coefficient per directed
    edge, and the self coefficient per silo.
    """

    src: np.ndarray          # (2E,) int32
    dst: np.ndarray          # (2E,) int32
    strong: np.ndarray       # (R, 2E) bool — refresh buffer this round?
    coeffs: np.ndarray       # (R, 2E) f32  — A[dst, src] this round
    diag: np.ndarray         # (R, N) f32   — A[i, i] this round
    aggregate: np.ndarray    # (R,) bool    — aggregation round at all?

    @property
    def num_rounds_cycle(self) -> int:
        return self.strong.shape[0]


def _directed_edges(graph: SimpleGraph):
    src, dst = [], []
    for i, j in graph.pairs:
        src += [i, j]
        dst += [j, i]
    return np.asarray(src, np.int32), np.asarray(dst, np.int32)


def multigraph_plan(net: NetworkSpec, wl: Workload, t: int = 5,
                    cap_states: int | None = timing.CAP_STATES,
                    tplan: timing.TimingPlan | None = None) -> tuple[RoundPlan, list[MultigraphState], SimpleGraph]:
    """Plan for the paper's multigraph: overlay MH weights, per-state

    strong masks (weak edges keep their coefficient but read stale
    buffers). States and overlay come from the SAME TimingPlan the
    wall-clock axis is simulated with (single source of truth for
    states, caps, and schedules — the trainer used to re-derive them
    with a different ``cap_states``)."""
    if tplan is None:
        tplan = timing.multigraph_timing_plan(net, wl, t=t,
                                              cap_states=cap_states)
    overlay = tplan.overlay
    states = list(tplan.states)
    src, dst = _directed_edges(overlay)
    a = metropolis_weights(overlay)
    r = len(states)
    e2 = len(src)
    strong = np.zeros((r, e2), bool)
    coeffs = np.zeros((r, e2), np.float32)
    diag = np.zeros((r, net.num_silos), np.float32)
    for k, st in enumerate(states):
        et = st.edge_type
        for e in range(e2):
            i, j = int(src[e]), int(dst[e])
            p = (i, j) if i < j else (j, i)
            strong[k, e] = bool(et[p])
            coeffs[k, e] = a[j, i]  # weight of src model in dst's average
        diag[k] = np.diag(a)
    plan = RoundPlan(src=src, dst=dst, strong=strong, coeffs=coeffs,
                     diag=diag, aggregate=np.ones((r,), bool))
    return plan, states, overlay


def static_plan(graph: SimpleGraph) -> RoundPlan:
    """Every round: all edges strong, MH coefficients of the graph."""
    src, dst = _directed_edges(graph)
    a = metropolis_weights(graph)
    coeffs = np.asarray([a[int(d), int(s)] for s, d in zip(src, dst)],
                        np.float32)
    return RoundPlan(
        src=src, dst=dst,
        strong=np.ones((1, len(src)), bool),
        coeffs=coeffs[None],
        diag=np.diag(a)[None].astype(np.float32),
        aggregate=np.ones((1,), bool))


def matcha_plan(design, num_nodes: int, rounds: int,
                graphs: list[SimpleGraph] | None = None) -> RoundPlan:
    """Per-round sampled matchings: coefficients are MH of the ACTIVE

    graph that round; inactive edges get coefficient 0. ``graphs``
    optionally supplies the pre-materialized per-round graphs (shared
    with the TimingPlan so both axes sample the same sequence)."""
    base_pairs = sorted({p for m in design.matchings for p in m})
    base = SimpleGraph(num_nodes=num_nodes, pairs=tuple(base_pairs))
    src, dst = _directed_edges(base)
    e2 = len(src)
    strong = np.zeros((rounds, e2), bool)
    coeffs = np.zeros((rounds, e2), np.float32)
    diag = np.ones((rounds, num_nodes), np.float32)
    pair_index = {p: ei for ei, p in enumerate(base.pairs)}
    for k in range(rounds):
        g = graphs[k] if graphs is not None else design.round_graph(k)
        if not g.pairs:
            continue
        a = metropolis_weights(g)
        for p in g.pairs:
            ei = pair_index[p]
            i, j = p
            strong[k, 2 * ei] = strong[k, 2 * ei + 1] = True
            coeffs[k, 2 * ei] = a[j, i]
            coeffs[k, 2 * ei + 1] = a[i, j]
        diag[k] = np.diag(a)
    return RoundPlan(src=src, dst=dst, strong=strong, coeffs=coeffs,
                     diag=diag, aggregate=np.ones((rounds,), bool))


def make_round_schedule(topology: str, net: NetworkSpec, wl: Workload, *,
                        t: int = 5, rounds: int = 1, seed: int = 0,
                        multiplicity=None, overlay: SimpleGraph | None = None,
                        ) -> tuple[RoundPlan, timing.TimingPlan]:
    """(RoundPlan, TimingPlan) for any topology in the paper's Table 1.

    The two plans are built from one schedule: for the multigraph the
    RoundPlan's per-state strong masks come from the TimingPlan's own
    parsed states, so `run_fl` totals and `simulate(...)` reports agree
    for the same config by construction.

    ``multiplicity`` (multigraph only) trains an EXPLICIT multiplicity
    vector aligned with the Christofides overlay's pairs — the format
    `repro.design.search` emits — instead of Algorithm 1's assignment.
    The vector goes through `timing.multiplicity_vector_plan`, i.e. the
    same constructor that scored it during the search, and the RoundPlan
    is built from that plan's own parsed states; passing Algorithm 1's
    vector reproduces the default plan bit-for-bit
    (tests/test_design_tta.py).

    ``overlay`` (multigraph only) reuses a prebuilt overlay graph
    instead of re-deriving the Christofides tour — callers that build
    several schedules over one overlay (the fault controller) pass it
    so every plan shares the identical pair order.
    """
    if topology == "multigraph":
        if multiplicity is not None:
            if overlay is None:
                from repro.core.topology import ring_topology
                overlay = ring_topology(net, wl).graph
            tplan = timing.multiplicity_vector_plan(
                net, wl, overlay, multiplicity, name="multigraph(searched)")
        else:
            tplan = timing.multigraph_timing_plan(net, wl, t=t,
                                                  overlay=overlay)
        plan, _, _ = multigraph_plan(net, wl, t=t, tplan=tplan)
        return plan, tplan
    if multiplicity is not None:
        raise ValueError("multiplicity vectors only apply to the "
                         f"multigraph topology, not {topology!r}")
    if topology == "star":
        design = build_topology("star", net, wl)
        return (static_plan(design.round_graph(0)),
                timing.star_timing_plan(net, wl))
    design = build_topology(topology, net, wl, **(
        {"seed": seed} if topology.startswith("matcha") else {}))
    if topology.startswith("matcha"):
        # One design, one counter-based activation sequence: round k's
        # matchings are a pure function of (seed, k), the RoundPlan
        # trains on round_graph(k) and the TimingPlan's vectorized
        # per-round times come from the SAME activation rows (every
        # round sampled, no tiled period), so the trainer's wall-clock
        # total and `simulate(...)`'s report total are identical —
        # tests/test_timing.py holds them bit-for-bit equal.
        tplan = timing.sampled_timing_plan(topology, net, wl, design,
                                           sample_rounds=max(rounds, 1))
        return matcha_plan(design, net.num_silos, rounds), tplan
    g = design.round_graph(0)
    if topology == "ring":
        return static_plan(g), timing.ring_timing_plan(net, wl, graph=g)
    return static_plan(g), timing.static_timing_plan(topology, net, wl, g)


# ---------------------------------------------------------------------------
# jitted FL round step
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FLSimState:
    silo_params: Params   # leaves (N, ...)
    opt_state: Params     # leaves (N, ...)
    buffers: Params       # leaves (2E, ...) — buf[e] = last w_src(e) seen

    def tree_flatten(self):
        return (self.silo_params, self.opt_state, self.buffers), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_fl_state(init_params: Callable[[jax.Array], Params], opt,
                  num_silos: int, src: np.ndarray,
                  key: jax.Array) -> FLSimState:
    keys = jax.random.split(key, num_silos)
    # Identical init across silos (the standard FL assumption).
    p0 = init_params(keys[0])
    silo_params = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_silos,) + x.shape).copy(), p0)
    opt_state = jax.vmap(opt.init)(silo_params)
    buffers = jax.tree.map(lambda w: w[src], silo_params)
    return FLSimState(silo_params, opt_state, buffers)


def fl_round_step(state: FLSimState, batches, plan_src, plan_dst,
                  strong, coeffs, diag, *, loss_fn, opt, local_updates: int,
                  lr_scale=1.0) -> tuple[FLSimState, jax.Array]:
    """One communication round (jit-friendly; plan_* are arrays).

    batches: pytree with leaves (u, N, b, ...) — one micro batch per
    local update per silo.
    """
    w, os_ = state.silo_params, state.opt_state

    def local_step(carry, batch_u):
        w, os_ = carry
        loss, grads = jax.vmap(jax.value_and_grad(loss_fn))(w, batch_u)
        w, os_ = jax.vmap(
            lambda p, g, s: opt.update(p, g, s, lr_scale))(w, grads, os_)
        return (w, os_), loss

    (w, os_), losses = jax.lax.scan(local_step, (w, os_), batches)

    # buffer refresh on strong edges (fresh w_src), else keep stale
    def refresh(buf, wall):
        fresh = wall[plan_src]
        mask = strong.reshape((-1,) + (1,) * (buf.ndim - 1))
        return jnp.where(mask, fresh, buf)

    buffers = jax.tree.map(refresh, state.buffers, w)

    # aggregation: w_i <- diag_i * w_i + sum_{e: dst=i} coeff_e * buf_e
    n = jax.tree.leaves(w)[0].shape[0]

    def aggregate(wall, buf):
        c = coeffs.reshape((-1,) + (1,) * (buf.ndim - 1)).astype(buf.dtype)
        contrib = jax.ops.segment_sum(c * buf, plan_dst, num_segments=n)
        d = diag.reshape((n,) + (1,) * (wall.ndim - 1)).astype(wall.dtype)
        return d * wall + contrib

    w = jax.tree.map(aggregate, w, buffers)
    return FLSimState(w, os_, buffers), jnp.mean(losses)
