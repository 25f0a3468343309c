"""FL training loop: runs any topology end-to-end on the paper's models

+ synthetic federated data, and pairs the learning curve with the
cycle-time simulator so results can be plotted against wall-clock time
(paper Fig. 5).

Training runs on the flat-parameter whole-cycle runtime
(repro/fl/runtime.py, DESIGN.md §9): params/opt-state/edge buffers are
packed `(N, T)`/`(2E, T)` arrays and a full multigraph cycle of R
rounds is ONE jitted dispatch (`lax.scan` over the RoundPlan arrays),
on one device or sharded over a silo mesh (fl/mesh.py, §16). One
driver (`_drive`) advances it cycle-at-a-time for both trainers, this
module's `run_fl` and `launch/train.py`'s `run_reduced_fl`; eval and
checkpoint hooks keep per-round granularity by splitting cycles at
their boundaries. The per-round step of `fl/dpasgd.py` over stacked
pytrees is the tests' oracle: the learning curves match it bit for
bit (tests/test_flat_runtime.py).
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delay import WORKLOADS, Workload
from repro.data.synthetic import make_federated_dataset
from repro.fl import dpasgd
from repro.models.small import SMALL_MODELS, SmallModelSpec
from repro.networks.zoo import NetworkSpec, get_network

_DATASET_MODEL = {"femnist": "femnist_cnn", "sent140": "sent140_lstm",
                  "inat": "inat_resnet"}
_DATASET_WL = {"femnist": "femnist", "sent140": "sentiment140",
               "inat": "inaturalist"}


@dataclasses.dataclass
class FLConfig:
    dataset: str = "femnist"
    network: str = "gaia"
    topology: str = "multigraph"
    t: int = 5
    rounds: int = 200
    local_updates: int = 1
    batch_size: int = 32
    lr: float = 0.05
    momentum: float = 0.0
    seed: int = 0
    eval_every: int = 20
    samples_per_silo: int = 128
    alpha: float = 0.5          # Dirichlet non-IID level
    # Table 4 ablation: remove silos from the RING overlay.
    remove_silos: int = 0
    remove_strategy: str = "none"  # none | random | inefficient
    # Silo-axis device mesh (DESIGN.md §16): None = one device, an int
    # = that many shards, "auto" = every host device, or a 1-D Mesh.
    mesh: object = None
    # Mesh only: cross-shard source-row collective, "halo" (ppermute
    # boundary exchange) or "all_gather".
    gossip: str = "halo"
    # An `obs.MetricsSpec` compiled into the cycle scan (§17); None = off.
    metrics: object = None
    # Path for a Perfetto trace-event JSON of the run; None = off.
    trace: str | None = None
    # Multigraph only: explicit multiplicity vector aligned with the
    # Christofides overlay pairs (the design search's exchange format);
    # None = Algorithm 1's assignment at `t`.
    multiplicity: tuple[int, ...] | None = None
    # Periodic checkpointing (checkpoint/ckpt.py): `ckpt_dir` turns it
    # on; every `ckpt_every` rounds (and at the final round) the
    # per-silo flat rows + run metadata land as a step-numbered FL
    # checkpoint the serving fleet can load. Under mesh sharding the
    # canonical (N, T) rows are saved (`fl/mesh.py` `place_runtime`),
    # so restores are bit-identical across device counts.
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    ckpt_keep: int = 8


@dataclasses.dataclass
class FLResult:
    config: FLConfig
    round_losses: list[float]
    eval_rounds: list[int]
    eval_accs: list[float]
    cycle_times_ms: list[float]
    mean_cycle_ms: float
    total_time_s: float
    # populated only when cfg.metrics is set
    metrics: np.ndarray | None = None        # (rounds, K) f32
    metric_columns: tuple[str, ...] = ()

    def final_acc(self) -> float:
        return self.eval_accs[-1] if self.eval_accs else float("nan")

    def wallclock_axis_s(self) -> np.ndarray:
        return np.cumsum(self.cycle_times_ms) / 1e3


def _removed_network(net: NetworkSpec, wl: Workload, k: int,
                     strategy: str, seed: int) -> tuple[NetworkSpec, np.ndarray]:
    """Drop k silos from the network (Table 4 ablation). Returns the

    reduced NetworkSpec and the kept silo indices. Thin wrapper over
    `repro.faults.degrade.removed_network`, which also supports an
    explicit drop set for mid-horizon removal."""
    from repro.faults.degrade import removed_network
    return removed_network(net, wl, k=k, strategy=strategy, seed=seed)


def _sample_round(data, n: int, local_updates: int, batch_size: int,
                  rng) -> tuple[np.ndarray, np.ndarray]:
    """One round of micro batches, (u, N, b, ...). The draw ORDER is
    the contract: every trainer and the tests' per-round oracle consume
    the same rng stream identically, so their curves are comparable."""
    xs, ys = [], []
    for _ in range(local_updates):
        per_silo = [data.sample_batch(s, batch_size, rng) for s in range(n)]
        xs.append(np.stack([b["x"] for b in per_silo]))
        ys.append(np.stack([b["y"] for b in per_silo]))
    return np.stack(xs), np.stack(ys)


def _recorder(trace: str | None, **meta):
    """A `TraceRecorder` carrying ``meta`` when ``trace`` is set."""
    if not trace:
        return None
    from repro.obs import TraceRecorder
    recorder = TraceRecorder()
    recorder.meta.update(meta)
    return recorder


@dataclasses.dataclass
class _Log:
    """What `_drive` has seen so far; hooks read it."""

    losses: list[float] = dataclasses.field(default_factory=list)
    eval_rounds: list[int] = dataclasses.field(default_factory=list)
    eval_accs: list[float] = dataclasses.field(default_factory=list)
    metrics: list[np.ndarray] = dataclasses.field(default_factory=list)


def _drive(rt, state, cycle_fn, draw, *, rounds: int, recorder=None,
           with_metrics: bool = False, eval_every: int = 0, evaluate=None,
           ckpt_every: int = 0, checkpoint=None) -> _Log:
    """Train ``rounds`` rounds, one compiled cycle dispatch at a time.

    A dispatch runs up to a whole cycle (``rt.num_rounds_cycle``
    rounds) and stops early at each eval boundary (every ``eval_every``
    rounds, when ``evaluate`` is given) and checkpoint boundary (every
    ``ckpt_every`` rounds, when ``checkpoint`` is given); both hooks
    also run after the last round. ``draw(r)`` returns the host batch
    arrays of the next ``r`` rounds, leaves ``(r, u, N, b, ...)``;
    ``evaluate(state)`` returns an accuracy; ``checkpoint(k, state,
    log)`` saves round ``k``.

    With a ``recorder`` the loop records host spans
    (`obs.TraceRecorder.host_span`, DESIGN.md §17), per dispatch:

        sample            ``draw``: each silo's batches, stacked
        copy              host->device copy of the batches (`bytes`)
        compile+dispatch  the first dispatch; `dispatch` every later one
          launch          plan-slice copies and the compiled cycle's call
          sync            the loss (and metrics) sync to the host
        eval              at each eval boundary
        checkpoint        at each checkpoint boundary

    `launch` and `sync` nest in the dispatch span (their `parent`); the
    others are top level. Each span is also a profiler annotation, and
    the compiled cycle splits its ops into the `fl.local_sgd`,
    `fl.refresh` and `fl.aggregate` scopes (fl/runtime.py), so a
    profile of the run puts host phases and device work on one clock.
    """
    span = (recorder.host_span if recorder is not None
            else lambda name, **args: contextlib.nullcontext())
    r_cycle = rt.num_rounds_cycle
    log = _Log()
    k = 0
    while k < rounds:
        next_stop = rounds
        if evaluate is not None:
            next_stop = min(next_stop, (k // eval_every + 1) * eval_every)
        if checkpoint is not None and ckpt_every > 0:
            next_stop = min(next_stop, (k // ckpt_every + 1) * ckpt_every)
        chunk = min(r_cycle, next_stop - k)
        with span("sample", rounds=chunk):
            host = draw(chunk)
        with span("copy", bytes=sum(a.nbytes for a in host.values())):
            batches = {name: jnp.asarray(a) for name, a in host.items()}
        pks = [(k + j) % r_cycle for j in range(chunk)]
        with span("compile+dispatch" if k == 0 else "dispatch",
                  start_round=k, rounds=chunk):
            with span("launch", rounds=chunk):
                out = cycle_fn(state, batches,
                               jnp.asarray(rt.strong[pks]),
                               jnp.asarray(rt.coeffs[pks]),
                               jnp.asarray(rt.diag[pks]))
            with span("sync"):
                if with_metrics:
                    state, losses, mets = out
                    log.metrics.append(np.asarray(mets))
                else:
                    state, losses = out
                losses = np.asarray(losses)
        log.losses.extend(float(x) for x in losses)
        k += chunk
        if evaluate is not None and (k % eval_every == 0 or k == rounds):
            with span("eval", round=k):
                acc = evaluate(state)
            log.eval_rounds.append(k)
            log.eval_accs.append(acc)
        if checkpoint is not None and (
                k == rounds or (ckpt_every > 0 and k % ckpt_every == 0)):
            with span("checkpoint", round=k):
                checkpoint(k, state, log)
    return log


def run_fl(cfg: FLConfig) -> FLResult:
    """Train `cfg` end to end; returns losses, evals and the WAN axis.

    With `cfg.trace` set the run writes a Perfetto trace of the
    driver's host spans (`_drive`) and the simulated WAN clock.
    """
    wl = WORKLOADS[_DATASET_WL[cfg.dataset]]
    net = get_network(cfg.network)
    if cfg.remove_strategy != "none" and cfg.remove_silos > 0:
        net, _ = _removed_network(net, wl, cfg.remove_silos,
                                  cfg.remove_strategy, cfg.seed)

    n = net.num_silos
    spec: SmallModelSpec = SMALL_MODELS[_DATASET_MODEL[cfg.dataset]]
    data = make_federated_dataset(cfg.dataset, n,
                                  samples_per_silo=cfg.samples_per_silo,
                                  alpha=cfg.alpha, seed=cfg.seed)

    # One schedule, two views: the RoundPlan drives training, the
    # TimingPlan it was built from drives the wall-clock axis.
    plan, tplan = dpasgd.make_round_schedule(cfg.topology, net, wl, t=cfg.t,
                                             rounds=cfg.rounds, seed=cfg.seed,
                                             multiplicity=cfg.multiplicity)
    key = jax.random.PRNGKey(cfg.seed)
    test_batch = {"x": jnp.asarray(data.test_x),
                  "y": jnp.asarray(data.test_y)}
    acc_fn = jax.jit(lambda p: spec.accuracy(p, test_batch))
    recorder = _recorder(cfg.trace, dataset=cfg.dataset,
                         network=cfg.network, topology=cfg.topology,
                         rounds=cfg.rounds, seed=cfg.seed)

    from repro.fl import flat as flatmod
    from repro.fl import mesh as flmesh
    from repro.fl import runtime as flrt
    from repro.optim import flat_sgd
    opt = flat_sgd(cfg.lr, momentum=cfg.momentum)
    rt, state, rows = flmesh.place_runtime(
        flrt.make_flat_runtime(plan, jax.eval_shape(spec.init, key), n),
        cfg.mesh, spec.init, opt, key)
    cycle_fn = flrt.make_cycle_fn(
        rt, loss_fn=lambda p, b: spec.loss(p, b), opt=opt,
        metrics=cfg.metrics,
        gossip=cfg.gossip if isinstance(rt, flmesh.MeshRuntime) else None)
    eval_params_fn = jax.jit(
        lambda w: flatmod.unravel(rt.spec, jnp.mean(w, axis=0)))

    rng = np.random.default_rng(cfg.seed + 1)

    def draw(chunk):
        per_round = [_sample_round(data, n, cfg.local_updates,
                                   cfg.batch_size, rng)
                     for _ in range(chunk)]
        return {"x": np.stack([x for x, _ in per_round]),
                "y": np.stack([y for _, y in per_round])}

    checkpoint = None
    if cfg.ckpt_dir:
        from repro.checkpoint import CheckpointManager, save_fl_checkpoint
        ckpt_mgr = CheckpointManager(cfg.ckpt_dir, keep=cfg.ckpt_keep)
        cum_ms = np.cumsum(tplan.cycle_times(cfg.rounds))

        def checkpoint(k, state, log):
            # the canonical (N, T) rows: D=8 and D=1 runs save
            # bit-identical blocks (tests/test_serving_loop.py)
            save_fl_checkpoint(
                ckpt_mgr, k, rows(state),
                round=k, network=cfg.network, dataset=cfg.dataset,
                topology=cfg.topology, t=cfg.t, seed=cfg.seed,
                num_silos=n, multiplicity=cfg.multiplicity,
                lr=cfg.lr, momentum=cfg.momentum, alpha=cfg.alpha,
                sim_time_ms=float(cum_ms[k - 1]) if k else 0.0,
                loss_tail=[float(x) for x in log.losses[-8:]],
                eval_accs=[float(x) for x in log.eval_accs[-4:]])

    log = _drive(rt, state, cycle_fn, draw, rounds=cfg.rounds,
                 recorder=recorder, with_metrics=cfg.metrics is not None,
                 eval_every=cfg.eval_every,
                 evaluate=lambda st: float(acc_fn(eval_params_fn(rows(st)))),
                 ckpt_every=cfg.ckpt_every, checkpoint=checkpoint)

    # One TimingPlan, one report: the per-round axis comes from
    # `cycle_times` and the scalar totals from the SAME plan's
    # `report`, which is also exactly what `simulate(...)` returns for
    # this config — trainer totals and simulator reports are one
    # number, not two estimators (the old MATCHA path tiled a 512-round
    # period here while the report averaged the period, so the two
    # drifted apart for rounds > 512).
    cycle = tplan.cycle_times(cfg.rounds)
    rep = tplan.report(cfg.rounds)
    all_metrics = np.concatenate(log.metrics) if log.metrics else None
    metric_cols = (getattr(cycle_fn, "metric_columns", ())
                   if cfg.metrics is not None else ())
    if recorder is not None:
        from repro.obs import write_trace
        recorder.add_sim_spans(tplan, cfg.rounds)
        if all_metrics is not None:
            starts = np.concatenate([[0.0], np.cumsum(cycle)[:-1]])
            recorder.add_metrics(all_metrics, metric_cols, starts)
        write_trace(cfg.trace, recorder)
    return FLResult(config=cfg, round_losses=log.losses,
                    eval_rounds=log.eval_rounds, eval_accs=log.eval_accs,
                    cycle_times_ms=cycle.tolist(),
                    mean_cycle_ms=rep.mean_cycle_ms,
                    total_time_s=rep.total_time_s,
                    metrics=all_metrics, metric_columns=tuple(metric_cols))
