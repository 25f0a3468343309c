"""FL training loop: runs any topology end-to-end on the paper's models

+ synthetic federated data, and pairs the learning curve with the
cycle-time simulator so results can be plotted against wall-clock time
(paper Fig. 5).

Two runtimes share one code path (`FLConfig.runtime`):

  * "flat" (default) — the flat-parameter whole-cycle runtime
    (repro/fl/runtime.py, DESIGN.md §9): params/opt-state/edge buffers
    are packed `(N, T)`/`(2E, T)` arrays and a full multigraph cycle of
    R rounds is ONE jitted dispatch (`lax.scan` over the RoundPlan
    arrays). The training loop advances cycle-at-a-time; eval hooks
    keep per-round granularity by splitting cycles at eval boundaries.
  * "legacy" — one jitted `fl_round_step` dispatch per round over
    stacked pytrees. Bit-for-bit fp32-identical learning curves
    (momentum=0; see tests/test_flat_runtime.py), kept as the
    equivalence oracle.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delay import WORKLOADS, Workload
from repro.data.synthetic import FederatedDataset, make_federated_dataset
from repro.fl import dpasgd
from repro.fl.options import RuntimeOptions, adopt_runtime_options
from repro.models.small import SMALL_MODELS, SmallModelSpec
from repro.networks.zoo import NetworkSpec, get_network
from repro.optim import sgd

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
    # "flat" = whole-cycle flat-parameter runtime; "legacy" = per-round
    # stacked-pytree steps (kept as the equivalence oracle).
    runtime: str = "flat"
    # Shared runtime knobs (fl/options.py): mesh sharding (§16), gossip
    # collective, in-scan metrics and trace output (§17). Either pass
    # one `RuntimeOptions` here or keep using the legacy kwargs below —
    # after construction the two views always agree.
    options: RuntimeOptions | None = None
    mesh: object = None
    gossip: str = "halo"
    metrics: object = None
    trace: str | None = None
    # Multigraph only: explicit multiplicity vector aligned with the
    # Christofides overlay pairs (the design search's exchange format);
    # None = Algorithm 1's assignment at `t`.
    multiplicity: tuple[int, ...] | None = None
    # Periodic checkpointing (checkpoint/ckpt.py): `ckpt_dir` turns it
    # on; every `ckpt_every` rounds (and at the final round) the
    # per-silo flat rows + run metadata land as a step-numbered FL
    # checkpoint the serving fleet can load. Under mesh sharding the
    # rows are gathered through `gather_flat_state` first, so restores
    # are bit-identical across device counts. Flat runtime only.
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    ckpt_keep: int = 8

    def __post_init__(self):
        adopt_runtime_options(self)


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


def _sample_round(data, n: int, cfg: FLConfig, rng) -> tuple[np.ndarray,
                                                             np.ndarray]:
    """One round of micro batches, (u, N, b, ...) — the draw ORDER is
    the contract: both runtimes consume the same rng stream identically,
    so learning curves are comparable across `cfg.runtime`."""
    xs, ys = [], []
    for _ in range(cfg.local_updates):
        per_silo = [data.sample_batch(s, cfg.batch_size, rng)
                    for s in range(n)]
        xs.append(np.stack([b["x"] for b in per_silo]))
        ys.append(np.stack([b["y"] for b in per_silo]))
    return np.stack(xs), np.stack(ys)


def run_fl(cfg: FLConfig) -> FLResult:
    """Train `cfg` end to end; returns losses, evals and the WAN axis.

    With `cfg.trace` set (flat runtime), the loop records host spans
    (`obs.TraceRecorder.host_span`, DESIGN.md §17), per dispatch:

        sample            draw each silo's batches, stack the chunk
        copy              host->device copy of the batches (`bytes`)
        compile+dispatch  the first dispatch; `dispatch` every later one
          launch          plan-slice copies and the compiled cycle's call
          sync            the loss (and metrics) sync to the host
        eval              every `eval_every` rounds
        checkpoint        when `ckpt_dir` is set

    `launch` and `sync` nest in the dispatch span (their `parent`); the
    others are top level. Each span is also a profiler annotation, and
    the compiled cycle splits its ops into the `fl.local_sgd`,
    `fl.refresh` and `fl.aggregate` scopes (fl/runtime.py), so a
    profile of the run puts host phases and device work on one clock.
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
    loss_fn = lambda p, b: spec.loss(p, b)
    test_batch = {"x": jnp.asarray(data.test_x),
                  "y": jnp.asarray(data.test_y)}
    acc_fn = jax.jit(lambda p: spec.accuracy(p, test_batch))

    rng = np.random.default_rng(cfg.seed + 1)
    r_cycle = plan.num_rounds_cycle
    round_losses, eval_rounds, eval_accs = [], [], []

    if (cfg.metrics is not None or cfg.trace) and cfg.runtime != "flat":
        raise ValueError("metrics=/trace= need the flat whole-cycle "
                         "runtime (the legacy path has no in-scan hook)")
    if cfg.ckpt_dir and cfg.runtime != "flat":
        raise ValueError("ckpt_dir= needs the flat runtime (the flat "
                         "(N, T) rows ARE the checkpoint format)")
    recorder = None
    if cfg.trace:
        from repro.obs import TraceRecorder
        recorder = TraceRecorder()
        recorder.meta.update(dataset=cfg.dataset, network=cfg.network,
                             topology=cfg.topology, rounds=cfg.rounds,
                             seed=cfg.seed)
    metrics_chunks: list[np.ndarray] = []

    if cfg.runtime == "flat":
        from repro.fl import flat as flatmod
        from repro.fl import runtime as flrt
        from repro.optim import flat_sgd
        opt = flat_sgd(cfg.lr, momentum=cfg.momentum)
        template = jax.eval_shape(spec.init, key)
        rt = flrt.make_flat_runtime(plan, template, n)
        if cfg.mesh is not None:
            from repro.fl import mesh as flmesh
            rt = flmesh.make_mesh_runtime(
                rt, None if cfg.mesh == "auto" else cfg.mesh)
            state = flmesh.init_mesh_state(spec.init, opt, rt, key)
            cycle_fn = flrt.make_cycle_fn(rt, loss_fn=loss_fn, opt=opt,
                                          gossip=cfg.gossip,
                                          metrics=cfg.metrics)
            # eval through the SAME single-device jit as mesh=None:
            # silo rows are bit-identical, so accuracies are too
            get_w = lambda st: jnp.asarray(
                np.asarray(jax.device_get(st.w))[:n])
        else:
            state = flrt.init_flat_state(spec.init, opt, rt, key)
            cycle_fn = flrt.make_cycle_fn(rt, loss_fn=loss_fn, opt=opt,
                                          metrics=cfg.metrics)
            get_w = lambda st: st.w
        eval_params_fn = jax.jit(
            lambda w: flatmod.unravel(rt.spec, jnp.mean(w, axis=0)))

        ckpt_mgr = None
        if cfg.ckpt_dir:
            from repro.checkpoint import CheckpointManager, \
                save_fl_checkpoint
            ckpt_mgr = CheckpointManager(cfg.ckpt_dir, keep=cfg.ckpt_keep)
            # the canonical (N, T) rows: a mesh run gathers through
            # gather_flat_state so pad rows / block-padded edge layout
            # never leak into the checkpoint — D=8 and D=1 runs save
            # bit-identical blocks (tests/test_serving_loop.py)
            if cfg.mesh is not None:
                ckpt_w = lambda st: flmesh.gather_flat_state(rt, st).w
            else:
                ckpt_w = lambda st: st.w
            cum_ms = np.cumsum(tplan.cycle_times(cfg.rounds))

            def emit_ckpt(k, state):
                save_fl_checkpoint(
                    ckpt_mgr, k, ckpt_w(state),
                    round=k, network=cfg.network, dataset=cfg.dataset,
                    topology=cfg.topology, t=cfg.t, seed=cfg.seed,
                    num_silos=n, multiplicity=cfg.multiplicity,
                    lr=cfg.lr, momentum=cfg.momentum,
                    alpha=cfg.alpha,
                    sim_time_ms=float(cum_ms[k - 1]) if k else 0.0,
                    loss_tail=[float(x) for x in round_losses[-8:]],
                    eval_accs=[float(x) for x in eval_accs[-4:]])

        span = (recorder.host_span if recorder is not None
                else lambda name, **args: contextlib.nullcontext())
        k = 0
        while k < cfg.rounds:
            # advance a whole cycle per dispatch, splitting at eval
            # boundaries so eval hooks keep per-round granularity
            # (and at checkpoint boundaries when ckpt_every is set)
            next_stop = min((k // cfg.eval_every + 1) * cfg.eval_every,
                            cfg.rounds)
            if ckpt_mgr is not None and cfg.ckpt_every > 0:
                next_stop = min(next_stop,
                                (k // cfg.ckpt_every + 1) * cfg.ckpt_every)
            chunk = min(r_cycle, next_stop - k)
            with span("sample", rounds=chunk):
                per_round = [_sample_round(data, n, cfg, rng)
                             for _ in range(chunk)]
                xs = np.stack([x for x, _ in per_round])
                ys = np.stack([y for _, y in per_round])
            with span("copy", bytes=xs.nbytes + ys.nbytes):
                batches = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
            pks = [(k + j) % r_cycle for j in range(chunk)]
            with span("compile+dispatch" if k == 0 else "dispatch",
                      start_round=k, rounds=chunk):
                with span("launch", rounds=chunk):
                    out = cycle_fn(state, batches,
                                   jnp.asarray(rt.strong[pks]),
                                   jnp.asarray(rt.coeffs[pks]),
                                   jnp.asarray(rt.diag[pks]))
                with span("sync"):
                    if cfg.metrics is not None:
                        state, losses, mets = out
                        metrics_chunks.append(np.asarray(mets))
                    else:
                        state, losses = out
                    losses = np.asarray(losses)
            round_losses.extend(float(x) for x in losses)
            k += chunk
            if k % cfg.eval_every == 0 or k == cfg.rounds:
                with span("eval", round=k):
                    acc = float(acc_fn(eval_params_fn(get_w(state))))
                eval_rounds.append(k)
                eval_accs.append(acc)
            if ckpt_mgr is not None and (
                    k == cfg.rounds or
                    (cfg.ckpt_every > 0 and k % cfg.ckpt_every == 0)):
                with span("checkpoint", round=k):
                    emit_ckpt(k, state)
    elif cfg.runtime == "legacy":
        if cfg.mesh is not None:
            raise ValueError("mesh= requires runtime='flat'")
        opt = sgd(cfg.lr, momentum=cfg.momentum)
        state = dpasgd.init_fl_state(spec.init, opt, n, plan.src, key)
        step = jax.jit(lambda st, batches, s, c, d: dpasgd.fl_round_step(
            st, batches, plan.src, plan.dst, s, c, d,
            loss_fn=loss_fn, opt=opt, local_updates=cfg.local_updates))
        eval_params_fn = jax.jit(
            lambda w: jax.tree.map(lambda x: jnp.mean(x, axis=0), w))

        for k in range(cfg.rounds):
            xs, ys = _sample_round(data, n, cfg, rng)
            batches = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
            pk = k % r_cycle
            state, loss = step(state, batches,
                               jnp.asarray(plan.strong[pk]),
                               jnp.asarray(plan.coeffs[pk]),
                               jnp.asarray(plan.diag[pk]))
            round_losses.append(float(loss))
            if (k + 1) % cfg.eval_every == 0 or k == cfg.rounds - 1:
                acc = float(acc_fn(eval_params_fn(state.silo_params)))
                eval_rounds.append(k + 1)
                eval_accs.append(acc)
    else:
        raise ValueError(f"unknown runtime {cfg.runtime!r}")

    # One TimingPlan, one report: the per-round axis comes from
    # `cycle_times` and the scalar totals from the SAME plan's
    # `report`, which is also exactly what `simulate(...)` returns for
    # this config — trainer totals and simulator reports are one
    # number, not two estimators (the old MATCHA path tiled a 512-round
    # period here while the report averaged the period, so the two
    # drifted apart for rounds > 512).
    cycle = tplan.cycle_times(cfg.rounds)
    rep = tplan.report(cfg.rounds)
    all_metrics = (np.concatenate(metrics_chunks)
                   if metrics_chunks else None)
    metric_cols = (getattr(cycle_fn, "metric_columns", ())
                   if cfg.metrics is not None else ())
    if recorder is not None:
        from repro.obs import write_trace
        recorder.add_sim_spans(tplan, cfg.rounds)
        if all_metrics is not None:
            starts = np.concatenate([[0.0], np.cumsum(cycle)[:-1]])
            recorder.add_metrics(all_metrics, metric_cols, starts)
        write_trace(cfg.trace, recorder)
    return FLResult(config=cfg, round_losses=round_losses,
                    eval_rounds=eval_rounds, eval_accs=eval_accs,
                    cycle_times_ms=cycle.tolist(),
                    mean_cycle_ms=rep.mean_cycle_ms,
                    total_time_s=rep.total_time_s,
                    metrics=all_metrics, metric_columns=tuple(metric_cols))
