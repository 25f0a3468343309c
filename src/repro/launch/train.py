"""End-to-end FL training driver for the LLM-scale architectures.

Two modes:

* --reduced (CPU-runnable): N silos federally train a REDUCED variant of
  any assigned architecture on synthetic per-silo LM streams, under any
  topology (multigraph/ring/star/...). This is the full paper technique
  — DPASGD local steps, multigraph state schedule, stale weak-edge
  buffers — driving the real model stack, plus the cycle-time simulator
  for the wall-clock axis. Used by examples/fl_llm_finetune.py.

* full-size production runs use the same step functions the dry-run
  lowers (launch/steps.py); on real hardware you would swap the mesh in
  and feed real data. This container is CPU-only, so full-size mode only
  builds and prints the plan.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m \
      --reduced --silos 6 --rounds 30 --topology multigraph
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce as reduce_cfg
from repro.core.delay import FEMNIST, MultigraphDelayTracker, WORKLOADS
from repro.data.synthetic import make_lm_dataset
from repro.fl import dpasgd
from repro.fl.options import RuntimeOptions, adopt_runtime_options
from repro.models import transformer as tf
from repro.models.frontends import prefix_tokens, synthetic_prefix
from repro.networks.zoo import NetworkSpec, get_network
from repro.optim import adamw, sgd


def _sub_network(net: NetworkSpec, n: int) -> NetworkSpec:
    keep = np.arange(min(n, net.num_silos))
    return NetworkSpec(name=f"{net.name}[{n}]",
                       silos=tuple(net.silos[i] for i in keep),
                       latency_ms=net.latency_ms[np.ix_(keep, keep)])


@dataclasses.dataclass
class TrainConfig:
    arch: str = "mamba2-370m"
    topology: str = "multigraph"
    network: str = "gaia"
    silos: int = 4
    rounds: int = 30
    t: int = 5
    seq_len: int = 32
    batch_size: int = 4
    lr: float = 3e-3
    seed: int = 0
    reduced: bool = True
    # Shared runtime knobs (fl/options.py): mesh sharding (None =
    # legacy per-round runtime; an int / "auto" / a Mesh runs the
    # whole-cycle flat runtime, DESIGN.md §16), gossip collective, and
    # trace output. Pass one `RuntimeOptions` or the legacy kwargs.
    options: RuntimeOptions | None = None
    mesh: object = None
    gossip: str = "halo"
    metrics: object = None
    trace: str | None = None
    # Mesh path only: rank > 0 trains LoRA deltas over a frozen shared
    # base (fl/lora.py) so per-silo state is T_lora, not T_full.
    lora_rank: int = 0
    # Periodic FL checkpoints (checkpoint/ckpt.py): per-silo flat rows
    # (the LoRA delta rows when lora_rank > 0) + metadata every
    # ckpt_every rounds and at the end; the serving fleet loads them.
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    ckpt_keep: int = 8

    def __post_init__(self):
        adopt_runtime_options(self)
        if self.metrics is not None:
            raise ValueError("TrainConfig does not thread in-scan "
                             "metrics; use FLConfig(metrics=...)")


def run_reduced_fl(cfg: TrainConfig) -> dict:
    mcfg = reduce_cfg(get_config(cfg.arch))
    net = _sub_network(get_network(cfg.network), cfg.silos)
    n = net.num_silos
    wl = WORKLOADS["femnist"]

    plan, tplan = dpasgd.make_round_schedule(cfg.topology, net, wl, t=cfg.t,
                                             rounds=cfg.rounds, seed=cfg.seed)
    recorder = None
    if cfg.trace:
        from repro.obs import TraceRecorder
        recorder = TraceRecorder()
        recorder.meta.update(arch=cfg.arch, topology=cfg.topology,
                             network=net.name, rounds=cfg.rounds,
                             seed=cfg.seed)
    key = jax.random.PRNGKey(cfg.seed)
    data = make_lm_dataset(mcfg.vocab_size, cfg.seq_len, n,
                           samples_per_silo=64, seed=cfg.seed)
    prefix = None
    if mcfg.frontend != "none":
        prefix = jnp.stack([synthetic_prefix(mcfg, cfg.batch_size, seed=s)
                            for s in range(n)])[None]  # (1, N, B, P, D)

    def loss_fn(p, batch):
        b = {"tokens": batch["tokens"], "labels": batch["labels"]}
        if "prefix_embeds" in batch:
            b["prefix_embeds"] = batch["prefix_embeds"]
        loss, _ = tf.loss_fn(p, mcfg, b)
        return loss

    rng = np.random.default_rng(cfg.seed)

    def draw_round():
        toks = np.stack([
            data[s][rng.integers(0, len(data[s]), cfg.batch_size)]
            for s in range(n)])  # (N, B, S+1)
        return toks

    losses = []
    r_cycle = plan.num_rounds_cycle
    t0 = time.time()
    ckpt_mgr = None
    ckpt_w = None  # set per-path: state -> gathered (N, T) flat rows
    if cfg.ckpt_dir:
        from repro.checkpoint import CheckpointManager
        ckpt_mgr = CheckpointManager(cfg.ckpt_dir, keep=cfg.ckpt_keep)
        ckpt_cum_ms = np.cumsum(tplan.cycle_times(cfg.rounds))

    def emit_ckpt(k, state):
        from repro.checkpoint import save_fl_checkpoint
        span = (recorder.host_span("checkpoint", round=k)
                if recorder is not None else contextlib.nullcontext())
        with span:
            save_fl_checkpoint(
                ckpt_mgr, k, ckpt_w(state),
                round=k, arch=cfg.arch, network=cfg.network,
                dataset="synthetic-lm", workload="femnist",
                topology=cfg.topology, t=cfg.t, seed=cfg.seed,
                num_silos=n, lora_rank=cfg.lora_rank,
                params_kind="lora_delta" if cfg.lora_rank else "full",
                seq_len=cfg.seq_len, lr=cfg.lr,
                sim_time_ms=float(ckpt_cum_ms[k - 1]) if k else 0.0,
                loss_tail=[float(x) for x in losses[-8:]])

    if cfg.mesh is not None:
        # mesh-sharded whole-cycle flat runtime (DESIGN.md §16); with
        # lora_rank > 0 the trainable per-silo state is the LoRA delta
        # over a frozen base shared by every silo (fl/lora.py)
        from repro.fl import lora as loramod
        from repro.fl import mesh as flmesh
        from repro.fl import runtime as flrt
        from repro.optim import flat_sgd
        init_fn = lambda k: tf.init_params(mcfg, k)
        cycle_loss = loss_fn
        if cfg.lora_rank > 0:
            base = tf.init_params(mcfg, jax.random.PRNGKey(cfg.seed + 1))
            adapter = loramod.make_lora_adapter(base, cfg.lora_rank)
            init_fn = adapter.init
            cycle_loss = adapter.wrap_loss(loss_fn)
        opt = flat_sgd(cfg.lr, momentum=0.9)
        rt = flrt.make_flat_runtime(plan, jax.eval_shape(init_fn, key), n)
        mrt = flmesh.make_mesh_runtime(
            rt, None if cfg.mesh == "auto" else cfg.mesh)
        state = flmesh.init_mesh_state(init_fn, opt, mrt, key)
        cycle = flrt.make_cycle_fn(mrt, loss_fn=cycle_loss, opt=opt,
                                   gossip=cfg.gossip)
        if ckpt_mgr is not None:
            # canonical single-device layout: drop pad rows, restore
            # dst-sorted edge order (DESIGN.md §16) so a D=8 run's
            # checkpoint is bit-identical to the D=1 run's
            ckpt_w = lambda st: flmesh.gather_flat_state(mrt, st).w
        k = 0
        while k < cfg.rounds:
            chunk = min(r_cycle, cfg.rounds - k)
            if ckpt_mgr is not None and cfg.ckpt_every > 0:
                nxt = (k // cfg.ckpt_every + 1) * cfg.ckpt_every
                chunk = min(chunk, nxt - k)
            toks = np.stack([draw_round() for _ in range(chunk)])
            batches = {"tokens": jnp.asarray(toks[:, None, :, :, :-1]),
                       "labels": jnp.asarray(toks[:, None, :, :, 1:])}
            if prefix is not None:
                batches["prefix_embeds"] = jnp.broadcast_to(
                    prefix[None], (chunk,) + prefix.shape)
            pks = [(k + j) % r_cycle for j in range(chunk)]
            span = (recorder.host_span(
                        "compile+dispatch" if k == 0 else "dispatch",
                        start_round=k, rounds=chunk)
                    if recorder is not None else contextlib.nullcontext())
            with span:
                state, chunk_losses = cycle(state, batches,
                                            jnp.asarray(rt.strong[pks]),
                                            jnp.asarray(rt.coeffs[pks]),
                                            jnp.asarray(rt.diag[pks]))
                chunk_losses = np.asarray(chunk_losses)
            losses.extend(float(x) for x in chunk_losses)
            k += chunk
            if ckpt_mgr is not None and (
                    k == cfg.rounds or
                    (cfg.ckpt_every > 0 and k % cfg.ckpt_every == 0)):
                emit_ckpt(k, state)
        # bytes a silo actually communicates per round: the flat row
        # (the LoRA delta when lora_rank > 0, not the frozen base)
        param_bytes = rt.spec.size * 4
    else:
        if cfg.lora_rank:
            raise ValueError("lora_rank requires the mesh runtime "
                             "(set mesh=, e.g. mesh='auto')")
        opt = sgd(cfg.lr, momentum=0.9)
        state = dpasgd.init_fl_state(lambda k: tf.init_params(mcfg, k), opt,
                                     n, plan.src, key)
        step = jax.jit(lambda st, batches, s, c, d: dpasgd.fl_round_step(
            st, batches, plan.src, plan.dst, s, c, d,
            loss_fn=loss_fn, opt=opt, local_updates=1))
        if ckpt_mgr is not None:
            from repro.fl import flat as flatmod
            ckpt_spec = flatmod.make_flat_spec(
                jax.eval_shape(lambda kk: tf.init_params(mcfg, kk), key))
            ckpt_w = lambda st: flatmod.ravel_stacked(ckpt_spec,
                                                      st.silo_params)
        for k in range(cfg.rounds):
            toks = draw_round()
            batches = {"tokens": jnp.asarray(toks[None, :, :, :-1]),
                       "labels": jnp.asarray(toks[None, :, :, 1:])}
            if prefix is not None:
                batches["prefix_embeds"] = prefix
            pk = k % r_cycle
            span = (recorder.host_span(
                        "compile+dispatch" if k == 0 else "dispatch",
                        start_round=k, rounds=1)
                    if recorder is not None else contextlib.nullcontext())
            with span:
                state, loss = step(state, batches,
                                   jnp.asarray(plan.strong[pk]),
                                   jnp.asarray(plan.coeffs[pk]),
                                   jnp.asarray(plan.diag[pk]))
                loss = float(loss)
            losses.append(loss)
            if ckpt_mgr is not None and (
                    k + 1 == cfg.rounds or
                    (cfg.ckpt_every > 0 and (k + 1) % cfg.ckpt_every == 0)):
                emit_ckpt(k + 1, state)
        param_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(state.silo_params)) / n

    # simulated wall-clock (model-size-aware workload)
    wl_model = dataclasses.replace(
        FEMNIST, name=cfg.arch, model_size_mbits=param_bytes * 8 / 1e6)
    from repro.core.simulator import simulate
    sim = simulate(cfg.topology if cfg.topology != "multigraph"
                   else "multigraph", net, wl_model,
                   num_rounds=cfg.rounds, **(
                       {"t": cfg.t} if cfg.topology == "multigraph" else {}))
    out = {
        "arch": cfg.arch, "topology": cfg.topology, "silos": n,
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": losses,
        "train_seconds": round(time.time() - t0, 1),
        "sim_mean_cycle_ms": sim.mean_cycle_ms,
        "sim_total_time_s": sim.total_time_s,
    }
    if ckpt_mgr is not None:
        out["ckpt_dir"] = str(ckpt_mgr.dir)
        out["ckpt_steps"] = ckpt_mgr.steps()
    if recorder is not None:
        from repro.obs import write_trace
        recorder.add_sim_spans(tplan, cfg.rounds)
        write_trace(cfg.trace, recorder)
        out["trace"] = cfg.trace
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m")
    ap.add_argument("--topology", default="multigraph")
    ap.add_argument("--network", default="gaia")
    ap.add_argument("--silos", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--t", type=int, default=5)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default=None,
                    help="silo shards: an int, 'auto', or unset for the "
                         "legacy per-round runtime")
    ap.add_argument("--lora-rank", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="emit FL checkpoints (per-silo flat rows + "
                         "metadata) into this directory")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every K rounds (0 = only at the end)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Perfetto trace-event JSON of the run "
                         "(open at ui.perfetto.dev)")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="dotted config override (repeatable), e.g. "
                         "--set seed=3 --set batch_size=8")
    args = ap.parse_args()
    from repro.config_cli import apply_overrides
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mesh = args.mesh
    if mesh is not None and mesh != "auto":
        mesh = int(mesh)
    cfg = TrainConfig(
        arch=args.arch, topology=args.topology, network=args.network,
        silos=args.silos, rounds=args.rounds, t=args.t,
        seq_len=args.seq_len, batch_size=args.batch_size, lr=args.lr,
        mesh=mesh, lora_rank=args.lora_rank, trace=args.trace,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    out = run_reduced_fl(apply_overrides(cfg, args.overrides))
    out.pop("losses")
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
