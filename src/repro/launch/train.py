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
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import get_config, reduce as reduce_cfg
from repro.core.delay import FEMNIST, WORKLOADS
from repro.data.synthetic import make_lm_dataset
from repro.fl import dpasgd
from repro.fl import lora as loramod
from repro.fl import mesh as flmesh
from repro.fl import runtime as flrt
from repro.fl.trainer import _drive, _recorder
from repro.models import transformer as tf
from repro.models.frontends import synthetic_prefix
from repro.networks.zoo import NetworkSpec, get_network
from repro.optim import flat_sgd


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
    # Silo-axis device mesh (DESIGN.md §16): None = one device, an int
    # = that many shards, "auto" = every host device, or a 1-D Mesh.
    mesh: object = None
    # Mesh only: cross-shard collective, "halo" or "all_gather".
    gossip: str = "halo"
    # Rejected: in-scan metrics are FLConfig's (§17).
    metrics: object = None
    # Path for a Perfetto trace-event JSON of the run; None = off.
    trace: str | None = None
    # rank > 0 trains LoRA deltas over a frozen shared base
    # (fl/lora.py) so per-silo state is T_lora, not T_full.
    lora_rank: int = 0
    # Periodic FL checkpoints (checkpoint/ckpt.py): per-silo flat rows
    # (the LoRA delta rows when lora_rank > 0) + metadata every
    # ckpt_every rounds and at the end; the serving fleet loads them.
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    ckpt_keep: int = 8

    def __post_init__(self):
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
    recorder = _recorder(cfg.trace, arch=cfg.arch, topology=cfg.topology,
                         network=net.name, rounds=cfg.rounds, seed=cfg.seed)
    key = jax.random.PRNGKey(cfg.seed)
    data = make_lm_dataset(mcfg.vocab_size, cfg.seq_len, n,
                           samples_per_silo=64, seed=cfg.seed)
    prefix = None
    if mcfg.frontend != "none":
        prefix = np.stack([synthetic_prefix(mcfg, cfg.batch_size, seed=s)
                           for s in range(n)])[None]  # (1, N, B, P, D)

    def loss_fn(p, batch):
        b = {"tokens": batch["tokens"], "labels": batch["labels"]}
        if "prefix_embeds" in batch:
            b["prefix_embeds"] = batch["prefix_embeds"]
        loss, _ = tf.loss_fn(p, mcfg, b)
        return loss

    rng = np.random.default_rng(cfg.seed)

    def draw(chunk):
        toks = np.stack([np.stack([
            data[s][rng.integers(0, len(data[s]), cfg.batch_size)]
            for s in range(n)]) for _ in range(chunk)])  # (r, N, B, S+1)
        batches = {"tokens": toks[:, None, :, :, :-1],
                   "labels": toks[:, None, :, :, 1:]}
        if prefix is not None:
            batches["prefix_embeds"] = np.broadcast_to(
                prefix[None], (chunk,) + prefix.shape)
        return batches

    t0 = time.time()
    # with lora_rank > 0 the trainable per-silo state is the LoRA delta
    # over a frozen base shared by every silo (fl/lora.py)
    init_fn = lambda k: tf.init_params(mcfg, k)
    cycle_loss = loss_fn
    if cfg.lora_rank > 0:
        base = tf.init_params(mcfg, jax.random.PRNGKey(cfg.seed + 1))
        adapter = loramod.make_lora_adapter(base, cfg.lora_rank)
        init_fn = adapter.init
        cycle_loss = adapter.wrap_loss(loss_fn)
    opt = flat_sgd(cfg.lr, momentum=0.9)
    rt, state, rows = flmesh.place_runtime(
        flrt.make_flat_runtime(plan, jax.eval_shape(init_fn, key), n),
        cfg.mesh, init_fn, opt, key)
    cycle = flrt.make_cycle_fn(
        rt, loss_fn=cycle_loss, opt=opt,
        gossip=cfg.gossip if isinstance(rt, flmesh.MeshRuntime) else None)

    checkpoint = None
    if cfg.ckpt_dir:
        from repro.checkpoint import CheckpointManager, save_fl_checkpoint
        ckpt_mgr = CheckpointManager(cfg.ckpt_dir, keep=cfg.ckpt_keep)
        ckpt_cum_ms = np.cumsum(tplan.cycle_times(cfg.rounds))

        def checkpoint(k, state, log):
            save_fl_checkpoint(
                ckpt_mgr, k, rows(state),
                round=k, arch=cfg.arch, network=cfg.network,
                dataset="synthetic-lm", workload="femnist",
                topology=cfg.topology, t=cfg.t, seed=cfg.seed,
                num_silos=n, lora_rank=cfg.lora_rank,
                params_kind="lora_delta" if cfg.lora_rank else "full",
                seq_len=cfg.seq_len, lr=cfg.lr,
                sim_time_ms=float(ckpt_cum_ms[k - 1]) if k else 0.0,
                loss_tail=[float(x) for x in log.losses[-8:]])

    losses = _drive(rt, state, cycle, draw, rounds=cfg.rounds,
                    recorder=recorder, ckpt_every=cfg.ckpt_every,
                    checkpoint=checkpoint).losses
    # bytes a silo actually communicates per round: the flat row
    # (the LoRA delta when lora_rank > 0, not the frozen base)
    param_bytes = rt.spec.size * 4

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
    if cfg.ckpt_dir:
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
                    help="silo shards: an int, 'auto', or unset for one "
                         "device")
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
