"""Smoke run of the federated training cycle on one TPU.

    python chip_smoke.py             # one chip: the phases below
    python chip_smoke.py --chips 4   # the silo-mesh phase only, 4 chips

One process drives the chip through the entry points a user calls:

1. FL training at the paper's FEMNIST width: `run_fl` on the gaia
   multigraph (N=11 silos, T=1,280,478 parameters, batch 32, 128
   samples per silo, t=5) for two whole cycles, evaluating once per
   cycle. Every round loss and accuracy must be finite.
2. Aggregator parity: the default aggregator of `make_cycle_fn` (the
   compiled Pallas `edge_aggregate` on a TPU; its program must hold a
   `tpu_custom_call`) against the `segment_sum` reference.
   - One aggregation of a trained state, and one aggregation-only cycle
     (learning rate 0, every round's refresh and aggregation) from it:
     both lowerings sum in one order, so each must agree with the other
     within 1e-6 * max|w|, and the single aggregation also with a host
     fp32 sum in that order.
   - One training cycle of each from the same state and batches,
     reported beside a control: the reference from the same state
     nudged by one ulp. Local SGD amplifies ulp-level differences, so
     the training cycle is compared with what one ulp does, not with a
     fixed bound.
   This phase also times compilation (persistent cache off) and steady
   cycles.
3. Device timing grid: the `core/sweep.py --check` consistency check on
   the paper's gaia/femnist multigraph cell, which holds the f64 grid
   on the device (`backend="jax"`) bit-equal to the numpy oracle.

`--chips 4` runs only the sharded runtime: `run_fl` with
`mesh=fl_mesh(4)` (3 silo rows per shard plus one pad row) against the
single-device run, an aggregation-only cycle of both from one trained
state (within 1e-6 * max|w|), and checks that the shards sit on four
devices and that the halo exchange compiles to collective-permutes.

Set-up figures print on their own lines by plain name. The last line of
stdout is one JSON object naming the device. The script exits non-zero
on any failure, and before any work when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import tempfile
import time

import jax

REPO = pathlib.Path(__file__).resolve().parent
LR = 0.05          # FLConfig's default learning rate
PARITY = 1e-6      # |dw| bound, relative to max|w|, for pure aggregation


def _device_or_exit():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {dev.platform!r} "
                 f"({dev.device_kind}), not a TPU")
    return dev


def _phase(name):
    print(f"== {name}", flush=True)


def _gaia_femnist(**kw):
    from repro.fl import FLConfig

    return FLConfig(dataset="femnist", network="gaia",
                    topology="multigraph", **kw)


class _Setup:
    """The FEMNIST/gaia flat runtime and one cycle of random batches."""

    def __init__(self):
        import jax.numpy as jnp
        import numpy as np

        from repro.core.delay import WORKLOADS
        from repro.fl import dpasgd
        from repro.fl import runtime as flrt
        from repro.models.small import SMALL_MODELS
        from repro.networks.zoo import get_network

        net = get_network("gaia")
        self.n = net.num_silos
        self.spec = SMALL_MODELS["femnist_cnn"]
        self.key = jax.random.PRNGKey(0)
        plan, _ = dpasgd.make_round_schedule(
            "multigraph", net, WORKLOADS["femnist"], t=5, rounds=1, seed=0)
        self.rt = flrt.make_flat_runtime(
            plan, jax.eval_shape(self.spec.init, self.key), self.n)
        self.r = self.rt.num_rounds_cycle
        rng = np.random.default_rng(0)
        shape = (self.r, 1, self.n, 32)
        self.batches = {
            "x": jnp.asarray(rng.normal(size=shape + self.spec.input_shape),
                             jnp.float32),
            "y": jnp.asarray(rng.integers(0, self.spec.num_classes, shape),
                             jnp.int32)}
        self.plan_args = (jnp.asarray(self.rt.strong),
                          jnp.asarray(self.rt.coeffs),
                          jnp.asarray(self.rt.diag))

    def cycle(self, lr, aggregator=None, rt=None):
        from repro.fl import runtime as flrt
        from repro.optim import flat_sgd

        return flrt.make_cycle_fn(rt or self.rt,
                                  loss_fn=lambda p, b: self.spec.loss(p, b),
                                  opt=flat_sgd(lr), aggregator=aggregator)

    def state(self, w=None):
        """A fresh single-device state: the initial one, or rows `w` with
        freshly refreshed edge buffers."""
        import jax.numpy as jnp

        from repro.fl import runtime as flrt
        from repro.optim import flat_sgd

        s = flrt.init_flat_state(self.spec.init, flat_sgd(LR), self.rt,
                                 self.key)
        if w is None:
            return s
        w = jnp.asarray(w)
        return flrt.FlatFLState(w, s.opt_state,
                                w[jnp.asarray(self.rt.src_sorted)])


def _max_dw(a, b) -> tuple[float, float]:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    assert np.isfinite(a).all() and np.isfinite(b).all()
    return float(np.max(np.abs(a - b))), float(np.max(np.abs(b)))


@contextlib.contextmanager
def _persistent_cache_off():
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def train_phase(r: int) -> None:
    import numpy as np

    from repro.fl import run_fl

    _phase(f"run_fl femnist/gaia multigraph, {2 * r} rounds")
    t0 = time.perf_counter()
    res = run_fl(_gaia_femnist(rounds=2 * r, eval_every=r))
    print("run_fl_seconds:", time.perf_counter() - t0)
    print("round_losses:", res.round_losses)
    print("eval_accs:", res.eval_accs)
    assert len(res.round_losses) == 2 * r, len(res.round_losses)
    assert res.eval_rounds == [r, 2 * r], res.eval_rounds
    assert np.isfinite(res.round_losses).all(), "non-finite round loss"
    assert np.isfinite(res.eval_accs).all(), "non-finite accuracy"


def _host_aggregate(w, buf, coeffs, row_ptr, diag):
    """fp32 on the host in the kernel's and segment_sum's order."""
    import numpy as np

    out = np.empty_like(w)
    for i in range(w.shape[0]):
        acc = np.zeros(w.shape[1], np.float32)
        for e in range(row_ptr[i], row_ptr[i + 1]):
            acc = acc + coeffs[e] * buf[e]
        out[i] = diag[i] * w[i] + acc
    return out


def parity_phase(s: _Setup) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.fl import runtime as flrt
    from repro.kernels.gossip_combine import ops as gossip_ops
    from repro.kernels.gossip_combine.ref import edge_aggregate_ref

    _phase("aggregator parity: Pallas edge_aggregate vs segment_sum")
    rt = s.rt
    print("N:", s.n, "T:", rt.spec.size, "2E:", len(rt.src_sorted),
          "R:", s.r)
    fresh = lambda st: jax.tree.map(jnp.copy, st)
    state0 = s.state()

    # one training cycle each, from the same state and batches
    lowered = s.cycle(LR).lower(state0, s.batches, *s.plan_args)
    with _persistent_cache_off():
        t0 = time.perf_counter()
        kernel_cycle = lowered.compile()
        compile_s = time.perf_counter() - t0
    assert "tpu_custom_call" in kernel_cycle.as_text(), \
        "default aggregator did not compile to the Pallas kernel"
    trained, losses_k = kernel_cycle(fresh(state0), s.batches, *s.plan_args)
    w_k = np.asarray(trained.w)
    secs, st = [], fresh(trained)
    for _ in range(3):
        t0 = time.perf_counter()
        st, _ = kernel_cycle(st, s.batches, *s.plan_args)
        jax.block_until_ready(st)
        secs.append(time.perf_counter() - t0)
    ref_cycle = s.cycle(LR, "reference")
    out_r, losses_r = ref_cycle(fresh(state0), s.batches, *s.plan_args)
    nudged = flrt.FlatFLState(
        jnp.asarray(np.nextafter(np.asarray(state0.w), np.float32(np.inf))),
        state0.opt_state, jnp.copy(state0.buffers))
    out_c, _ = ref_cycle(nudged, s.batches, *s.plan_args)
    dw_train, scale = _max_dw(w_k, out_r.w)
    dw_ulp, _ = _max_dw(out_c.w, out_r.w)
    print("compile_seconds:", compile_s)
    print("steady_seconds_per_cycle:", secs)
    print("training_cycle_kernel_vs_reference_max_abs_dw:", dw_train,
          "max_abs_w:", scale)
    print("training_cycle_one_ulp_nudge_max_abs_dw:", dw_ulp)
    print("training_cycle_max_abs_dloss:", float(np.max(np.abs(
        np.asarray(losses_k) - np.asarray(losses_r)))))
    print("training_cycle_bit_equal:", bool(np.array_equal(w_k, out_r.w)))

    # one aggregation of the trained state, round 0 of the cycle
    w, buf = trained.w, trained.buffers
    coeffs, diag = jnp.asarray(rt.coeffs[0]), jnp.asarray(rt.diag[0])
    agg_k = np.asarray(gossip_ops.edge_aggregate(
        w, buf, coeffs, jnp.asarray(rt.row_ptr), diag))
    agg_r = np.asarray(jax.jit(edge_aggregate_ref)(
        w, buf, coeffs, jnp.asarray(rt.dst_sorted), diag))
    agg_h = _host_aggregate(np.asarray(w), np.asarray(buf), rt.coeffs[0],
                            rt.row_ptr, rt.diag[0])
    dk, scale = _max_dw(agg_k, agg_h)
    dr, _ = _max_dw(agg_r, agg_h)
    print("aggregation_kernel_vs_host_max_abs_dw:", dk, "max_abs_w:", scale)
    print("aggregation_reference_vs_host_max_abs_dw:", dr)
    print("aggregation_kernel_bit_equal_host:",
          bool(np.array_equal(agg_k, agg_h)))
    print("aggregation_reference_bit_equal_host:",
          bool(np.array_equal(agg_r, agg_h)))
    assert dk <= PARITY * scale and dr <= PARITY * scale, (dk, dr, scale)

    # an aggregation-only cycle (lr 0) from the trained state
    ws = [np.asarray(s.cycle(0.0, agg)(fresh(trained), s.batches,
                                       *s.plan_args)[0].w)
          for agg in (None, "reference")]
    dw, scale = _max_dw(*ws)
    print("aggregation_cycle_kernel_vs_reference_max_abs_dw:", dw,
          "max_abs_w:", scale)
    print("aggregation_cycle_bit_equal:", bool(np.array_equal(*ws)))
    assert dw <= PARITY * scale, (dw, scale)


def grid_phase() -> None:
    import numpy as np

    from repro.core import timing
    from repro.core.sweep import (SweepConfig, build_sweep_plans,
                                  consistency_check)

    _phase("device timing grid (f64) vs numpy oracle, gaia/femnist")
    cfg = SweepConfig(topologies=("multigraph",), networks=("gaia",),
                      workloads=("femnist",))
    grid = timing.build_timing_grid(build_sweep_plans(cfg)[0])
    dev, host = (grid.cycle_time_matrix(cfg.num_rounds, backend=b)
                 for b in ("jax", "numpy"))
    rel = np.abs(dev - host) / np.abs(host)
    print("grid_cycle_times_bit_equal:", bool(np.array_equal(dev, host)))
    print("grid_cycle_times_max_rel_diff:", float(rel.max()),
          "rounds_differing:", int(np.count_nonzero(dev != host)),
          "of", host.size)
    # bit-exact where f64 is native; within EMULATED_F64_RTOL on a TPU
    consistency_check(cfg)


def mesh_phase(s: _Setup) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import load_fl_checkpoint
    from repro.fl import mesh as flmesh
    from repro.fl import run_fl
    from repro.fl import runtime as flrt
    from repro.launch.mesh import fl_mesh

    _phase(f"silo mesh over {jax.device_count()} chips vs one chip")
    mesh = fl_mesh(4)
    r = s.r
    with tempfile.TemporaryDirectory(dir=REPO) as d:
        kw = dict(rounds=r, eval_every=r)
        one = run_fl(_gaia_femnist(ckpt_dir=f"{d}/one", **kw))
        four = run_fl(_gaia_femnist(ckpt_dir=f"{d}/four", mesh=mesh, **kw))
        w1 = np.asarray(load_fl_checkpoint(f"{d}/one").w)
        w4 = np.asarray(load_fl_checkpoint(f"{d}/four").w)
    assert w1.shape == w4.shape, (w1.shape, w4.shape)
    assert np.isfinite(four.round_losses).all()
    dw, scale = _max_dw(w4, w1)
    print("training_cycle_mesh_vs_single_max_abs_dw:", dw,
          "max_abs_w:", scale)
    print("training_cycle_mesh_vs_single_max_abs_dloss:", float(np.max(
        np.abs(np.asarray(one.round_losses) -
               np.asarray(four.round_losses)))))
    print("eval_accs_single:", one.eval_accs, "mesh:", four.eval_accs)

    # an aggregation-only cycle (lr 0) from the trained single-device
    # rows: the halo exchange and per-shard aggregation against the
    # single-device kernel
    mrt = flmesh.make_mesh_runtime(s.rt, mesh)
    flat0 = s.state(w1)
    n, rows = s.n, mrt.mspec.rows_padded
    w_pad = np.zeros((rows, w1.shape[1]), np.float32)
    w_pad[:n] = w1
    buf = np.asarray(flat0.buffers)
    buf_pad = np.concatenate([buf, np.zeros_like(buf[:1])])[mrt.edge_perm]
    mstate = mrt.mspec.shard_tree(mesh, flrt.FlatFLState(
        jnp.asarray(w_pad), {"step": jnp.zeros((), jnp.int32)},
        jnp.asarray(buf_pad)))
    mcycle = s.cycle(0.0, rt=mrt)
    compiled = mcycle.lower(mstate, s.batches, *s.plan_args).compile()
    permutes = compiled.as_text().count("collective-permute")
    print("compiled_collective_permutes:", permutes)
    assert permutes > 0, "halo exchange compiled without a ppermute"
    mout, _ = compiled(mstate, s.batches, *s.plan_args)
    devs = {x.device for x in mout.w.addressable_shards}
    print("w_sharding:", mout.w.sharding)
    print("w_shard_devices:", sorted(str(x) for x in devs))
    print("w_shard_shape:", mout.w.addressable_shards[0].data.shape)
    assert len(devs) == 4, devs
    sout, _ = s.cycle(0.0)(flat0, s.batches, *s.plan_args)
    gathered = flmesh.gather_flat_state(mrt, mout)
    dw, scale = _max_dw(gathered.w, sout.w)
    dbuf, _ = _max_dw(gathered.buffers, sout.buffers)
    print("aggregation_cycle_mesh_vs_single_max_abs_dw:", dw,
          "max_abs_w:", scale, "max_abs_dbuf:", dbuf)
    print("aggregation_cycle_mesh_bit_equal:",
          bool(np.array_equal(gathered.w, sout.w)))
    assert dw <= PARITY * scale and dbuf <= PARITY * scale, (dw, dbuf, scale)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    dev = _device_or_exit()
    if jax.device_count() < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {jax.device_count()}")
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print("compile_cache:", enable_compile_cache())
    print("device:", dev.platform, dev.device_kind, jax.device_count())
    s = _Setup()
    if args.chips == 4:
        mesh_phase(s)
    else:
        train_phase(s.r)
        parity_phase(s)
        grid_phase()
    stats = dev.memory_stats() or {}
    print("peak_bytes_in_use:", stats.get("peak_bytes_in_use", "not reported"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
