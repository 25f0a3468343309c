"""One fl_mesh_cycle point for kernel_bench: `measure(net, D, iters)`.

Parity-checks one sharded cycle against the single-device oracle, then
times the sharded whole-cycle dispatch. On a TPU host kernel_bench calls
`measure` in its own process over `jax.devices()`. On the CPU backend
each shard count needs its own forced host-device count, fixed at
backend init, so kernel_bench runs this file as a child per point with
XLA_FLAGS=--xla_force_host_platform_device_count=<D>; it then prints
one JSON line on stdout.

    python benchmarks/mesh_cycle_child.py <network> <num_shards> [iters]
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delay import FEMNIST
from repro.fl import dpasgd, mesh as flmesh, runtime as rtmod
from repro.networks.zoo import get_network
from repro.optim import flat_sgd

D_IN, D_H = 256, 252  # MLP: T = 256*252 + 252 ~= 64.8k


def _init(key):
    return {"w": jax.random.normal(key, (D_IN, D_H)) * 0.05,
            "b": jnp.zeros((D_H,))}


def _loss(p, batch):
    return jnp.mean((batch["x"] @ p["w"] + p["b"]) ** 2)


def measure(net_name: str, d: int, iters: int = 3) -> dict:
    assert jax.device_count() >= d, (jax.device_count(), d)

    net = get_network(net_name)
    n = net.num_silos
    plan, _, _ = dpasgd.multigraph_plan(net, FEMNIST, t=5)
    r = plan.num_rounds_cycle
    key = jax.random.PRNGKey(0)
    opt = flat_sgd(0.05, momentum=0.9)
    rt = rtmod.make_flat_runtime(plan, jax.eval_shape(_init, key), n)
    rng = np.random.default_rng(0)
    batches = {"x": jnp.asarray(rng.normal(size=(r, 1, n, 2, D_IN)),
                                jnp.float32)}
    args = (batches, jnp.asarray(rt.strong), jnp.asarray(rt.coeffs),
            jnp.asarray(rt.diag))

    mrt = flmesh.make_mesh_runtime(rt, d)
    state = flmesh.init_mesh_state(_init, opt, mrt, key)
    cycle = rtmod.make_cycle_fn(mrt, loss_fn=_loss, opt=opt)

    # parity vs the single-device oracle, full cycle, before timing (the
    # oracle aggregates by segment_sum, as every shard does)
    s1 = rtmod.init_flat_state(_init, opt, rt, key)
    c1 = rtmod.make_cycle_fn(rt, loss_fn=_loss, opt=opt,
                             aggregator="reference")
    s1, _ = c1(s1, *args)
    sm, _ = cycle(state, *args)
    flat = flmesh.gather_flat_state(mrt, sm)
    parity = (np.array_equal(np.asarray(s1.w), np.asarray(flat.w))
              and np.array_equal(np.asarray(s1.buffers),
                                 np.asarray(flat.buffers)))

    jax.block_until_ready(sm)
    t0 = time.perf_counter()
    for _ in range(iters):
        sm, losses = cycle(sm, *args)
    jax.block_until_ready(sm)
    us = (time.perf_counter() - t0) / iters * 1e6

    dev = jax.devices()[0]
    return {
        "net": net_name, "num_silos": n, "d": d, "t": rt.spec.size,
        "rounds_per_cycle": r, "us_per_cycle": round(us, 1),
        "parity": bool(parity), "halo_rows": mrt.halo.halo_rows,
        "trace_count": cycle.trace_count["count"],
        "platform": dev.platform, "device_kind": dev.device_kind,
    }


def main():
    iters = int(sys.argv[3]) if len(sys.argv) > 3 else 3
    print(json.dumps(measure(sys.argv[1], int(sys.argv[2]), iters)))


if __name__ == "__main__":
    main()
