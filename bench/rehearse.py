"""Compile each cell's training cycle for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <name> ...]

For every cell of `BENCHMARK.json` (or the ones named) this builds the
cycle `run_fl` would build, at full width, and compiles it for the
devices of a described `v5e:2x2`: one device for a one-chip cell (the
Pallas aggregator, as on a TPU), a silo mesh over the cell's chips
otherwise. It prints, per device, the bytes `memory_analysis()` gives
(arguments, outputs, aliased, temporaries), whether the kernel is in
the program and how many collective-permutes it holds. A cell that
does not fit fails here, as the chip's compiler would refuse it, before
any chip time is spent on it. Nothing runs.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def rehearse(cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.delay import WORKLOADS
    from repro.fl import dpasgd
    from repro.fl import mesh as flmesh
    from repro.fl import runtime as flrt
    from repro.fl.trainer import _DATASET_MODEL, _DATASET_WL
    from repro.models.small import SMALL_MODELS
    from repro.networks.zoo import get_network
    from repro.optim import flat_sgd

    t, ds = cell.traffic, cell.config["dataset"]
    net = get_network(t["network"])
    spec = SMALL_MODELS[_DATASET_MODEL[ds]]
    plan, _ = dpasgd.make_round_schedule(t["topology"], net,
                                         WORKLOADS[_DATASET_WL[ds]],
                                         t=t["t"], rounds=1, seed=0)
    n = net.num_silos
    rt = flrt.make_flat_runtime(plan, jax.eval_shape(
        spec.init, jax.random.PRNGKey(0)), n)
    r, e2, size = rt.num_rounds_cycle, len(rt.src_sorted), rt.spec.size
    if cell.chips == 1:
        put = SingleDeviceSharding(topo.devices[0])
        row = edge = rep = put
        rows, edges = n, e2
    else:
        mesh = Mesh(topo.devices[:cell.chips], ("silo",))
        rt = flmesh.make_mesh_runtime(rt, mesh)
        row = edge = NamedSharding(mesh, P("silo", None))
        rep = NamedSharding(mesh, P())
        rows, edges = rt.mspec.rows_padded, rt.mspec.edges_padded

    def shape(dims, sharding, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    cycle = flrt.make_cycle_fn(rt, loss_fn=lambda p, b: spec.loss(p, b),
                               opt=flat_sgd(t["lr"]))
    state = flrt.FlatFLState(shape((rows, size), row),
                             {"step": shape((), rep, jnp.int32)},
                             shape((edges, size), edge))
    u, b = t["local_updates"], t["batch_size"]
    batches = {"x": shape((r, u, n, b) + spec.input_shape, rep),
               "y": shape((r, u, n, b), rep, jnp.int32)}
    compiled = cycle.lower(state, batches, shape((r, e2), rep, jnp.bool_),
                           shape((r, e2), rep), shape((r, n), rep)).compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    return {"workload": cell.name, "chips": cell.chips, "silos": n,
            "edges": e2, "params": size, "rounds_per_dispatch": r,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "kernel": "tpu_custom_call" in hlo,
            "collective_permutes": hlo.count("collective-permute-start")
            or hlo.count("collective-permute(")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies

    from bench.core import cell as cellmod
    names = args.workload or [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the runtime's TPU branches
    try:
        for name in names:
            print(json.dumps(rehearse(cellmod.load(name), topo)), flush=True)
    finally:
        jax.default_backend = real
    return 0


if __name__ == "__main__":
    sys.exit(main())
