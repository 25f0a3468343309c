"""Kernel micro-benchmarks (interpret-mode correctness + XLA-twin

timing on CPU; TPU wall-times are not measurable in this container, so
us_per_call covers the XLA reference path and `derived` records the
kernel's analytic VMEM working set vs the 16 MB budget).

Rows are also emitted as JSON into BENCH_kernels.json (repo cwd) so CI
and downstream tooling can diff them; the `edge_aggregate` rows cover
the CSR aggregation kernel on the paper's gaia (N=11) network with the
FEMNIST CNN parameter count: interpret-mode parity vs the `segment_sum`
reference, the per-round aggregation op-count reduction vs the legacy
per-leaf lowering, and measured CPU wall-clock for the three lowerings.
"""

from __future__ import annotations

import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.gossip_combine.ref import (dense_edge_aggregate,
                                              edge_aggregate_ref,
                                              gossip_combine_ref)
from repro.kernels.ssd_scan.ref import ssd_scan_ref


def _time(fn, *args, iters=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(quick: bool = False):
    rows = []
    key = jax.random.PRNGKey(0)

    # flash attention: (block_q, hd)x(block_k, hd) tiles
    b, hq, hkv, s, hd = 1, 8, 2, 1024, 128
    q = jax.random.normal(key, (b, hq, s, hd), jnp.bfloat16)
    k = jax.random.normal(key, (b, hkv, s, hd), jnp.bfloat16)
    v = jax.random.normal(key, (b, hkv, s, hd), jnp.bfloat16)
    us = _time(jax.jit(lambda a, b_, c: flash_attention_ref(a, b_, c)),
               q, k, v)
    group = hq // hkv
    vmem = (group * 128 * hd + 2 * 128 * hd + group * 128 * hd +
            group * 128 * (2 + hd)) * 4
    rows.append(("kernel/flash_attention/ref_1k", us,
                 f"vmem_tile_bytes={vmem} (<16MB: {vmem < 16e6})"))

    # ssd scan
    bs, seq, h, p, n = 2, 2048, 8, 64, 128
    x = jax.random.normal(key, (bs, seq, h, p))
    dt = jax.nn.softplus(jax.random.normal(key, (bs, seq, h)))
    A = -jnp.exp(jax.random.normal(key, (h,)) * 0.5)
    B = jax.random.normal(key, (bs, seq, n))
    C = jax.random.normal(key, (bs, seq, n))
    us = _time(jax.jit(lambda *a: ssd_scan_ref(*a, chunk=256)),
               x, dt, A, B, C)
    q_ = 256
    vmem = (q_ * (p + 2 * n) + q_ * q_ + p * n + q_) * 4
    rows.append(("kernel/ssd_scan/ref_2k", us,
                 f"vmem_tile_bytes={vmem} (<16MB: {vmem < 16e6})"))

    # gossip combine: fused vs naive HBM traffic
    kk, t = 3, 1 << 22
    w = jax.random.normal(key, (kk, t), jnp.bfloat16)
    a = jnp.asarray([1 / 3] * 3)
    us = _time(jax.jit(gossip_combine_ref), w, a)
    naive = (2 * kk - 1) * t * 2 + t * 2   # k reads + k-1 intermediate rt
    fused = kk * t * 2 + t * 2             # one pass
    rows.append(("kernel/gossip_combine/ref_4M", us,
                 f"hbm_naive={naive} hbm_fused={fused} "
                 f"saving={naive / fused:.2f}x"))

    rows.extend(_edge_aggregate_rows(quick=quick))
    rows.extend(_mesh_cycle_rows(quick=quick))
    _merge_json(rows)
    return rows


def _edge_aggregate_rows(quick: bool = False):
    """CSR edge aggregation on the gaia (N=11) FEMNIST CNN config."""
    from repro.core.delay import FEMNIST
    from repro.fl import dpasgd, flat as flatmod
    from repro.kernels.gossip_combine.kernel import (_pick_block_t,
                                                     block_vmem_bytes)
    from repro.kernels.gossip_combine.ops import csr_sort, edge_aggregate
    from repro.models.small import SMALL_MODELS
    from repro.networks.zoo import get_network

    key = jax.random.PRNGKey(0)
    net = get_network("gaia")
    n = net.num_silos
    plan, _, _ = dpasgd.multigraph_plan(net, FEMNIST, t=5)
    e2 = len(plan.src)
    spec = SMALL_MODELS["femnist_cnn"]
    p0 = spec.init(key)
    fspec = flatmod.make_flat_spec(p0)
    t_full = fspec.size
    # quick mode: shrink T for the interpret-mode pass only
    t_par = (1 << 17) + 1 if quick else t_full

    order, row_ptr = csr_sort(plan.dst, n)
    coeffs = jnp.asarray(plan.coeffs[0][order])
    diag = jnp.asarray(plan.diag[0])
    dst_sorted = jnp.asarray(plan.dst[order])
    rows = []

    # --- parity: kernel == segment_sum reference ---
    w = jax.random.normal(key, (n, t_par), jnp.float32)
    buf = jax.random.normal(jax.random.PRNGKey(1), (e2, t_par), jnp.float32)
    # (compiled on a TPU, the Pallas interpreter elsewhere)
    out = edge_aggregate(w, buf, coeffs, jnp.asarray(row_ptr), diag)
    ref = jax.jit(lambda w_, b_: edge_aggregate_ref(
        w_, b_, coeffs, dst_sorted, diag))(w, buf)
    maxdiff = float(jnp.max(jnp.abs(out - ref)))
    match = bool(np.allclose(np.asarray(out), np.asarray(ref),
                             rtol=1e-5, atol=1e-5))
    block_t = _pick_block_t(t_par, n, e2, 65536)
    vmem = block_vmem_bytes(n, e2, block_t)
    rows.append((f"kernel/edge_aggregate/parity_T{t_par}", 0.0,
                 f"platform={jax.default_backend()} "
                 f"matches_segment_sum={match} "
                 f"maxdiff={maxdiff:.2e} block_t={block_t} "
                 f"vmem_tile_bytes={vmem} (<16MB: {vmem < 16e6})"))

    # --- per-round aggregation op count: legacy per-leaf vs flat ---
    w_tree = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape).copy(), p0)
    buf_tree = jax.tree.map(lambda x: x[plan.src], w_tree)
    coeffs0 = jnp.asarray(plan.coeffs[0])
    dst = jnp.asarray(plan.dst)

    def legacy_agg(wt, bt):
        def aggregate(wall, b):
            c = coeffs0.reshape((-1,) + (1,) * (b.ndim - 1)).astype(b.dtype)
            contrib = jax.ops.segment_sum(c * b, dst, num_segments=n)
            d = diag.reshape((n,) + (1,) * (wall.ndim - 1)).astype(wall.dtype)
            return d * wall + contrib
        return jax.tree.map(aggregate, wt, bt)

    w_flat = flatmod.ravel_stacked(fspec, w_tree)
    buf_flat = flatmod.ravel_stacked(fspec, buf_tree)[jnp.asarray(order)]

    def flat_agg(w_, b_):
        return edge_aggregate_ref(w_, b_, coeffs, dst_sorted, diag)

    deg = int(np.diff(row_ptr)[0])
    cmat = coeffs.reshape(n, deg)

    def dense_agg(w_, b_):
        return dense_edge_aggregate(w_, b_, cmat, diag)

    eq_legacy = len(jax.make_jaxpr(legacy_agg)(w_tree, buf_tree).eqns)
    eq_flat = len(jax.make_jaxpr(flat_agg)(w_flat, buf_flat).eqns)
    us_legacy = _time(jax.jit(legacy_agg), w_tree, buf_tree)
    us_flat = _time(jax.jit(flat_agg), w_flat, buf_flat)
    us_dense = _time(jax.jit(dense_agg), w_flat, buf_flat)
    rows.append((f"kernel/edge_aggregate/legacy_per_leaf_T{t_full}",
                 us_legacy, f"jaxpr_eqns={eq_legacy} leaves="
                 f"{len(jax.tree.leaves(p0))}"))
    rows.append((f"kernel/edge_aggregate/flat_segment_sum_T{t_full}",
                 us_flat, f"jaxpr_eqns={eq_flat} opcount_reduction="
                 f"{eq_legacy / eq_flat:.2f}x"))
    rows.append((f"kernel/edge_aggregate/flat_dense_T{t_full}", us_dense,
                 f"uniform_degree={deg} wallclock_speedup_vs_legacy="
                 f"{us_legacy / us_dense:.2f}x"))
    return rows


def _mesh_cycle_rows(quick: bool = False):
    """Sharded vs single-device whole-cycle scaling (fl/mesh.py).

    Every point parity-checks the sharded cycle against the
    single-device oracle before timing (benchmarks/mesh_cycle_child.py);
    a row with parity=False is a correctness failure, not a slow result.

    On a TPU host the points run in THIS process over `jax.devices()`:
    the process holds the chips, so a child could not reach them. On the
    CPU backend each shard count needs its own forced host-device count,
    fixed at backend init, so every (network, D) point runs in a CHILD
    process with XLA_FLAGS=--xla_force_host_platform_device_count=D.
    Those host devices are threads of nproc physical cores, so
    whole-cycle time does NOT drop with D; the derived field records
    `platform=cpu` and cpu_cores so the rows are never read as chip
    numbers (DESIGN.md §16).
    """
    import os

    points = ([("gaia", d) for d in (1, 2)] if quick else
              [(net, d) for net in ("gaia", "wan64")
               for d in (1, 2, 4, 8)])
    on_cpu = jax.default_backend() == "cpu"
    if not on_cpu:
        points = [(net, d) for net, d in points if d <= jax.device_count()]
    cores = os.cpu_count()
    rows, base_us = [], {}
    for net, d in points:
        name = f"kernel/fl_mesh_cycle/{net}_d{d}"
        if on_cpu:
            out = _mesh_cycle_child(net, d)
            if isinstance(out, str):
                rows.append((name, 0.0, f"FAILED: {out}"))
                continue
            where = (f"platform=cpu cpu_cores={cores} (host devices share "
                     f"{cores} core(s): CPU-bound, see DESIGN.md §16)")
        else:
            from benchmarks.mesh_cycle_child import measure
            out = measure(net, d, 2)
            where = (f"platform={out['platform']} "
                     f"device_kind={out['device_kind']}")
        base_us.setdefault(net, out["us_per_cycle"])
        speedup = base_us[net] / max(out["us_per_cycle"], 1e-9)
        rows.append((
            name, out["us_per_cycle"],
            f"N={out['num_silos']} T={out['t']} "
            f"rounds={out['rounds_per_cycle']} parity={out['parity']} "
            f"halo_rows={out['halo_rows']} speedup_vs_d1={speedup:.2f}x "
            f"{where}"))
    return rows


def _mesh_cycle_child(net: str, d: int):
    """One CPU-backend point in a child process; its JSON dict, or the
    tail of its stderr when it failed."""
    import subprocess
    import sys

    child = pathlib.Path(__file__).parent / "mesh_cycle_child.py"
    src = pathlib.Path(__file__).parent.parent / "src"
    # JAX_PLATFORMS=cpu: the child must not probe accelerator plugins
    # (only the CPU backend takes this route)
    env = {"PYTHONPATH": str(src), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={d}"}
    r = subprocess.run([sys.executable, str(child), net, str(d), "2"],
                       capture_output=True, text=True, timeout=1500, env=env)
    if r.returncode != 0:
        return r.stderr[-200:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _merge_json(rows, path: str = "BENCH_kernels.json") -> None:
    """Own-prefix merge: replace the `kernel/<bench>/` prefixes this run

    produced, keep every other row (so a partial re-run — e.g. only the
    mesh scaling sweep — refreshes its own rows without clobbering the
    rest of the file)."""
    prefixes = tuple({"/".join(name.split("/")[:2]) + "/"
                      for name, _, _ in rows})
    p = pathlib.Path(path)
    existing = []
    if p.exists():
        existing = [r for r in json.loads(p.read_text())
                    if not str(r.get("name", "")).startswith(prefixes)]
    payload = existing + [
        {"name": name, "us_per_call": round(us, 1), "derived": der}
        for name, us, der in rows]
    p.write_text(json.dumps(payload, indent=2) + "\n")
