"""Train -> checkpoint -> regional fleet -> traffic loop (DESIGN.md
§18) plus the seams it rides on: the network registry, the LM
trainer's host spans, and the FL-checkpoint format's mesh/single-device
round-trip contract.

One tiny reduced-LM FL run (module-scoped fixture) feeds every fleet
test; the D=8 sharded round-trip runs in a subprocess with forced
host devices (slow tier), mirroring tests/test_fl_mesh.py.
"""

import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_fl_checkpoint
from repro.launch.train import TrainConfig, run_reduced_fl
from repro.serving import (REGION_ANCHORS, RegionalFleet, TrafficConfig,
                           generate_requests, nearest_region, simulate,
                           sweep_loads)

TINY = dict(arch="mamba2-370m", network="gaia", silos=6, rounds=3, t=2,
            seq_len=16, batch_size=2)


@pytest.fixture(scope="module")
def ckpt_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("serve_ckpt")
    out = run_reduced_fl(TrainConfig(**TINY, ckpt_dir=str(d),
                                     ckpt_every=2))
    assert out["ckpt_steps"] == [2, 3]
    return str(d)


@pytest.fixture(scope="module")
def fleet(ckpt_dir):
    return RegionalFleet.from_checkpoint(ckpt_dir, max_slots=4,
                                         max_seq=64)


# ---------------------------------------------------------------------------
# satellite seams: registry
# ---------------------------------------------------------------------------

class TestNetworkRegistry:
    def test_fixed_and_pattern_lookup(self):
        from repro.networks.registry import get_network, list_networks
        assert get_network("gaia").num_silos == 11
        assert get_network("wan12").num_silos == 12
        assert get_network("gaia", capacity_gbps=2.0).upload_gbps().max() \
            < get_network("gaia").upload_gbps().max()
        names = list_networks()
        assert {"gaia", "amazon", "geant", "exodus", "ebone"} <= set(names)
        assert "wan<K>" in list_networks(include_patterns=True)

    def test_unknown_name_lists_known(self):
        from repro.networks.registry import get_network
        with pytest.raises(KeyError, match="gaia"):
            get_network("nope")

    def test_zoo_shims_deprecated_but_identical(self):
        from repro.networks import zoo
        with pytest.warns(DeprecationWarning):
            old = zoo.gaia()
        new = zoo.get_network("gaia")
        np.testing.assert_array_equal(old.latency_ms, new.latency_ms)


# ---------------------------------------------------------------------------
# checkpoint round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh", [None, 1])
def test_run_reduced_fl_host_spans_per_dispatch(tmp_path, mesh):
    """`run_reduced_fl` trains through `run_fl`'s driver on one device
    and on the mesh alike: the same host spans, one set a dispatch."""
    import repro.obs as obs
    kept = []

    class Kept(obs.TraceRecorder):
        def __post_init__(self):
            super().__post_init__()
            kept.append(self)

    real, obs.TraceRecorder = obs.TraceRecorder, Kept
    try:
        run_reduced_fl(TrainConfig(**dict(TINY, topology="ring", rounds=3),
                                   mesh=mesh,
                                   trace=str(tmp_path / "t.json")))
    finally:
        obs.TraceRecorder = real
    (rec,) = kept
    ev = rec.host_events
    end = lambda e: e["t0_ms"] + e["dur_ms"]
    disp = [e for e in ev if e["name"] in ("compile+dispatch", "dispatch")]
    assert [e["name"] for e in disp] == ["compile+dispatch"] + ["dispatch"] * 2
    for name in ("sample", "copy", "launch", "sync"):
        assert len([e for e in ev if e["name"] == name]) == len(disp), name
    for d in disp:
        # the jit call and the loss sync, in order, inside the dispatch
        launch, sync = [e for e in ev if e["parent"] == d["name"]
                        and d["t0_ms"] <= e["t0_ms"] and end(e) <= end(d)]
        assert (launch["name"], sync["name"]) == ("launch", "sync")
        assert end(launch) <= sync["t0_ms"]
        assert launch["args"] == {"rounds": 1}
    samples = [e for e in ev if e["name"] == "sample"]
    copies = [e for e in ev if e["name"] == "copy"]
    for s, c, d in zip(samples, copies, disp):
        assert s["parent"] is None and c["parent"] is None
        assert end(s) <= c["t0_ms"] and end(c) <= d["t0_ms"]
        assert s["args"] == {"rounds": 1}
        # one round of 6 silos x 2 sequences: int32 tokens and labels
        assert c["args"] == {"bytes": 2 * 6 * 2 * TINY["seq_len"] * 4}


def test_checkpoint_single_device_vs_mesh_bitexact(tmp_path):
    cfg = dict(TINY, rounds=2)
    run_reduced_fl(TrainConfig(**cfg, ckpt_dir=str(tmp_path / "a")))
    run_reduced_fl(TrainConfig(**cfg, mesh=1,
                               ckpt_dir=str(tmp_path / "b")))
    a = load_fl_checkpoint(str(tmp_path / "a"))
    b = load_fl_checkpoint(str(tmp_path / "b"))
    np.testing.assert_array_equal(a.w, b.w)
    assert a.meta["round"] == b.meta["round"] == 2
    assert a.meta["sim_time_ms"] == b.meta["sim_time_ms"]


@pytest.mark.slow
def test_checkpoint_mesh_d8_roundtrip(tmp_path):
    """The bugfix contract: a run sharded over 8 devices gathers via
    `gather_flat_state` before saving, so its checkpoint has the
    single-device layout (shape, dst-sorted rows, no padding) and
    matches the D=1 run to the last float32 ulp. Exact bit-identity
    across DIFFERENT shard counts is not attainable for the
    transformer loss — XLA tiles the per-shard matmuls differently —
    so the tolerance is one ulp of the parameter scale; a missing
    gather (pad rows saved, block-permuted order) fails by orders of
    magnitude."""
    script = (pathlib.Path(__file__).parent / "mp_scripts"
              / "serve_ckpt_check.py")
    d8 = tmp_path / "d8"
    r = subprocess.run(
        [sys.executable, str(script), str(d8)],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "d8-mesh-ckpt-ok" in r.stdout, r.stdout
    run_reduced_fl(TrainConfig(**dict(TINY, rounds=2), mesh=1,
                               ckpt_dir=str(tmp_path / "d1")))
    a = load_fl_checkpoint(str(tmp_path / "d1"))
    b = load_fl_checkpoint(str(d8))
    assert a.w.shape == b.w.shape
    assert a.meta["round"] == b.meta["round"]
    np.testing.assert_allclose(a.w, b.w, rtol=0, atol=1e-7)


def test_serving_older_step_records_staleness(ckpt_dir):
    f = RegionalFleet.from_checkpoint(ckpt_dir, step=2, max_slots=2,
                                      max_seq=64)
    assert f.ckpt.step == 2
    assert f.staleness_lag_ms > 0.0
    assert f.staleness_ms(10.0) == pytest.approx(
        f.staleness_lag_ms + 10.0)


# ---------------------------------------------------------------------------
# fleet: regions, routing, per-region variants
# ---------------------------------------------------------------------------

def test_region_partition_and_routing(fleet):
    idxs = sorted(i for r in fleet.regions.values()
                  for i in r.silo_indices)
    assert idxs == list(range(6))  # every training silo, exactly once
    assert set(fleet.regions) <= set(REGION_ANCHORS)
    from repro.networks.registry import get_network
    net = get_network("gaia")
    for rname, reg in fleet.regions.items():
        for i in reg.silo_indices:
            s = net.silos[i]
            # a silo's own coordinates route back to its region
            assert fleet.route(s.lat, s.lon) == rname
            assert nearest_region(s.lat, s.lon) == rname


def test_region_variants_route_distinct_logits(fleet):
    """Regions serve their own silo rows: the SAME prompt produces
    different logits in different regions (and bit-identical logits in
    the same region), so routing is observable at the model output."""
    from repro.models import transformer as tf
    prompt = [3, 5, 7, 2]

    def logits_of(region):
        eng = fleet.regions[region].engine
        st = tf.init_decode_state(eng.cfg, 1, 16)
        out = None
        for k, tok in enumerate(prompt):
            out, st = tf.decode_step(
                eng.params, eng.cfg,
                jnp.asarray([[tok]], jnp.int32), st)
        return np.asarray(out[0, -1])

    names = list(fleet.regions)
    base = logits_of(names[0])
    np.testing.assert_array_equal(base, logits_of(names[0]))
    for other in names[1:]:
        assert not np.allclose(base, logits_of(other)), \
            f"{names[0]} and {other} serve identical variants"


# ---------------------------------------------------------------------------
# traffic: determinism, nesting, drain
# ---------------------------------------------------------------------------

CFG = TrafficConfig(seed=0, duration_ms=400.0, step_ms=10.0)


def test_traffic_deterministic_replay(fleet):
    a = simulate(fleet, CFG, 60.0)
    b = simulate(fleet, CFG, 60.0)
    assert [(r.t_gen, r.site, r.prompt, r.t_done) for r in a.requests] \
        == [(r.t_gen, r.site, r.prompt, r.t_done) for r in b.requests]
    assert a.summary == b.summary


def test_loads_nest_and_p99_monotone(fleet):
    loads = [20.0, 60.0, 120.0]
    traces = {ld: generate_requests(fleet, CFG, ld) for ld in loads}
    keys = {ld: {(r.t_gen, r.site) for r in traces[ld]} for ld in loads}
    assert keys[20.0] <= keys[60.0] <= keys[120.0]
    # shared arrivals carry identical content at every load
    by_key = {(r.t_gen, r.site): (r.prompt, r.new_tokens, r.region)
              for r in traces[120.0]}
    for ld in (20.0, 60.0):
        for r in traces[ld]:
            assert by_key[(r.t_gen, r.site)] == \
                (r.prompt, r.new_tokens, r.region)
    res = sweep_loads(fleet, CFG, loads)
    p99 = [r.summary["p99_ms"] for r in res]
    assert all(a <= b for a, b in zip(p99, p99[1:])), p99


def test_drain_and_utilization_invariants(fleet):
    res = simulate(fleet, CFG, 120.0)
    s = res.summary
    assert s["completed"] == s["arrived"] > 0
    assert 0.0 < s["util"] <= 1.0
    for reg in fleet.regions.values():  # fully drained after the run
        assert reg.engine.utilization() == 0.0
        assert not reg.engine.queue
    for r in res.requests:
        assert r.t_done >= r.t_submit >= r.t_gen
        assert r.e2e_ms >= 2 * r.net_ms  # both WAN legs are paid
        assert r.staleness_ms >= fleet.staleness_lag_ms


def test_request_spans_export_to_perfetto(fleet, tmp_path):
    from repro.obs import TraceRecorder, write_trace
    rec = TraceRecorder()
    simulate(fleet, CFG, 60.0, recorder=rec)
    assert rec.serve_events
    obj = write_trace(str(tmp_path / "serve.json"), rec)
    spans = [e for e in obj["traceEvents"]
             if e.get("cat") == "serve" and e["ph"] == "X"]
    assert len(spans) == len(rec.serve_events)
    assert {e["args"]["region"] for e in spans} <= set(fleet.regions)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_end_to_end(tmp_path):
    bench = tmp_path / "BENCH_serving.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.serving", "--silos", "4",
         "--rounds", "2", "--t", "2", "--loads", "30,90",
         "--duration-ms", "300", "--ckpt-dir", str(tmp_path / "ck"),
         "--bench", str(bench)],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert len(out["serve"]) == 2
    assert all(s["completed"] == s["arrived"] for s in out["serve"])
    rows = json.loads(bench.read_text())
    from repro.obs.__main__ import validate_bench_rows
    assert validate_bench_rows(rows) == []
    assert sum("serving/load_" in row["name"] for row in rows) == 2
