"""Observability layer (obs/, DESIGN.md §17).

Five contracts:

* **inertness** — `metrics=None` compiles the EXACT pre-obs program:
  final state (w, opt state, edge buffers) bit-identical to metrics-on
  on both the flat and mesh runtimes, and each cycle fn traces once.
  (Loss SCALARS may drift ~1 ulp with metrics on: the silo_loss column
  adds a second consumer of the per-round losses, which changes XLA's
  reduce-to-scalar emitter — same caveat as the mesh runtime's in
  DESIGN.md §16, hence rtol=5e-7 on losses, exact on state.)
* **reconciliation** — simulated spans sum exactly to the TimingPlan's
  `cycle_times` per round (and to a FaultedSegment's realized taus).
* **schema** — exported trace JSON passes `validate_trace` (the
  Perfetto trace_event subset), and the BENCH row validator accepts
  the repo's BENCH_*.json files.
* **zero-recompile** — a traced controller run across live schedule
  swaps still compiles its cycle exactly once.
* **layer boundaries** — `run_fl`'s host spans nest as documented
  (sample and copy before each dispatch, launch and sync inside it),
  each is also a profiler annotation, and the compiled flat and mesh
  cycles carry the `fl.local_sgd` / `fl.refresh` / `fl.aggregate`
  scopes in their HLO `op_name` metadata.

Like test_fl_mesh.py this file runs on however many devices the host
exposes (1 in tier-1; the CI obs/fl-mesh jobs re-run with 8 forced
host devices).
"""

import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import timing
from repro.core.delay import FEMNIST, WORKLOADS
from repro.core.topology import ring_topology
from repro.fl import dpasgd
from repro.fl import mesh as flmesh
from repro.fl import runtime as rtmod
from repro.networks.zoo import get_network
from repro.obs import (MetricsSpec, TraceRecorder, metric_columns,
                       to_trace_json, validate_trace, write_run_record,
                       load_run_record, write_trace)
from repro.optim import flat_sgd

D_MODEL = 8


def _toy_init(key):
    return {"w": jax.random.normal(key, (D_MODEL,)), "b": jnp.zeros((3,))}


def _toy_loss(p, batch):
    return jnp.sum((p["w"] - batch["t"]) ** 2) + jnp.sum(p["b"] ** 2)


@pytest.fixture(scope="module")
def gaia_setup():
    net = get_network("gaia")
    tplan = timing.multigraph_timing_plan(net, FEMNIST, t=5)
    plan, _, _ = dpasgd.multigraph_plan(net, FEMNIST, t=5, tplan=tplan)
    n = int(plan.diag.shape[1])
    r = plan.num_rounds_cycle
    rng = np.random.default_rng(0)
    batches = np.asarray(rng.normal(size=(r, 1, n, 1, D_MODEL)), np.float32)
    return net, tplan, plan, n, batches


def _cycle_args(rt, batches):
    r = batches.shape[0]
    return ({"t": jnp.asarray(batches)}, jnp.asarray(rt.strong[:r]),
            jnp.asarray(rt.coeffs[:r]), jnp.asarray(rt.diag[:r]))


# ---------------------------------------------------------------------------
# inertness: metrics=None is the seed program, bit for bit
# ---------------------------------------------------------------------------


def test_flat_metrics_off_bit_exact(gaia_setup):
    _, _, plan, n, batches = gaia_setup
    key = jax.random.PRNGKey(3)
    opt = flat_sgd(0.05, momentum=0.9)
    rt = rtmod.make_flat_runtime(plan, jax.eval_shape(_toy_init, key), n)
    args = _cycle_args(rt, batches)

    c_off = rtmod.make_cycle_fn(rt, loss_fn=_toy_loss, opt=opt)
    s_off, l_off = c_off(rtmod.init_flat_state(_toy_init, opt, rt, key),
                         *args)
    c_on = rtmod.make_cycle_fn(rt, loss_fn=_toy_loss, opt=opt,
                               metrics=MetricsSpec())
    s_on, l_on, mets = c_on(rtmod.init_flat_state(_toy_init, opt, rt, key),
                            *args)

    np.testing.assert_array_equal(np.asarray(s_off.w), np.asarray(s_on.w))
    np.testing.assert_array_equal(np.asarray(s_off.buffers),
                                  np.asarray(s_on.buffers))
    for a, b in zip(jax.tree.leaves(s_off.opt_state),
                    jax.tree.leaves(s_on.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(l_off), np.asarray(l_on),
                               rtol=5e-7, atol=0)
    assert c_off.trace_count["count"] == 1
    assert c_on.trace_count["count"] == 1

    cols = c_on.metric_columns
    assert cols == metric_columns(MetricsSpec(), n)
    mets = np.asarray(mets)
    assert mets.shape == (batches.shape[0], len(cols))
    assert np.isfinite(mets).all()
    # semantic traffic column: strong-edge count x flat row bytes
    gb = mets[:, cols.index("gossip_bytes")]
    exp = rt.strong[:batches.shape[0]].sum(1) * rt.spec.size * 4
    np.testing.assert_allclose(gb, exp.astype(np.float64), rtol=1e-6)


def test_mesh_metrics_off_bit_exact(gaia_setup):
    _, _, plan, n, batches = gaia_setup
    key = jax.random.PRNGKey(3)
    opt = flat_sgd(0.05, momentum=0.9)
    rt = rtmod.make_flat_runtime(plan, jax.eval_shape(_toy_init, key), n)
    mrt = flmesh.make_mesh_runtime(rt)  # every device the host exposes
    args = _cycle_args(rt, batches)

    m_off = rtmod.make_cycle_fn(mrt, loss_fn=_toy_loss, opt=opt)
    s_off, l_off = m_off(flmesh.init_mesh_state(_toy_init, opt, mrt, key),
                         *args)
    m_on = rtmod.make_cycle_fn(mrt, loss_fn=_toy_loss, opt=opt,
                               metrics=MetricsSpec())
    s_on, l_on, mets = m_on(flmesh.init_mesh_state(_toy_init, opt, mrt, key),
                            *args)

    np.testing.assert_array_equal(np.asarray(s_off.w), np.asarray(s_on.w))
    np.testing.assert_array_equal(np.asarray(s_off.buffers),
                                  np.asarray(s_on.buffers))
    np.testing.assert_allclose(np.asarray(l_off), np.asarray(l_on),
                               rtol=5e-7, atol=0)
    assert m_on.trace_count["count"] == 1
    assert m_on.metric_columns == metric_columns(MetricsSpec(), n, mesh=True)
    assert m_on.metric_columns[-1] == "fabric_bytes"
    assert np.isfinite(np.asarray(mets)).all()


def test_flat_and_mesh_metric_values_agree(gaia_setup):
    """Same reductions either side of the shard boundary — values agree
    to fp-association tolerance (never bitwise; DESIGN.md §16)."""
    _, _, plan, n, batches = gaia_setup
    key = jax.random.PRNGKey(3)
    opt = flat_sgd(0.05, momentum=0.9)
    rt = rtmod.make_flat_runtime(plan, jax.eval_shape(_toy_init, key), n)
    args = _cycle_args(rt, batches)
    _, _, mets_f = rtmod.make_cycle_fn(
        rt, loss_fn=_toy_loss, opt=opt, metrics=MetricsSpec())(
        rtmod.init_flat_state(_toy_init, opt, rt, key), *args)
    mrt = flmesh.make_mesh_runtime(rt)
    _, _, mets_m = rtmod.make_cycle_fn(
        mrt, loss_fn=_toy_loss, opt=opt, metrics=MetricsSpec())(
        flmesh.init_mesh_state(_toy_init, opt, mrt, key), *args)
    mets_f = np.asarray(mets_f)
    np.testing.assert_allclose(mets_f,
                               np.asarray(mets_m)[:, :mets_f.shape[1]],
                               rtol=1e-5, atol=1e-6)


def test_metrics_spec_all_off_rejected():
    with pytest.raises(ValueError, match="nothing"):
        MetricsSpec(grad_norm=False, param_norm=False, update_norm=False,
                    silo_loss=False, staleness=False, traffic=False)


# ---------------------------------------------------------------------------
# reconciliation: spans sum exactly to the timing engine's cycle times
# ---------------------------------------------------------------------------


def test_delay_history_matches_cycle_times(gaia_setup):
    net, tplan, *_ = gaia_setup
    taus, d, strong = tplan.delay_history(37)
    np.testing.assert_array_equal(
        taus, np.asarray(tplan.cycle_times(37), np.float64))
    assert d.shape == (37, tplan.pair_i.shape[0]) == strong.shape


def test_sim_spans_reconcile_exactly(gaia_setup):
    net, tplan, *_ = gaia_setup
    rounds = 29
    rec = TraceRecorder()
    end = rec.add_sim_spans(tplan, rounds)
    taus = np.asarray(tplan.cycle_times(rounds), np.float64)
    t = 0.0
    for k in range(rounds):
        t += float(taus[k])
        assert rec.round_end_ms(k) == t  # EXACT, not allclose
    assert end == t
    # every silo contributes spans every round
    per_round = {}
    for e in rec.sim_events:
        per_round.setdefault(e["round"], set()).add(e["silo"])
    assert all(len(v) == net.num_silos for v in per_round.values())


def test_faulted_spans_reconcile_and_mark_crashes(gaia_setup):
    from repro.faults import FaultedSession, get_scenario
    net, tplan, *_ = gaia_setup
    sess = FaultedSession(tplan, get_scenario("outage").schedule,
                          record_obs=True)
    seg = sess.advance(32)
    rec = TraceRecorder()
    end = rec.add_faulted_spans(tplan.pair_i, tplan.pair_j, seg)
    t = 0.0
    for k in range(32):
        t += float(seg.taus[k])
        assert rec.round_end_ms(k) == t
    assert end == t
    downs = [e for e in rec.sim_events if e["name"] == "down"]
    assert len(downs) == int(np.asarray(seg.crashed).sum())
    assert not validate_trace(to_trace_json(rec))


def test_faulted_spans_require_record_obs(gaia_setup):
    from repro.faults import FaultedSession, get_scenario
    _, tplan, *_ = gaia_setup
    seg = FaultedSession(tplan, get_scenario("drift").schedule).advance(4)
    with pytest.raises(ValueError, match="record_obs"):
        TraceRecorder().add_faulted_spans(tplan.pair_i, tplan.pair_j, seg)


# ---------------------------------------------------------------------------
# schema: Perfetto trace_event subset + BENCH row tables
# ---------------------------------------------------------------------------


def test_trace_json_schema_valid(gaia_setup, tmp_path):
    _, tplan, *_ = gaia_setup
    rec = TraceRecorder()
    rec.meta.update(network="gaia")
    rec.add_sim_spans(tplan, 6)
    with rec.host_span("compile+dispatch", rounds=6):
        pass
    rec.instant("swap", t_ms=1.0, round=2, vector=[1, 2])
    taus = np.asarray(tplan.cycle_times(6), np.float64)
    starts = np.concatenate([[0.0], np.cumsum(taus)[:-1]])
    rec.add_metrics(np.ones((6, 2)), ("a", "b"), starts)

    obj = to_trace_json(rec)
    assert validate_trace(obj) == []
    json.dumps(obj)  # serializable
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert phases == {"M", "X", "C", "i"}

    out = tmp_path / "t.json"
    write_trace(out, rec)
    assert validate_trace(json.loads(out.read_text())) == []

    # JSONL run-record round-trips into an equivalent recorder
    rr = tmp_path / "t.jsonl"
    write_run_record(rr, rec)
    rec2 = load_run_record(rr)
    assert len(rec2.sim_events) == len(rec.sim_events)
    assert len(rec2.counter_events) == len(rec.counter_events)
    assert validate_trace(to_trace_json(rec2)) == []


def test_validate_trace_catches_malformed():
    bad = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 1},                      # phase
        {"ph": "X", "pid": 1, "ts": 0, "dur": 1},                # no name
        {"ph": "X", "name": "x", "pid": 1, "ts": -5, "dur": 1},  # neg ts
        {"ph": "X", "name": "x", "pid": 1, "ts": 0, "dur": -1},  # neg dur
        {"ph": "C", "name": "c", "pid": 1, "ts": 0,
         "args": {"v": "high"}},                                 # non-num
        {"ph": "X", "name": "x", "pid": 1, "tid": 7, "ts": 9, "dur": 0},
        {"ph": "X", "name": "x", "pid": 1, "tid": 7, "ts": 3, "dur": 0},
    ]}
    errs = validate_trace(bad)
    assert len(errs) == 6  # one per defect incl. non-monotone track
    assert validate_trace([]) and validate_trace({"x": 1})


def test_bench_schema_validator(tmp_path):
    from repro.obs.__main__ import validate_bench_rows
    ok = [{"name": "a/b", "us_per_call": 1.5, "derived": "x"},
          {"name": "c", "us_per_call": 2, "ts": 10.0},
          {"name": "d", "us_per_call": 0, "ts": 11.0}]
    assert validate_bench_rows(ok) == []
    assert validate_bench_rows({"name": "a"})  # not a list
    assert validate_bench_rows([{"us_per_call": 1}])  # no name
    assert validate_bench_rows([{"name": "a", "us_per_call": "fast"}])
    bad_ts = [{"name": "a", "us_per_call": 1, "ts": 5.0},
              {"name": "b", "us_per_call": 1, "ts": 4.0}]
    assert any("decreases" in e for e in validate_bench_rows(bad_ts))
    # unstamped legacy rows interleave freely
    mixed = [{"name": "a", "us_per_call": 1},
             {"name": "b", "us_per_call": 1, "ts": 3.0},
             {"name": "c", "us_per_call": 1},
             {"name": "d", "us_per_call": 1, "ts": 7.0}]
    assert validate_bench_rows(mixed) == []


def test_repo_bench_files_pass_schema():
    import pathlib
    for p in sorted(pathlib.Path(".").glob("BENCH_*.json")):
        rows = json.loads(p.read_text())
        assert validate_bench_rows_errs(p, rows) == []


def validate_bench_rows_errs(path, rows):
    from repro.obs.__main__ import validate_bench_rows
    return [f"{path}: {e}" for e in validate_bench_rows(rows)]


# ---------------------------------------------------------------------------
# controller: tracing on, live swaps, still exactly one compile
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_controller():
    from repro.design.controller import ControllerConfig, ControllerHarness
    return ControllerHarness(ControllerConfig(
        rounds=24, replan_every=12, samples_per_silo=16, batch_size=4))


@pytest.mark.slow
def test_controller_traced_single_compile(traced_controller):
    h = traced_controller
    rec = TraceRecorder()
    run = h.run("churn", adaptive=True, recorder=rec)
    h.assert_single_trace()

    # simulated spans reconcile with the REALIZED (faulted) cycle times
    t = 0.0
    for k in range(24):
        t += float(run.cycle_times_ms[k])
        assert rec.round_end_ms(k) == t
    # controller instants recorded at segment boundaries; any swap the
    # run reports appears as a swap instant (and vice versa)
    names = [e["name"] for e in rec.ctrl_events]
    assert names.count("observe") == 24 // 12 - 1
    swap_rounds = tuple(e["round"] for e in rec.ctrl_events
                        if e["name"] == "swap")
    assert swap_rounds == run.swap_rounds
    # host spans cover every segment dispatch
    assert len([e for e in rec.host_events
                if e["name"] == "dispatch"]) == 24 // 12
    assert validate_trace(to_trace_json(rec)) == []


@pytest.mark.slow
def test_run_fl_metrics_and_trace(tmp_path):
    from repro.fl.trainer import FLConfig, run_fl
    out = tmp_path / "fl_trace.json"
    kw = dict(dataset="femnist", network="gaia", rounds=8, eval_every=8,
              samples_per_silo=16, batch_size=4, seed=1)
    base = run_fl(FLConfig(**kw))
    res = run_fl(FLConfig(**kw, metrics=MetricsSpec(), trace=str(out)))
    # inertness at the trainer level: identical training trajectory
    np.testing.assert_allclose(res.round_losses, base.round_losses,
                               rtol=5e-7, atol=0)
    assert res.metrics is not None and res.metrics.shape[0] == 8
    assert len(res.metric_columns) == res.metrics.shape[1]
    obj = json.loads(out.read_text())
    assert validate_trace(obj) == []
    host = [e for e in obj["traceEvents"] if e.get("cat") == "host"]
    assert any(e["name"] == "compile+dispatch" for e in host)
    counters = [e for e in obj["traceEvents"] if e["ph"] == "C"]
    assert {e["name"] for e in counters} >= {"grad_norm", "param_norm"}


# ---------------------------------------------------------------------------
# layer boundaries: host spans in run_fl, scopes in the compiled cycle
# ---------------------------------------------------------------------------


def test_run_fl_host_spans_per_dispatch(tmp_path):
    from repro.fl.trainer import FLConfig, run_fl
    import repro.obs as obs
    kept = []

    class Kept(obs.TraceRecorder):
        def __post_init__(self):
            super().__post_init__()
            kept.append(self)

    real, obs.TraceRecorder = obs.TraceRecorder, Kept
    try:
        run_fl(FLConfig(dataset="femnist", network="gaia", topology="ring",
                        rounds=4, eval_every=2, samples_per_silo=8,
                        batch_size=2, seed=2, trace=str(tmp_path / "t.json")))
    finally:
        obs.TraceRecorder = real
    (rec,) = kept
    ev = rec.host_events
    end = lambda e: e["t0_ms"] + e["dur_ms"]
    disp = [e for e in ev if e["name"] in ("compile+dispatch", "dispatch")]
    assert [e["name"] for e in disp] == ["compile+dispatch"] + ["dispatch"] * 3
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
        # one round of 11 silos x 2 images (28x28 f32) and int32 labels
        assert c["args"] == {"bytes": 11 * 2 * (28 * 28 * 4 + 4)}
    assert all(e["parent"] is None for e in ev if e["name"] == "eval")


def test_host_span_is_a_profiler_annotation(tmp_path):
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    rec = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    with rec.host_span("dispatch", rounds=1):
        with rec.host_span("launch"):
            y = f(x)
        with rec.host_span("sync"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(e)
    for name in ("dispatch", "launch", "sync"):
        assert len(host.get(name, [])) == 1, name
    (d,), (ln,), (sy,) = host["dispatch"], host["launch"], host["sync"]
    assert d.start_ns <= ln.start_ns and sy.end_ns <= d.end_ns
    assert [(e["name"], e["parent"]) for e in rec.host_events] == [
        ("launch", "dispatch"), ("sync", "dispatch"), ("dispatch", None)]


SCOPES = (rtmod.SCOPE_LOCAL_SGD, rtmod.SCOPE_REFRESH, rtmod.SCOPE_AGGREGATE)


def _scope_ops(hlo_text: str) -> dict:
    """Instructions of the compiled cycle per `fl.*` scope of their
    `op_name` (a scope nested in another counts once, innermost)."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m:
            found = re.findall(r"fl\.(?:local_sgd|refresh|aggregate)",
                               m.group(1))
            key = found[-1] if found else None
            out[key] = out.get(key, 0) + 1
    return out


@pytest.mark.parametrize("aggregator", ["reference", "dense"])
def test_flat_cycle_carries_scopes(gaia_setup, aggregator):
    _, _, _, n, batches = gaia_setup
    # the dense twin needs a uniform in-degree: the ring's
    plan = (dpasgd.make_round_schedule("ring", get_network("gaia"),
                                       FEMNIST)[0]
            if aggregator == "dense" else gaia_setup[2])
    key = jax.random.PRNGKey(0)
    opt = flat_sgd(0.05)
    rt = rtmod.make_flat_runtime(plan, jax.eval_shape(_toy_init, key), n)
    st = rtmod.init_flat_state(_toy_init, opt, rt, key)
    cyc = rtmod.make_cycle_fn(rt, loss_fn=_toy_loss, opt=opt,
                              aggregator=aggregator)
    r = min(batches.shape[0], rt.num_rounds_cycle)
    ops = _scope_ops(cyc.lower(st, *_cycle_args(rt, batches[:r]))
                     .compile().as_text())
    for scope in SCOPES:
        assert ops.get(scope, 0) > 0, (scope, ops)


def test_mesh_cycle_carries_scopes():
    """The sharded cycle, compiled over four forced CPU devices (a
    subprocess: this process keeps the devices it started with)."""
    code = f"""
import jax, jax.numpy as jnp, re
from repro.core.delay import FEMNIST
from repro.core import timing
from repro.fl import dpasgd, mesh as flmesh, runtime as rtmod
from repro.networks.zoo import get_network
from repro.optim import flat_sgd
assert jax.device_count() == 4
net = get_network("gaia")
tp = timing.multigraph_timing_plan(net, FEMNIST, t=5)
plan, _, _ = dpasgd.multigraph_plan(net, FEMNIST, t=5, tplan=tp)
init = lambda k: {{"w": jax.random.normal(k, (8,))}}
loss = lambda p, b: jnp.sum((p["w"] - b["t"]) ** 2)
key = jax.random.PRNGKey(0)
opt = flat_sgd(0.05)
n = int(plan.diag.shape[1])
rt = rtmod.make_flat_runtime(plan, jax.eval_shape(init, key), n)
for backend in ("halo", "all_gather"):
    mrt = flmesh.make_mesh_runtime(rt, 4)
    st = flmesh.init_mesh_state(init, opt, mrt, key)
    cyc = rtmod.make_cycle_fn(mrt, loss_fn=loss, opt=opt, gossip=backend)
    txt = cyc.lower(st, {{"t": jnp.zeros((2, 1, n, 1, 8))}},
                    jnp.asarray(rt.strong[:2]), jnp.asarray(rt.coeffs[:2]),
                    jnp.asarray(rt.diag[:2])).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', txt)
    for scope in {SCOPES!r}:
        assert any(scope in s for s in names), (backend, scope)
    # the cross-shard fetch of the source rows is part of the refresh
    comm = [l for l in txt.splitlines() if re.search(
        r"= [^ ]+ (collective-permute|all-gather)(-start)?\\(", l)]
    fetch = [l for l in comm if "fl.refresh" in l]
    assert fetch, (backend, comm[:4])
    print(backend + "-scopes-ok")
"""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "halo-scopes-ok" in r.stdout and "all_gather-scopes-ok" \
        in r.stdout, r.stdout


def test_trainer_rejects_obs_on_legacy_runtime():
    from repro.fl.trainer import FLConfig, run_fl
    with pytest.raises(ValueError, match="flat"):
        run_fl(FLConfig(runtime="legacy", metrics=MetricsSpec(), rounds=2))
