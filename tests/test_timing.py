"""Vectorized timing engine tests (core/timing.py, core/sweep.py):

  * array Eq. 4/5 == dict `MultigraphDelayTracker` oracle, bit-for-bit
    over >= 3 full state cycles on the paper's networks x workloads
    (exodus/ebone in the slow tier);
  * Algorithm 2 cap fix: multiplicities are capped BEFORE the LCM, so
    the materialized schedule stays exactly cyclic across the wrap
    (the old prefix-truncation desynchronized non-divisors);
  * one TimingPlan shared by trainer and simulator: `run_fl` totals ==
    `simulate("multigraph", ...)` for the same config;
  * ring tour: 2-silo networks work, non-Hamiltonian graphs raise
    instead of crashing with IndexError;
  * cyclic plans (static/star/ring/sampled) match the scalar
    `delay.py` implementations they vectorize;
  * batched TimingGrid == per-cell scalar/array paths bit-for-bit
    (paper cells + property-tested random cells), and the batched
    sweep == the per-cell oracle sweep;
  * full-horizon MATCHA: vectorized per-round times == the per-graph
    oracle, plans are counter-seeded (reproducible across processes
    and call orders), and for rounds > 512 the trainer's wall-clock
    total == the simulator's report total exactly (the old tiled
    512-round period made them diverge).
"""

import numpy as np
import pytest

from _hyp_compat import given, settings, st  # hypothesis or local fallback
from repro.core import parsing, timing
from repro.core.delay import (FEMNIST, WORKLOADS, MultigraphDelayTracker,
                              directed_delay_ms, graph_pair_delays,
                              pair_delay_ms, static_cycle_time_ms)
from repro.core.graph import STRONG, Multigraph, make_graph
from repro.core.multigraph import build_multigraph
from repro.core.simulator import simulate, simulate_multigraph, simulate_ring
from repro.core.topology import ring_topology
from repro.networks.zoo import NetworkSpec, Silo, get_network

GAIA = get_network("gaia")


def _tiny_net(n, latency=5.0, hetero=False):
    silos = tuple(
        Silo(name=f"s{i}", lat=float(i), lon=0.0,
             upload_gbps=10.0 * (1.0 + 0.1 * i if hetero else 1.0),
             download_gbps=10.0,
             compute_scale=1.0 + (0.05 * i if hetero else 0.0))
        for i in range(n))
    lat = np.full((n, n), latency)
    np.fill_diagonal(lat, 0.0)
    return NetworkSpec(name=f"tiny{n}", silos=silos, latency_ms=lat)


# ---------------------------------------------------------------------------
# array Eq. 3
# ---------------------------------------------------------------------------


def test_directed_delay_matrix_matches_scalar():
    n = GAIA.num_silos
    rng = np.random.default_rng(0)
    out_deg = rng.integers(1, 4, n)
    in_deg = rng.integers(1, 4, n)
    mat = timing.directed_delay_matrix(GAIA, FEMNIST, out_deg, in_deg)
    for i in range(n):
        for j in range(n):
            if i == j:
                continue
            assert mat[i, j] == directed_delay_ms(
                GAIA, FEMNIST, i, j, int(out_deg[i]), int(in_deg[j]))


def test_pair_delay_vector_matches_scalar():
    g = ring_topology(GAIA, FEMNIST).graph
    deg = g.degrees()
    pi = np.array([p[0] for p in g.pairs])
    pj = np.array([p[1] for p in g.pairs])
    vec = timing.pair_delay_vector(GAIA, FEMNIST, pi, pj, deg)
    ref = graph_pair_delays(GAIA, FEMNIST, g)
    for e, p in enumerate(g.pairs):
        assert vec[e] == ref[p]
    assert timing.static_cycle_time(GAIA, FEMNIST, g) == \
        static_cycle_time_ms(GAIA, FEMNIST, g)


# ---------------------------------------------------------------------------
# Eq. 4/5 recurrence vs the dict oracle
# ---------------------------------------------------------------------------


def _assert_matches_oracle(net, wl, t=5, min_rounds=100):
    plan = timing.multigraph_timing_plan(net, wl, t=t)
    rounds = max(3 * plan.num_states + 7, min_rounds)  # >= 3 full cycles
    taus = plan.cycle_times(rounds)
    tracker = MultigraphDelayTracker(net=net, wl=wl, overlay=plan.overlay)
    ref = np.array([tracker.round_cycle_time(s) for _, s in
                    parsing.state_schedule(list(plan.states), rounds)])
    # bit-for-bit (the acceptance bar is 1e-9 relative; we hold exact)
    np.testing.assert_array_equal(taus, ref)
    # isolated stats match the per-round dict scan
    iso = plan.isolated_per_round(rounds)
    ref_iso = np.array([len(s.isolated_nodes()) for _, s in
                        parsing.state_schedule(list(plan.states), rounds)])
    np.testing.assert_array_equal(iso, ref_iso)


@pytest.mark.parametrize("netname", ["gaia", "amazon", "geant"])
@pytest.mark.parametrize("wlname", sorted(WORKLOADS))
def test_recurrence_matches_oracle(netname, wlname):
    _assert_matches_oracle(get_network(netname), WORKLOADS[wlname])


@pytest.mark.slow
@pytest.mark.parametrize("netname", ["exodus", "ebone"])
@pytest.mark.parametrize("wlname", sorted(WORKLOADS))
def test_recurrence_matches_oracle_large(netname, wlname):
    _assert_matches_oracle(get_network(netname), WORKLOADS[wlname])


def test_recurrence_matches_oracle_past_periodic_shortcut():
    """The periodic-orbit extrapolation must agree with the oracle deep
    into the tiled region, not just over the live transient."""
    plan = timing.multigraph_timing_plan(GAIA, FEMNIST, t=5)
    rounds = 40 * plan.num_states
    taus = plan.cycle_times(rounds)
    tracker = MultigraphDelayTracker(net=GAIA, wl=FEMNIST,
                                     overlay=plan.overlay)
    ref = np.array([tracker.round_cycle_time(s) for _, s in
                    parsing.state_schedule(list(plan.states), rounds)])
    np.testing.assert_array_equal(taus, ref)


def test_recurrence_t_knob_and_report():
    rep = simulate_multigraph(GAIA, FEMNIST, t=5, num_rounds=300)
    assert rep.num_states > 1
    assert rep.states_with_isolated > 0
    assert rep.rounds_with_isolated > 0
    rep1 = simulate_multigraph(GAIA, FEMNIST, t=1, num_rounds=50)
    assert rep1.num_states == 1
    assert rep1.rounds_with_isolated == 0
    overlay_ct = static_cycle_time_ms(GAIA, FEMNIST,
                                      ring_topology(GAIA, FEMNIST).graph)
    assert rep1.mean_cycle_ms == pytest.approx(overlay_ct)


def test_lazy_states_match_strong_matrix():
    """`strong` is built in closed form (m % L[p] == 0) while `states`
    lazily materializes Algorithm 2's countdown — they must agree
    pair-for-pair, state-for-state."""
    plan = timing.multigraph_timing_plan(GAIA, FEMNIST, t=5)
    sts = plan.states
    assert len(sts) == plan.num_states
    for m, st in enumerate(sts):
        for e, p in enumerate(plan.overlay.pairs):
            assert (st.edge_type[p] == STRONG) == bool(plan.strong[m, e])


def test_transition_codes():
    plan = timing.multigraph_timing_plan(GAIA, FEMNIST, t=5)
    # state 0 is the all-strong overlay
    assert plan.strong[0].all()
    # codes consistent with (prev, cur) strong masks incl. the wrap
    for s in range(plan.num_states):
        prev = plan.strong[(s - 1) % plan.num_states]
        cur = plan.strong[s]
        np.testing.assert_array_equal(
            plan.trans[s], 2 * prev.astype(np.int8) + cur.astype(np.int8))


# ---------------------------------------------------------------------------
# Algorithm 2 cap fix: schedule stays cyclic across the wrap
# ---------------------------------------------------------------------------


def test_capped_multiplicities_divide_cap():
    mult = {(0, 1): 2, (1, 2): 7, (0, 2): 1}
    capped = parsing.capped_multiplicities(mult, cap_states=8)
    # m_max=6 -> lcm(2, 6, 1) = 6 <= 8
    assert capped == {(0, 1): 2, (1, 2): 6, (0, 2): 1}
    assert parsing.capped_multiplicities(mult, None) == mult
    with pytest.raises(ValueError):
        parsing.capped_multiplicities(mult, 0)


def test_parse_capped_schedule_is_cyclic_across_wrap():
    """Regression: a pair whose multiplicity does not divide the cap
    used to desynchronize at the wrap (strong at round cap, cap+7, ...
    instead of every 7th round). With multiplicity capping the pattern
    `strong iff k % m == 0` must hold for ALL rounds, including past
    the wrap, for the CAPPED multiplicities."""
    mg = Multigraph(num_nodes=3,
                    multiplicity={(0, 1): 2, (1, 2): 7, (0, 2): 1})
    cap = 8
    states = parsing.parse_multigraph(mg, cap_states=cap)
    capped = parsing.capped_multiplicities(mg.multiplicity, cap)
    s_max = len(states)
    assert s_max <= cap
    # cycle through >2 full periods: the wrap must be seamless
    for k, st in parsing.state_schedule(states, 3 * s_max + 1):
        for p, m in capped.items():
            want = STRONG if k % m == 0 else 1 - STRONG
            assert st.edge_type[p] == want, (k, p, m)
    # wrapped state 0 is the all-strong overlay (Algorithm 2 invariant)
    assert not states[0].weak_pairs()


def test_parse_uncapped_unchanged_for_paper_configs():
    """t<=5 gives LCM <= 60: the cap must not alter the paper configs."""
    overlay = ring_topology(GAIA, FEMNIST).graph
    mg = build_multigraph(GAIA, FEMNIST, overlay, t=5)
    free = parsing.parse_multigraph(mg, cap_states=None)
    capped = parsing.parse_multigraph(mg, cap_states=timing.CAP_STATES)
    assert len(free) == len(capped)
    for a, b in zip(free, capped):
        assert a.edge_type == b.edge_type


# ---------------------------------------------------------------------------
# unified cap: trainer and simulator share one TimingPlan
# ---------------------------------------------------------------------------


def test_run_fl_totals_match_simulate():
    """Regression for the 120-vs-360 cap split: training curves and
    timing reports for the same FLConfig come from the same schedule."""
    from repro.fl.trainer import FLConfig, run_fl

    rounds = 6
    res = run_fl(FLConfig(dataset="femnist", network="gaia",
                          topology="multigraph", rounds=rounds,
                          eval_every=6, samples_per_silo=8, batch_size=2,
                          seed=0))
    rep = simulate("multigraph", get_network("gaia"),
                   WORKLOADS["femnist"], num_rounds=rounds)
    assert res.total_time_s == pytest.approx(rep.total_time_s, rel=1e-12)
    assert res.mean_cycle_ms == pytest.approx(rep.mean_cycle_ms, rel=1e-12)
    np.testing.assert_array_equal(
        np.asarray(res.cycle_times_ms),
        timing.multigraph_timing_plan(
            get_network("gaia"), WORKLOADS["femnist"],
            t=5).cycle_times(rounds))


def test_round_plan_and_timing_plan_share_states():
    from repro.fl import dpasgd

    plan, tplan = dpasgd.make_round_schedule("multigraph", GAIA, FEMNIST,
                                             t=5)
    assert plan.num_rounds_cycle == tplan.num_states
    # the RoundPlan's strong mask per round == the TimingPlan's states
    for k, st in enumerate(tplan.states):
        for e in range(len(plan.src)):
            i, j = int(plan.src[e]), int(plan.dst[e])
            p = (i, j) if i < j else (j, i)
            assert bool(plan.strong[k, e]) == (st.edge_type[p] == STRONG)


# ---------------------------------------------------------------------------
# ring tour (2-silo + non-Hamiltonian regression)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [2, 3, 4, 5])
def test_simulate_ring_small_networks(n):
    rep = simulate_ring(_tiny_net(n, hetero=True), FEMNIST, num_rounds=10)
    assert np.isfinite(rep.mean_cycle_ms)
    assert rep.mean_cycle_ms > 0


def test_ring_tour_two_nodes():
    assert timing.ring_tour(make_graph(2, [(0, 1)])) == [0, 1, 0]


def test_ring_tour_rejects_non_hamiltonian():
    # two disjoint triangles: 2-regular but not a single cycle
    g = make_graph(6, [(0, 1), (1, 2), (0, 2), (3, 4), (4, 5), (3, 5)])
    with pytest.raises(ValueError, match="Hamiltonian|close"):
        timing.ring_tour(g)
    # a path: walk gets stuck at the endpoint
    g2 = make_graph(4, [(0, 1), (1, 2), (2, 3)])
    with pytest.raises(ValueError):
        timing.ring_tour(g2)


def test_ring_matches_legacy_semantics():
    """Vectorized ring plan == the scalar max-plus computation."""
    net = GAIA
    graph = ring_topology(net, FEMNIST).graph
    tour = timing.ring_tour(graph)
    total = sum(directed_delay_ms(net, FEMNIST, a, b, 1, 1)
                for a, b in zip(tour[:-1], tour[1:]))
    deg = graph.degrees()
    two_circuit = max(pair_delay_ms(net, FEMNIST, i, j, deg) / 2.0
                      for i, j in graph.pairs)
    comp = FEMNIST.compute_ms(net)
    lam = max(total / graph.num_nodes, two_circuit, float(np.max(comp)))
    rep = simulate_ring(net, FEMNIST, num_rounds=10)
    assert rep.mean_cycle_ms == pytest.approx(lam, rel=1e-12)


# ---------------------------------------------------------------------------
# cyclic plans and the sweep driver
# ---------------------------------------------------------------------------


def test_star_plan_matches_scalar():
    n = GAIA.num_silos
    best = np.inf
    for hub in range(n):
        up = max(directed_delay_ms(GAIA, FEMNIST, i, hub, 1, n - 1)
                 for i in range(n) if i != hub)
        down = max(directed_delay_ms(GAIA, FEMNIST, hub, i, n - 1, 1)
                   for i in range(n) if i != hub)
        best = min(best, up + down)
    rep = simulate("star", GAIA, FEMNIST, num_rounds=10)
    assert rep.mean_cycle_ms == pytest.approx(best, rel=1e-12)


def test_sampled_plan_tiles():
    plan = timing.make_timing_plan("matcha", GAIA, FEMNIST,
                                   sample_rounds=16)
    times = plan.cycle_times(40)
    assert times.shape == (40,)
    np.testing.assert_array_equal(times[:16], times[16:32])
    assert plan.isolated_per_round(40).sum() == 0


# ---------------------------------------------------------------------------
# full-horizon MATCHA: vectorized times, deterministic plans, and the
# trainer-total == report-total identity past the old 512-round period
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo", ["matcha", "matcha_plus"])
@pytest.mark.parametrize("netname", ["gaia", "geant"])
def test_sampled_cycle_times_match_per_graph_oracle(topo, netname):
    """`timing.sampled_cycle_times` (one array program over the whole
    horizon) == the scalar `static_cycle_time(round_graph(k))` oracle,
    bit-for-bit, on both the complete-graph and physical-graph bases."""
    from repro.core.topology import build_topology

    net = get_network(netname)
    design = build_topology(topo, net, FEMNIST, seed=0)
    rounds = 150
    vec = timing.sampled_cycle_times(design, net, FEMNIST, rounds)
    ref = np.array([timing.static_cycle_time(net, FEMNIST,
                                             design.round_graph(k))
                    for k in range(rounds)])
    np.testing.assert_array_equal(vec, ref)


def test_sampled_cycle_times_hetero_capacity_path():
    """Non-uniform link capacities take the general (two-direction)
    path; it must equal the oracle bit-for-bit too."""
    from repro.core.topology import matcha_topology

    net = _tiny_net(6, hetero=True)
    design = matcha_topology(net, FEMNIST, seed=3)
    rounds = 80
    vec = timing.sampled_cycle_times(design, net, FEMNIST, rounds)
    ref = np.array([timing.static_cycle_time(net, FEMNIST,
                                             design.round_graph(k))
                    for k in range(rounds)])
    np.testing.assert_array_equal(vec, ref)


def test_matcha_plan_deterministic_and_order_independent():
    """Counter-based activation: round_graph(k) is a pure function of
    (seed, k) — same bits across fresh designs and call orders."""
    from repro.core.topology import matcha_topology

    d1 = matcha_topology(GAIA, FEMNIST, seed=7)
    d2 = matcha_topology(GAIA, FEMNIST, seed=7)
    other = matcha_topology(GAIA, FEMNIST, seed=8)
    # reversed call order on d2 must not perturb anything
    assert d1.round_graph(3) == d2.round_graph(3)
    assert d2.round_graph(0) == d1.round_graph(0)
    assert d1.round_graph(3) == d2.round_graph(3)
    np.testing.assert_array_equal(d1.activation_matrix(50),
                                  d2.activation_matrix(50))
    assert (d1.activation_matrix(200) != other.activation_matrix(200)).any()
    # single-round draws agree with the batched matrix
    for k in (0, 1, 49):
        np.testing.assert_array_equal(d1.activation(k),
                                      d1.activation_matrix(50)[k])


def test_matcha_trainer_total_equals_report_total_past_512():
    """Regression for the tiled 512-round period: for rounds > 512 the
    trainer's wall-clock axis (the TimingPlan `make_round_schedule`
    returns, summed exactly as `run_fl` does) and the report that
    `simulate` emits for the same config are the SAME number — every
    round is sampled, nothing is tiled."""
    from repro.fl import dpasgd

    rounds = 520
    for topo in ("matcha", "matcha_plus"):
        plan, tplan = dpasgd.make_round_schedule(topo, GAIA, FEMNIST,
                                                 rounds=rounds, seed=0)
        cycle = tplan.cycle_times(rounds)
        trainer_total = float(np.sum(cycle)) / 1e3
        trainer_mean = float(np.mean(cycle))
        rep = simulate(topo, GAIA, FEMNIST, num_rounds=rounds, seed=0)
        assert trainer_total == rep.total_time_s
        assert trainer_mean == rep.mean_cycle_ms
        # and the report the trainer embeds is the same object's report
        own = tplan.report(rounds)
        assert own.total_time_s == rep.total_time_s
        # the RoundPlan trains on the same activation the plan timed
        assert plan.num_rounds_cycle == rounds


# ---------------------------------------------------------------------------
# batched timing grid == per-cell paths, bit-for-bit
# ---------------------------------------------------------------------------


def _grid_vs_cells(plans, rounds):
    grid = timing.build_timing_grid(plans)
    mat = grid.cycle_time_matrix(rounds)
    for c, plan in enumerate(plans):
        np.testing.assert_array_equal(mat[c], plan.cycle_times(rounds),
                                      err_msg=f"cell {c}: {plan.topology}/"
                                              f"{plan.network}/"
                                              f"{plan.workload}")
    for rep, plan in zip(grid.reports(rounds), plans):
        assert rep == plan.report(rounds)


def test_grid_matches_per_cell_paper_cells():
    """All fast-tier paper recurrence cells + a cyclic cell stacked in
    one grid == each cell's own scalar/array path, bit-for-bit (the
    per-cell paths are oracle-checked against the dict tracker in the
    tests above, so this chains to the dict oracle)."""
    plans = []
    for netname in ("gaia", "amazon", "geant"):
        net = get_network(netname)
        for wlname in sorted(WORKLOADS):
            plans.append(timing.multigraph_timing_plan(
                net, WORKLOADS[wlname], t=5))
    plans.append(timing.star_timing_plan(GAIA, FEMNIST))
    plans.append(timing.make_timing_plan("matcha", GAIA, FEMNIST,
                                         sample_rounds=600))
    _grid_vs_cells(plans, 600)


@pytest.mark.slow
def test_grid_matches_per_cell_paper_cells_large():
    """The full 15-cell paper grid (exodus/ebone included), 6,400
    rounds — the sweep's exact workload."""
    plans = [timing.multigraph_timing_plan(get_network(n), WORKLOADS[w],
                                           t=5)
             for n in ("gaia", "amazon", "geant", "exodus", "ebone")
             for w in sorted(WORKLOADS)]
    _grid_vs_cells(plans, 6400)


@given(seed=st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_grid_matches_per_cell_random_cells(seed):
    """Property: grids over random heterogeneous nets, random overlays
    and random t stay bit-identical to the per-cell paths (covers both
    the scalar SMALL_E twin and the array path, ragged S and E)."""
    rng = np.random.default_rng(seed)
    plans = []
    for _ in range(rng.integers(2, 5)):
        n = int(rng.integers(3, 9))
        net = _tiny_net(n, latency=float(rng.uniform(1.0, 30.0)),
                        hetero=bool(rng.integers(0, 2)))
        pairs = {(i, (i + 1) % n) if i < (i + 1) % n else ((i + 1) % n, i)
                 for i in range(n)}
        extra = [(i, j) for i in range(n) for j in range(i + 1, n)
                 if rng.random() < 0.3]
        overlay = make_graph(n, list(pairs) + extra)
        plans.append(timing.multigraph_timing_plan(
            net, FEMNIST, t=int(rng.integers(2, 7)), overlay=overlay))
    rounds = int(rng.integers(50, 400))
    _grid_vs_cells(plans, rounds)


def test_sweep_batched_equals_per_cell():
    """`run_sweep(batched=True)` (one TimingGrid) == the per-cell
    oracle sweep, report-for-report."""
    from repro.core import sweep

    cfg = sweep.SweepConfig(
        topologies=("star", "matcha", "ring", "multigraph"),
        networks=("gaia",), workloads=("femnist",),
        t_values=(3, 5), num_rounds=700)
    batched = sweep.run_sweep(cfg, batched=True)
    oracle = sweep.run_sweep(cfg, batched=False)
    assert len(batched) == len(oracle) == 5
    for b, o in zip(batched, oracle):
        assert b.report == o.report


def test_reports_agree_rtol():
    """Bitwise by default. Under the emulated-f64 bound, float fields may
    differ by rounding but not by a wrong branch, and integer
    statistics stay exact."""
    import dataclasses

    from repro.core import sweep

    rep = simulate("multigraph", GAIA, FEMNIST, num_rounds=400, t=5)
    rtol = sweep.EMULATED_F64_RTOL
    rounding = dataclasses.replace(
        rep, mean_cycle_ms=rep.mean_cycle_ms * (1 + 4e-16),
        total_time_s=float(np.nextafter(rep.total_time_s, np.inf)))
    wrong = dataclasses.replace(rep, mean_cycle_ms=rep.mean_cycle_ms * 1.01)
    count = dataclasses.replace(
        rep, states_with_isolated=rep.states_with_isolated + 1)
    assert sweep.reports_agree(rep, rep)
    assert not sweep.reports_agree(rounding, rep)
    assert sweep.reports_agree(rounding, rep, rtol)
    assert not sweep.reports_agree(wrong, rep, rtol)
    assert not sweep.reports_agree(count, rep, rtol)


def test_sweep_driver_quick_grid():
    from repro.core import sweep

    cfg = sweep.SweepConfig(topologies=("star", "ring", "multigraph"),
                            networks=("gaia",), workloads=("femnist",),
                            t_values=(3, 5), num_rounds=400)
    cells = sweep.run_sweep(cfg)
    # star, ring, and one multigraph cell per t
    assert len(cells) == 4
    by_topo = {(c.report.topology, c.t): c for c in cells}
    assert by_topo[("multigraph(t=5)", 5)].report.total_time_s < \
        by_topo[("ring", None)].report.total_time_s < \
        by_topo[("star", None)].report.total_time_s
    t1 = sweep.format_table1(cells)
    t3 = sweep.format_table3(cells)
    assert "gaia" in t1 and "multigraph" in t1
    assert "gaia" in t3 and "iso_rounds" in t3
    # sweep cells agree with the one-off simulator entry points
    rep = simulate("multigraph", GAIA, FEMNIST, num_rounds=400, t=3)
    assert by_topo[("multigraph(t=3)", 3)].report.mean_cycle_ms == \
        rep.mean_cycle_ms


def test_sweep_cli_smoke(capsys):
    from repro.core import sweep

    sweep.main(["--quick", "--rounds", "200", "--topologies",
                "star,ring,multigraph", "--networks", "gaia",
                "--workloads", "femnist"])
    out = capsys.readouterr().out
    assert "Table 1" in out and "Table 3" in out
