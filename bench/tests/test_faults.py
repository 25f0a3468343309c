"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (`harness.run`: warm-up, measured `run_fl`, reference, judgement)
on the CPU at a size a test run holds: batch 4, 16 samples a silo, and
the shortest eval period that holds three dispatches (45 rounds on the
15-round gaia cycle, 3 on the ring, 18 on the iNat gaia cycle of 6,
36 on the FEMNIST geant cycle of 12 that the mesh test runs). The cell's own limits
judge. A sound run passes them; each fault a training cell can have
fails them:

    unchanged      a dispatch returns the state it was given
    half_batch     the cycle sees the first half of every batch
    no_exchange    the mesh's halo exchange sends nothing (4 CPU devices)
    answer         the eval's accuracy is altered where it is produced
    weak_link      the plan leaves one link weak after the cycle's
                   first round (the reference would follow it)
    weights        the plan mixes by another row-stochastic rule
    control        the reference in bfloat16 in the program's place
"""

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.core import cell as cellmod
from bench.core import check, harness, reference
from bench.core import plan as plancheck

SEED = 2 ** 31 + 11


EVAL_EVERY = {"femnist_cnn.gaia.multigraph": 45, "femnist_cnn.gaia.ring": 3,
              "inat_resnet.gaia.multigraph": 18}


def _cell(workload):
    c = cellmod.load(workload)
    assert c.limits, f"{workload} has no limits"
    c.traffic.update(batch_size=4, samples_per_silo=16,
                     eval_every=EVAL_EVERY[workload])
    return c


def _run(c, wrap=None):
    return harness.run(c, SEED, 0.5, False, time.perf_counter(),
                       jax.devices()[:c.chips], wrap=wrap)[0]


def _unchanged(fn, rt):
    return lambda state, *a: (state, fn(state, *a)[1])


def _half_batch(fn, rt):
    def cycle(state, batches, *plan):
        half = batches["x"].shape[3] // 2
        return fn(state, {k: v[:, :, :, :half] for k, v in batches.items()},
                  *plan)
    return cycle


CELLS = ["femnist_cnn.gaia.multigraph", "femnist_cnn.gaia.ring"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = _run(_cell(workload))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["unchanged", "half_batch"])
def test_fault_is_not_correct(workload, fault):
    out = _run(_cell(workload), wrap=fault)
    assert not out["correct"], out["checks"]


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.models import small
    real = small.SmallModelSpec.accuracy
    monkeypatch.setattr(small.SmallModelSpec, "accuracy",
                        lambda self, p, b: real(self, p, b) + 0.25)
    out = _run(_cell("femnist_cnn.gaia.ring"))
    assert not out["correct"], out["checks"]
    assert out["checks"]["eval_answers"]["value"] >= 128


def test_mesh_sound_and_without_exchange(monkeypatch):
    """The silo-mesh path (`bench/traffic/geant.multigraph.mesh4.json`:
    geant on four chips, the halo exchange) at a size a CPU holds: the
    FEMNIST CNN, whose geant cycle is 12 rounds, judged by the FEMNIST
    multigraph cell's limits."""
    c = _cell("femnist_cnn.gaia.multigraph")
    c.chips = 4
    c.traffic = json.loads(
        (cellmod.BENCH / "traffic" / "geant.multigraph.mesh4.json").read_text())
    c.traffic.update(batch_size=4, samples_per_silo=16, eval_every=36)
    c.plan = {"rounds_per_dispatch": 12, "strong_edge_rounds": 654}
    out = _run(c)
    assert out["correct"], out["checks"]

    from repro.fl import gossip

    def no_halo(w, send_idx, perms, gather_idx, axis):
        parts = [w] + [jnp.zeros_like(w[i]) for i in send_idx]
        return jnp.concatenate(parts, axis=0)[gather_idx]

    monkeypatch.setattr(gossip, "csr_gather_halo", no_halo)
    out = _run(c)
    assert not out["correct"], out["checks"]


def _weak_link(plan):
    strong = plan.strong.copy()
    strong[1:, :2] = False
    return dataclasses.replace(plan, strong=strong)


def _weights(plan):
    return dataclasses.replace(plan, coeffs=np.full_like(plan.coeffs, 0.25),
                               diag=np.full_like(plan.diag, 0.5))


@pytest.mark.parametrize("fault,number", [(_weak_link, "plan_strong"),
                                          (_weights, "plan_weights")],
                         ids=["weak_link", "weights"])
def test_wrong_plan_is_not_correct(monkeypatch, fault, number):
    """A plan the program gets wrong upstream of the cycle, which the
    reference follows, is caught by the plan's own check."""
    from repro.fl import dpasgd
    real = dpasgd.make_round_schedule

    def altered(*a, **kw):
        plan, tplan = real(*a, **kw)
        return fault(plan), tplan

    monkeypatch.setattr(dpasgd, "make_round_schedule", altered)
    out = _run(_cell("femnist_cnn.gaia.multigraph"))
    assert not out["correct"], out["checks"]
    assert out["checks"][number]["value"] >= 1


@pytest.mark.parametrize("network,stated", [
    ("gaia", {"rounds_per_dispatch": 15, "strong_edge_rounds": 146}),
    ("geant", {"rounds_per_dispatch": 12, "strong_edge_rounds": 654})])
def test_program_plan_reads_no_breaks(network, stated):
    """The program's FEMNIST multigraph plans hold every invariant, and
    each planted break counts."""
    from repro.fl import dpasgd
    from repro.fl.trainer import WORKLOADS, _DATASET_WL
    from repro.networks.registry import get_network
    net = get_network(network)
    plan, _ = dpasgd.make_round_schedule(
        "multigraph", net, WORKLOADS[_DATASET_WL["femnist"]], t=5)
    n, d = net.num_silos, [(plan.strong, plan.coeffs, plan.diag)]
    zero = {"plan_overlay": 0, "plan_weights": 0, "plan_strong": 0}
    assert plancheck.numbers(n, plan.src, plan.dst, d, 5, stated) == zero
    chord = (np.append(plan.src, [0, 2]), np.append(plan.dst, [2, 0]))
    assert plancheck.numbers(n, *chord, [], 5, stated)["plan_overlay"] > 0
    other = dict(stated, strong_edge_rounds=stated["strong_edge_rounds"] - 1)
    assert plancheck.numbers(n, plan.src, plan.dst, d, 5,
                             other)["plan_strong"] == 1
    one_way = plan.strong.copy()
    one_way[:, 0] = False
    assert plancheck.numbers(n, plan.src, plan.dst,
                             [(one_way, plan.coeffs, plan.diag)], 5,
                             stated)["plan_strong"] > 0


@pytest.mark.parametrize("workload", CELLS + ["inat_resnet.gaia.multigraph"])
def test_control_is_not_correct(workload):
    """The reference computed in bfloat16, put in the program's place
    and judged by the cell's limits, fails them."""
    c = _cell(workload)
    t = c.traffic
    n, r = 11, 1
    data = reference.make_data(c.config["synthetic_data"], n,
                               t["samples_per_silo"], t["alpha"], SEED)
    x, y = reference.make_feed(data, 3 * r, t["batch_size"], 1, SEED)
    w0, layout = reference.initial_row(c.model, c.config, SEED, n)
    src = np.array([i for i in range(n)] + [(i + 1) % n for i in range(n)])
    dst = np.array([(i + 1) % n for i in range(n)] + [i for i in range(n)])
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    rng = np.random.default_rng(0)
    strong = rng.random((3 * r, 2 * n)) < 0.6
    plan = reference.Plan(src, dst, strong, np.full((3 * r, 2 * n), 0.25,
                                                    np.float32),
                          np.full((3 * r, n), 0.5, np.float32))
    args = (c.model, c.config, np.asarray(w0), layout, x, y, plan)
    kw = dict(lr=t["lr"], silo_block=1, snapshot_at=(r, 3 * r))
    ref = reference.run_first_steps(*args, **kw)
    ctl = reference.run_first_steps(*args, arith=reference.BF16, **kw)
    loss1 = reference.first_loss(c.model, c.config, np.asarray(w0), layout,
                                 x, y, arith=reference.CONFIGURED)
    values = check.step_numbers(ctl.losses, ctl.snapshots, ref,
                                np.asarray(w0), layout, r, loss1)
    limits = {k: v for k, v in c.limits.items() if k in values}
    ok, compared = check.judge(values, limits)
    assert not ok, compared
