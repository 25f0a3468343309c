"""Flat-parameter FL runtime tests (repro/fl/flat.py, runtime.py, the

CSR edge-aggregation kernel, and the trainer's whole-cycle path):

  * flatten/unflatten round-trips, single and stacked;
  * CSR `edge_aggregate` == per-destination `segment_sum` oracle on
    random graphs with random degrees INCLUDING isolated destinations
    (zero incoming edges — the paper's isolated-node mechanism);
  * one flat-runtime cycle == R jitted legacy `fl_round_step` calls,
    bit-for-bit in fp32 INCLUDING momentum (the optimizers pin the FMA
    contraction of `momentum*mu + g` / `w - lr*mu` via fl/flat.py's
    `pin_f32`, so packed and per-leaf layouts compute identical bits);
  * a full multigraph cycle is ONE compiled dispatch: the cycle
    function traces exactly once across repeated cycles;
  * flat_sgd == vmapped per-silo sgd;
  * run_fl(runtime="flat") == run_fl(runtime="legacy") end-to-end.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hyp_compat import given, settings, st  # hypothesis or local fallback
from repro.core.delay import FEMNIST
from repro.fl import dpasgd, flat as flatmod, runtime as rtmod
from repro.kernels.gossip_combine.ops import csr_sort, edge_aggregate
from repro.kernels.gossip_combine.ref import edge_aggregate_ref
from repro.networks.zoo import get_network
from repro.optim import flat_sgd, sgd

KEY = jax.random.PRNGKey(0)
D = 8


def _toy_init(key):
    return {"w": jax.random.normal(key, (D,)), "b": jnp.zeros((3,))}


def _toy_loss(p, batch):
    return jnp.sum((p["w"] - batch["t"]) ** 2) + jnp.sum(p["b"] ** 2)


# ---------------------------------------------------------------------------
# flatten
# ---------------------------------------------------------------------------


def test_flat_round_trip():
    tree = {"a": jax.random.normal(KEY, (4, 5)),
            "b": {"c": jnp.arange(7, dtype=jnp.float32),
                  "d": jnp.ones((2, 3, 2), jnp.bfloat16)}}
    spec = flatmod.make_flat_spec(tree)
    assert spec.size == 4 * 5 + 7 + 12
    flat = flatmod.ravel(spec, tree)
    assert flat.shape == (spec.size,)
    back = flatmod.unravel(spec, flat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_flat_round_trip_stacked():
    n = 6
    tree = {"w": jax.random.normal(KEY, (n, 3, 4)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (n, 5))}
    spec = flatmod.make_flat_spec(
        jax.tree.map(lambda x: x[0], tree))
    mat = flatmod.ravel_stacked(spec, tree)
    assert mat.shape == (n, 17)
    back = flatmod.unravel_stacked(spec, mat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flat_grad_matches_leaf_grad():
    """AD through unravel: flat gradient == ravel of per-leaf grads."""
    p = _toy_init(KEY)
    spec = flatmod.make_flat_spec(p)
    batch = {"t": jax.random.normal(KEY, (1, D))}
    g_tree = jax.grad(_toy_loss)(p, batch)
    g_flat = jax.grad(
        lambda v: _toy_loss(flatmod.unravel(spec, v), batch))(
        flatmod.ravel(spec, p))
    np.testing.assert_array_equal(
        np.asarray(flatmod.ravel(spec, g_tree)), np.asarray(g_flat))


# ---------------------------------------------------------------------------
# CSR edge-aggregation kernel
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 999), n=st.integers(2, 12),
       e2=st.integers(0, 40), t=st.integers(1, 700))
@settings(max_examples=25, deadline=None)
def test_edge_aggregate_property(seed, n, e2, t):
    """Kernel == segment_sum oracle in fp32 on random multigraphs with
    random per-destination degrees; destination 0 is forced isolated
    (zero incoming edges) whenever n > 1 and e2 > 0."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(n, t)), jnp.float32)
    buf = jnp.asarray(rng.normal(size=(e2, t)), jnp.float32)
    lo = 1 if n > 1 else 0
    dst = rng.integers(lo, n, size=e2).astype(np.int32)
    coeffs = jnp.asarray(rng.random(e2), jnp.float32)
    diag = jnp.asarray(rng.random(n), jnp.float32)
    order, row_ptr = csr_sort(dst, n)
    out = edge_aggregate(w, buf[jnp.asarray(order)],
                         coeffs[np.asarray(order)],
                         jnp.asarray(row_ptr), diag,
                         block_t=256, interpret=True)
    ref = edge_aggregate_ref(w, buf, coeffs, jnp.asarray(dst), diag)
    # few-ulp tolerance: XLA fuses the kernel's mul+add into an FMA
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    if e2 and n > 1:  # isolated destination: diag-scaled own weights only
        np.testing.assert_allclose(
            np.asarray(out[0]), np.asarray(diag[0] * w[0]),
            rtol=1e-6, atol=1e-6)


def test_edge_aggregate_gaia_plan():
    """The actual gaia (N=11) multigraph plan, every state of the cycle."""
    net = get_network("gaia")
    plan, _, _ = dpasgd.multigraph_plan(net, FEMNIST, t=5)
    n, e2, t = net.num_silos, len(plan.src), 513  # non-divisible tile
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(n, t)), jnp.float32)
    buf = jnp.asarray(rng.normal(size=(e2, t)), jnp.float32)
    order, row_ptr = csr_sort(plan.dst, n)
    for k in (0, plan.num_rounds_cycle - 1):
        coeffs = jnp.asarray(plan.coeffs[k])
        diag = jnp.asarray(plan.diag[k])
        out = edge_aggregate(w, buf[jnp.asarray(order)],
                             coeffs[np.asarray(order)],
                             jnp.asarray(row_ptr), diag,
                             block_t=256, interpret=True)
        ref = edge_aggregate_ref(w, buf, coeffs, jnp.asarray(plan.dst), diag)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


def test_edge_aggregate_degenerate_shapes():
    w = jax.random.normal(KEY, (4, 16))
    diag = jnp.full((4,), 0.5)
    # no edges at all
    out = edge_aggregate(w, jnp.zeros((0, 16)), jnp.zeros((0,)),
                         jnp.zeros((5,), jnp.int32), diag, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 0.5 * np.asarray(w),
                               rtol=1e-7, atol=0)
    # zero-width model
    out = edge_aggregate(jnp.zeros((4, 0)), jnp.zeros((3, 0)),
                         jnp.ones((3,)), jnp.asarray([0, 1, 2, 3, 3],
                                                     jnp.int32),
                         diag, interpret=True)
    assert out.shape == (4, 0)


# ---------------------------------------------------------------------------
# whole-cycle equivalence vs legacy fl_round_step
# ---------------------------------------------------------------------------


def _run_legacy(plan, opt, key, batches_all, local_updates):
    n = int(plan.diag.shape[1])
    state = dpasgd.init_fl_state(_toy_init, opt, n, plan.src, key)
    step = jax.jit(lambda st, b, s, c, d: dpasgd.fl_round_step(
        st, b, plan.src, plan.dst, s, c, d, loss_fn=_toy_loss, opt=opt,
        local_updates=local_updates))
    losses = []
    for k in range(batches_all.shape[0]):
        state, loss = step(state, {"t": jnp.asarray(batches_all[k])},
                           jnp.asarray(plan.strong[k]),
                           jnp.asarray(plan.coeffs[k]),
                           jnp.asarray(plan.diag[k]))
        losses.append(float(loss))
    return state, losses


def _run_flat(plan, opt, key, batches_all, momentum):
    n = int(plan.diag.shape[1])
    rt = rtmod.make_flat_runtime(
        plan, jax.eval_shape(_toy_init, KEY), n)
    state = rtmod.init_flat_state(_toy_init, opt, rt, key)
    cycle = rtmod.make_cycle_fn(rt, loss_fn=_toy_loss, opt=opt)
    r = batches_all.shape[0]
    state, losses = cycle(state, {"t": jnp.asarray(batches_all)},
                          jnp.asarray(rt.strong[:r]),
                          jnp.asarray(rt.coeffs[:r]),
                          jnp.asarray(rt.diag[:r]))
    return rt, state, [float(x) for x in np.asarray(losses)]


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_flat_cycle_matches_legacy_rounds(momentum):
    net = get_network("gaia")
    plan, _, _ = dpasgd.multigraph_plan(net, FEMNIST, t=5)
    r = plan.num_rounds_cycle
    n = net.num_silos
    rng = np.random.default_rng(1)
    batches_all = np.asarray(rng.normal(size=(r, 2, n, 1, D)), np.float32)

    sl, losses_l = _run_legacy(plan, sgd(0.05, momentum=momentum), KEY,
                               batches_all, local_updates=2)
    rt, sf, losses_f = _run_flat(plan, flat_sgd(0.05, momentum=momentum),
                                 KEY, batches_all, momentum)

    wl = np.asarray(flatmod.ravel_stacked(rt.spec, sl.silo_params))
    bl = np.asarray(flatmod.ravel_stacked(rt.spec, sl.buffers))
    bf = np.asarray(sf.buffers)[np.argsort(rt.order)]
    # bit-for-bit in fp32 after a FULL multigraph cycle, momentum
    # included: `optim.sgd`/`flat_sgd` pin the FMA-contraction sites of
    # the momentum update (fl/flat.py `pin_f32`), so the packed and
    # per-leaf layouts compute identical bits.
    np.testing.assert_array_equal(wl, np.asarray(sf.w))
    np.testing.assert_array_equal(bl, bf)
    assert losses_l == losses_f


def test_flat_cycle_aggregators_agree():
    """aggregator='kernel' (interpret-mode Pallas) and 'dense' (uniform
    in-degree fast path) == 'reference'."""
    net = get_network("gaia")
    plan, _, _ = dpasgd.multigraph_plan(net, FEMNIST, t=5)
    r, n = 4, net.num_silos
    rng = np.random.default_rng(2)
    batches_all = np.asarray(rng.normal(size=(r, 1, n, 1, D)), np.float32)
    rt = rtmod.make_flat_runtime(plan, jax.eval_shape(_toy_init, KEY), n)
    outs = {}
    for agg in ("reference", "kernel", "dense"):
        opt = flat_sgd(0.05)
        state = rtmod.init_flat_state(_toy_init, opt, rt, KEY)
        cycle = rtmod.make_cycle_fn(rt, loss_fn=_toy_loss, opt=opt,
                                    aggregator=agg)
        state, _ = cycle(state, {"t": jnp.asarray(batches_all)},
                         jnp.asarray(rt.strong[:r]),
                         jnp.asarray(rt.coeffs[:r]),
                         jnp.asarray(rt.diag[:r]))
        outs[agg] = np.asarray(state.w)
    np.testing.assert_allclose(outs["kernel"], outs["reference"],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs["dense"], outs["reference"],
                               rtol=1e-5, atol=1e-5)


def test_cycle_traces_exactly_once():
    """A full multigraph cycle is ONE compiled dispatch: repeated cycles
    never retrace (acceptance criterion for the whole-cycle scan)."""
    net = get_network("gaia")
    plan, _, _ = dpasgd.multigraph_plan(net, FEMNIST, t=5)
    r, n = plan.num_rounds_cycle, net.num_silos
    rt = rtmod.make_flat_runtime(plan, jax.eval_shape(_toy_init, KEY), n)
    opt = flat_sgd(0.05)
    state = rtmod.init_flat_state(_toy_init, opt, rt, KEY)
    cycle = rtmod.make_cycle_fn(rt, loss_fn=_toy_loss, opt=opt)
    rng = np.random.default_rng(3)
    for _ in range(3):  # 3 cycles = 3*R rounds, one trace
        batches = np.asarray(rng.normal(size=(r, 1, n, 1, D)), np.float32)
        state, losses = cycle(state, {"t": jnp.asarray(batches)},
                              jnp.asarray(rt.strong),
                              jnp.asarray(rt.coeffs),
                              jnp.asarray(rt.diag))
        assert losses.shape == (r,)
    assert cycle.trace_count["count"] == 1


def test_flat_sgd_matches_vmapped_sgd():
    n, t = 5, 33
    w = jax.random.normal(KEY, (n, t))
    g = jax.random.normal(jax.random.PRNGKey(1), (n, t))
    for momentum in (0.0, 0.9):
        ref_opt = sgd(0.1, momentum=momentum)
        fl_opt = flat_sgd(0.1, momentum=momentum)
        ref_state = jax.vmap(ref_opt.init)(w)
        fl_state = fl_opt.init(w)
        wr, wf = w, w
        for _ in range(3):
            wr, ref_state = jax.vmap(
                lambda p, gg, s: ref_opt.update(p, gg, s))(wr, g, ref_state)
            wf, fl_state = fl_opt.update(wf, g, fl_state)
        np.testing.assert_array_equal(np.asarray(wr), np.asarray(wf))


# ---------------------------------------------------------------------------
# trainer end-to-end: flat == legacy
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_trainer_flat_matches_legacy():
    from repro.fl.trainer import FLConfig, run_fl
    base = dict(dataset="femnist", network="gaia", topology="multigraph",
                rounds=4, eval_every=2, samples_per_silo=16, batch_size=4,
                lr=0.05, seed=3)
    flat = run_fl(FLConfig(runtime="flat", **base))
    legacy = run_fl(FLConfig(runtime="legacy", **base))
    assert flat.round_losses == legacy.round_losses
    assert flat.eval_rounds == legacy.eval_rounds
    assert flat.eval_accs == legacy.eval_accs


@pytest.mark.slow
def test_trainer_flat_matches_legacy_momentum():
    """momentum>0 end-to-end cycle equivalence (flat_sgd vs sgd),
    bit-for-bit: the FMA-contraction sites of the momentum update are
    pinned (fl/flat.py `pin_f32`), so the packed and per-leaf layouts
    produce identical curves — no ulp allowance anymore."""
    from repro.fl.trainer import FLConfig, run_fl
    base = dict(dataset="femnist", network="gaia", topology="multigraph",
                rounds=4, eval_every=2, samples_per_silo=16, batch_size=4,
                lr=0.05, momentum=0.9, seed=5)
    flat = run_fl(FLConfig(runtime="flat", **base))
    legacy = run_fl(FLConfig(runtime="legacy", **base))
    assert flat.round_losses == legacy.round_losses
    assert flat.eval_rounds == legacy.eval_rounds
    assert flat.eval_accs == legacy.eval_accs
    # both runtimes share the same TimingPlan wall-clock axis exactly
    assert flat.cycle_times_ms == legacy.cycle_times_ms


# ---------------------------------------------------------------------------
# pin_dtype: uint-width generalization of pin_f32 (bf16 / f16 / f32 / f64)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,uint", [
    (jnp.float16, jnp.uint16),
    (jnp.bfloat16, jnp.uint16),
    (jnp.float32, jnp.uint32),
])
@pytest.mark.parametrize("step", [0, 1, 2 ** 15 + 3, 2 ** 31 - 1])
def test_pin_dtype_is_bitwise_identity(dtype, uint, step):
    """The opaque-zero xor must be a bitwise no-op for EVERY pinnable
    dtype and EVERY step value — in particular steps >= 2**15, where a
    naive cast of the step to a 16-bit uint before the >> (width-1)
    shift would leak a set bit into the xor and flip real mantissa
    bits (the trap the uint32-first derivation avoids)."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=257), dtype)
    y = jax.jit(flatmod.pin_dtype)(x, jnp.int32(step))
    assert y.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(jax.lax.bitcast_convert_type(x, uint)),
        np.asarray(jax.lax.bitcast_convert_type(y, uint)))


def test_pin_dtype_f64_and_passthrough():
    """f64 maps to uint64 (under x64), non-float dtypes pass through
    untouched, and `pin_f32` remains an alias of `pin_dtype`."""
    assert flatmod.pin_f32 is flatmod.pin_dtype
    ints = jnp.arange(5, dtype=jnp.int32)
    assert flatmod.pin_dtype(ints, jnp.int32(1)) is ints
    with jax.enable_x64(True):
        x = jnp.asarray(np.random.default_rng(1).normal(size=64),
                        jnp.float64)
        y = jax.jit(flatmod.pin_dtype)(x, jnp.int32(2 ** 15 + 7))
        assert y.dtype == jnp.float64
        np.testing.assert_array_equal(
            np.asarray(x).view(np.uint64), np.asarray(y).view(np.uint64))


def test_pin_dtype_pins_momentum_bits_in_f32():
    """The original pin_f32 contract, restated through the alias: the
    pinned mul-feeding-add computes mul-then-add bits under jit."""
    rng = np.random.default_rng(2)
    m = jnp.asarray(rng.normal(size=1024), jnp.float32)
    g = jnp.asarray(rng.normal(size=1024), jnp.float32)

    def pinned(m, g, step):
        return flatmod.pin_dtype(jnp.float32(0.9) * m, step) + g

    got = jax.jit(pinned)(m, g, jnp.int32(3))
    want = np.asarray(jnp.float32(0.9) * m) + np.asarray(g)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  want.astype(np.float32).view(np.uint32))
