"""The plain reference of one FL cell: data, feed, first steps, eval.

It imports nothing of the program. From the seed it makes what the
program makes from the same seed: the synthetic federated data (class
prototypes plus noise, a Dirichlet label skew over the silos), the
batches of every round (one `rng.integers` draw per silo per local
update from `default_rng(seed + 1)`) and the starting weights (the
model's `init` on the first of `N` subkeys of `PRNGKey(seed)`, the same
for every silo). The generator is a copy of the program's
(`data/synthetic.py`, `fl/trainer.py`), kept here so that no later
change to the program can move it.

It takes one thing from the run: the round plan, i.e. which directed
edges are strong in each round and the mixing weights (`strong`,
`coeffs`, `diag`, with each edge's source and destination). A round of
decentralised SGD is then, for every silo i,

    w_i <- w_i - lr * grad loss(w_i; batch_i)            local SGD
    buf_e <- w_src(e)   if e is strong this round          refresh
    w_i <- diag_i * w_i + sum_{e: dst(e)=i} coeff_e * buf_e   aggregate

with every buffer starting at its source's initial weights. Rows are
kept one per silo, so the reference fits beside nothing but itself; the
local step runs `silo_block` silos at a time.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.core import nn

# ---------------------------------------------------------------------------
# data and feed
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Data:
    silo_x: list
    silo_y: list
    test_x: np.ndarray
    test_y: np.ndarray


def _dirichlet_partition(labels, num_silos, alpha, rng):
    num_classes = int(labels.max()) + 1
    idx_by_class = [np.flatnonzero(labels == c) for c in range(num_classes)]
    silo_idx = [[] for _ in range(num_silos)]
    for idxs in idx_by_class:
        rng.shuffle(idxs)
        props = rng.dirichlet(np.full(num_silos, alpha))
        cuts = (np.cumsum(props) * len(idxs)).astype(int)[:-1]
        for s, part in enumerate(np.split(idxs, cuts)):
            silo_idx[s].extend(part.tolist())
    out = []
    for s in range(num_silos):
        ii = np.array(sorted(silo_idx[s]), dtype=np.int64)
        if len(ii) < 2:
            ii = rng.integers(0, len(labels), size=8)
        out.append(ii)
    return out


def make_data(dcfg: dict, num_silos: int, samples_per_silo: int,
              alpha: float, seed: int) -> Data:
    """Image-classification stand-in: prototypes + gaussian noise."""
    rng = np.random.default_rng(seed + dcfg["seed_offset"])
    c, shape = dcfg["num_classes"], tuple(dcfg["shape"])
    n_test = dcfg["test_samples"]
    protos = rng.normal(size=(c,) + shape).astype(np.float32)
    protos /= np.linalg.norm(protos.reshape(c, -1),
                             axis=1).reshape((-1,) + (1,) * len(shape))
    protos *= np.sqrt(np.prod(shape))
    total = num_silos * samples_per_silo + n_test
    labels = rng.integers(0, c, size=total)
    x = (protos[labels] + dcfg["noise"] *
         rng.normal(size=(total,) + shape)).astype(np.float32)
    parts = _dirichlet_partition(labels[:-n_test], num_silos, alpha, rng)
    return Data([x[p] for p in parts],
                [labels[p].astype(np.int32) for p in parts],
                x[-n_test:], labels[-n_test:].astype(np.int32))


def make_feed(data: Data, rounds: int, batch: int, local_updates: int,
              seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Batches of the first `rounds` rounds: x (rounds, u, N, b, ...)."""
    rng = np.random.default_rng(seed + 1)
    n = len(data.silo_x)
    xs, ys = [], []
    for _ in range(rounds * local_updates):
        sel = [rng.integers(0, len(data.silo_x[s]), size=batch)
               for s in range(n)]
        xs.append(np.stack([data.silo_x[s][i] for s, i in enumerate(sel)]))
        ys.append(np.stack([data.silo_y[s][i] for s, i in enumerate(sel)]))
    shape = (rounds, local_updates, n, batch)
    return (np.stack(xs).reshape(shape + xs[0].shape[2:]),
            np.stack(ys).reshape(shape))


# ---------------------------------------------------------------------------
# flat layout of one model replica
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Layout:
    """Leaves of a parameter tree laid end to end, in tree order."""

    treedef: object
    shapes: tuple
    offsets: tuple
    size: int

    @classmethod
    def of(cls, params):
        leaves, treedef = jax.tree.flatten(params)
        shapes = tuple(tuple(l.shape) for l in leaves)
        sizes = [int(np.prod(s)) for s in shapes]
        return cls(treedef, shapes, tuple(int(o) for o in
                                          np.cumsum([0] + sizes[:-1])),
                   int(sum(sizes)))

    def leaf_slices(self):
        return [slice(o, o + int(np.prod(s)))
                for o, s in zip(self.offsets, self.shapes)]

    def ravel(self, params):
        return jnp.concatenate([l.reshape(-1) for l in
                                self.treedef.flatten_up_to(params)])

    def unravel(self, flat):
        return self.treedef.unflatten(
            [flat[sl].reshape(s) for sl, s in
             zip(self.leaf_slices(), self.shapes)])


def initial_row(model, mcfg, seed: int, num_silos: int):
    """(T,) float32 starting weights of every silo, and their layout."""
    key = jax.random.split(jax.random.PRNGKey(seed), num_silos)[0]
    p0 = model.init(key, mcfg)
    layout = Layout.of(p0)
    return layout.ravel(p0), layout


# ---------------------------------------------------------------------------
# the first steps
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Arith:
    """Storage dtype and matmul precision of one reference run."""

    dtype: object = jnp.float32
    precision: object = lax.Precision.HIGHEST


REFERENCE = Arith()
#: float32 at the matmul precision the configuration states (default:
#: on a TPU, one bfloat16 pass with float32 accumulation).
CONFIGURED = Arith(jnp.float32, lax.Precision.DEFAULT)
#: The control: the nearest precision below the configuration's float32
#: at default matmul precision, i.e. everything in bfloat16.
BF16 = Arith(jnp.bfloat16, lax.Precision.DEFAULT)


@dataclasses.dataclass
class Plan:
    """Round plan in one edge order: src/dst (2E,), per round (R, ·)."""

    src: np.ndarray
    dst: np.ndarray
    strong: np.ndarray      # (rounds, 2E) bool
    coeffs: np.ndarray      # (rounds, 2E)
    diag: np.ndarray        # (rounds, N)


@dataclasses.dataclass
class Trajectory:
    losses: list             # one mean loss per round
    snapshots: dict          # rounds done -> (N, T) float32 host rows
    grad_leaf_norms: np.ndarray   # first local step, per leaf, all silos


def _silo_step(model, mcfg, layout, arith, lr):
    def loss(w_row, x, y):
        p = layout.unravel(w_row)
        logits = model.apply(p, x.astype(arith.dtype), mcfg, arith.precision)
        return nn.cross_entropy(logits, y)

    def step(w, x, y):
        l, g = jax.vmap(jax.value_and_grad(loss))(w, x, y)
        gsq = jnp.stack([jnp.sum(jnp.square(g[:, sl].astype(jnp.float32)))
                         for sl in layout.leaf_slices()])
        return l.astype(jnp.float32), w - (lr * g).astype(w.dtype), gsq

    return jax.jit(step)


def run_first_steps(model, mcfg, w0, layout, x, y, plan: Plan, *, lr: float,
                    arith: Arith = REFERENCE, silo_block: int = 1,
                    snapshot_at=(), half_batch: bool = False,
                    frozen_edges: np.ndarray | None = None) -> Trajectory:
    """Follow rounds 0..len(x)-1 of decentralised SGD from `w0`.

    x, y: the feed, (rounds, u, N, b, ...). snapshot_at: rounds after
    which the rows are copied to the host. Two switches plant a fault
    for the readings of the comparison's upper ends: `half_batch` drops
    the second half of every batch (the loss is the mean over the
    rest), `frozen_edges` (2E,) bool never refreshes those buffers.
    """
    n = x.shape[2]
    step = _silo_step(model, mcfg, layout, arith, lr)
    rows = [jnp.asarray(w0, arith.dtype)] * n
    bufs = [rows[int(s)] for s in plan.src]
    coeffs = plan.coeffs.astype(np.float32)
    diag = plan.diag.astype(np.float32)
    in_edges = [np.flatnonzero(plan.dst == i) for i in range(n)]
    losses, snaps, gsq0 = [], {}, None
    b = x.shape[3] // 2 if half_batch else x.shape[3]
    for r in range(x.shape[0]):
        round_loss = []
        for u in range(x.shape[1]):
            new, ls, gs = [], [], []
            for lo in range(0, n, silo_block):
                blk = slice(lo, lo + silo_block)
                l, w, g = step(jnp.stack(rows[blk]),
                               jnp.asarray(x[r, u, blk, :b]),
                               jnp.asarray(y[r, u, blk, :b]))
                new.extend(list(w))
                ls.append(np.asarray(l))
                gs.append(np.asarray(g))
            rows = new
            round_loss.append(float(np.mean(np.concatenate(ls))))
            if gsq0 is None:
                gsq0 = np.sum(np.stack(gs), axis=(0, 1))
        losses.append(float(np.mean(round_loss)))
        strong = plan.strong[r]
        if frozen_edges is not None:
            strong = strong & ~frozen_edges
        bufs = [rows[int(s)] if strong[e] else bufs[e]
                for e, s in enumerate(plan.src)]
        out = []
        for i in range(n):
            acc = jnp.asarray(diag[r, i], arith.dtype) * rows[i]
            for e in in_edges[i]:
                acc = acc + jnp.asarray(coeffs[r, e], arith.dtype) * bufs[e]
            out.append(acc)
        rows = out
        if r + 1 in snapshot_at:
            snaps[r + 1] = np.stack([np.asarray(w, np.float32) for w in rows])
    return Trajectory(losses, snaps, np.sqrt(gsq0))


def first_loss(model, mcfg, w0, layout, x, y, *, arith: Arith,
               silo_block: int = 1) -> float:
    """Mean loss of the first round's batches at the starting weights."""
    def loss(w_row, x, y):
        logits = model.apply(layout.unravel(w_row), x.astype(arith.dtype),
                             mcfg, arith.precision)
        return nn.cross_entropy(logits, y)

    fn = jax.jit(jax.vmap(loss, in_axes=(None, 0, 0)))
    w = jnp.asarray(w0, arith.dtype)
    out = []
    for u in range(x.shape[1]):
        for lo in range(0, x.shape[2], silo_block):
            blk = slice(lo, lo + silo_block)
            out.append(np.asarray(fn(w, jnp.asarray(x[0, u, blk]),
                                     jnp.asarray(y[0, u, blk])), np.float32))
    return float(np.mean(np.concatenate(out)))


def accuracy(model, mcfg, layout, w_row: np.ndarray, test_x, test_y, *,
             arith: Arith = REFERENCE) -> float:
    """Test accuracy of one weight row on the whole test set, as one
    batch (a batch-statistics norm sees all of it at once)."""
    p = layout.unravel(jnp.asarray(w_row, arith.dtype))
    out = model.apply(p, jnp.asarray(test_x, arith.dtype), mcfg,
                      arith.precision)
    return float(np.mean(np.argmax(np.asarray(out, np.float32), -1)
                         == test_y))
