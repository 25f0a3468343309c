"""Each configuration's FLOPs function against XLA's own count.

`forward_macs` counts the multiply-adds of the convolutions (every tap,
padding included, as the program's im2col matmul computes them) and
the dense layers. XLA's `cost_analysis()` of the program's forward pass
at batch 1 counts those as 2 flops each and adds the elementwise work
(ReLU, pooling, the batch-statistics norm, bias adds, the mean pool),
which MFU leaves out. So 2 x forward_macs must lie at or below XLA's
count and within 5 % of it.
"""

import jax
import jax.numpy as jnp
import pytest

from bench.core import cell as cellmod

CONFIGS = ["femnist_cnn", "inat_resnet"]


@pytest.mark.parametrize("name", CONFIGS)
def test_forward_flops_match_xla(name):
    from repro.models.small import SMALL_MODELS
    cfg = cellmod.json.loads(
        (cellmod.BENCH / "configs" / f"{name}.json").read_text())
    model = cellmod._load_module(cellmod.BENCH / "configs" / f"{name}.py",
                                 f"flops_{name}")
    spec = SMALL_MODELS[name]
    params = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1,) + tuple(cfg["input_shape"]), jnp.float32)
    xla = jax.jit(spec.apply).lower(params, x).compile().cost_analysis()
    ours = 2 * model.forward_macs(cfg)
    assert ours == 2 * cfg["forward_macs"]
    assert 0.95 * xla["flops"] <= ours <= xla["flops"], (ours, xla["flops"])


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_init_is_the_programs(name):
    """The reference draws the program's starting weights from the seed
    (bit for bit), and its parameter count is the configuration's."""
    from repro.models.small import SMALL_MODELS
    from bench.core import reference
    c = cellmod.load(f"{name}.gaia.multigraph")
    key = jax.random.split(jax.random.PRNGKey(2 ** 31 + 7), 11)[0]
    want = jax.tree.leaves(SMALL_MODELS[name].init(key))
    got = jax.tree.leaves(c.model.init(key, c.config))
    assert all(bool((a == b).all()) for a, b in zip(want, got))
    _, layout = reference.initial_row(c.model, c.config, 2 ** 31 + 7, 11)
    assert layout.size == c.config["params"]
