"""Compile the main path's Pallas kernels and the FL cycle for a TPU v5e.

The TPU compiler compiles for a described chip that is not attached
(`jax.experimental.topologies`), so these tests catch what interpret
mode cannot: block shapes the Mosaic lowering refuses, dynamic loads it
cannot express, and VMEM budgets it rejects. Nothing runs; each test
lowers at the real width and asserts the kernel (`tpu_custom_call`) is
in the compiled program.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

FEMNIST_T = 1_280_478   # FEMNIST CNN parameter count (paper width)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,e2", [(11, 22), (87, 174)],
                         ids=["femnist-gaia", "femnist-ebone"])
def test_edge_aggregate_compiles(shape, n, e2):
    from repro.kernels.gossip_combine.kernel import (_pick_block_t,
                                                     block_vmem_bytes,
                                                     edge_aggregate)
    compiled = edge_aggregate.lower(
        shape((n, FEMNIST_T)), shape((e2, FEMNIST_T)), shape((e2,)),
        shape((n + 1,), jnp.int32), shape((n,))).compile()
    _assert_kernel(compiled)
    block_t = _pick_block_t(FEMNIST_T, n, e2, 65536)
    assert block_vmem_bytes(n, e2, block_t) <= 12 << 20


def test_gossip_combine_compiles(shape):
    from repro.kernels.gossip_combine.kernel import gossip_combine
    _assert_kernel(gossip_combine.lower(shape((3, 1 << 22)),
                                        shape((3,))).compile())


def test_decode_attention_compiles(shape):
    """qwen2-7b decode widths: 28 query heads over 4 KV heads, hd=128."""
    from repro.configs import get_config
    from repro.kernels.decode_attention.kernel import decode_attention
    cfg = get_config("qwen2-7b")
    b, s = 8, 4096
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    compiled = decode_attention.lower(
        shape((b, hq, hd), jnp.bfloat16),
        shape((b, hkv, s, hd), jnp.bfloat16),
        shape((b, hkv, s, hd), jnp.bfloat16),
        shape((b,), jnp.int32)).compile()
    _assert_kernel(compiled)


def test_ssd_scan_compiles(shape):
    """mamba2-370m widths: 32 heads of 64, state 128, chunk 256."""
    from repro.configs import get_config
    from repro.kernels.ssd_scan.kernel import ssd_scan
    cfg = get_config("mamba2-370m")
    b, s = 1, 2048
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    compiled = ssd_scan.lower(
        shape((b, s, h, p)), shape((b, s, h)), shape((h,)),
        shape((b, s, n)), shape((b, s, n)), chunk=cfg.ssm_chunk).compile()
    _assert_kernel(compiled)


def test_flat_cycle_compiles(shape, monkeypatch):
    """The whole FEMNIST/gaia multigraph cycle, as `run_fl` builds it on
    a TPU: the runtime picks the Pallas aggregator and the kernel
    compiles (not interpreted) inside the scan."""
    from repro.core.delay import WORKLOADS
    from repro.fl import dpasgd
    from repro.fl import runtime as flrt
    from repro.models.small import SMALL_MODELS
    from repro.networks.zoo import get_network
    from repro.optim import flat_sgd

    # this process's backend is the CPU; steer the runtime's backend
    # choices onto their TPU branches for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    net = get_network("gaia")
    spec = SMALL_MODELS["femnist_cnn"]
    plan, _ = dpasgd.make_round_schedule(
        "multigraph", net, WORKLOADS["femnist"], t=5, rounds=1, seed=0)
    rt = flrt.make_flat_runtime(
        plan, jax.eval_shape(spec.init, jax.random.PRNGKey(0)),
        net.num_silos)
    n, t, e2 = net.num_silos, rt.spec.size, len(rt.src_sorted)
    r = rt.num_rounds_cycle
    assert (n, t, e2) == (11, FEMNIST_T, 22)
    cycle = flrt.make_cycle_fn(rt, loss_fn=lambda p, b: spec.loss(p, b),
                               opt=flat_sgd(0.05))
    state = flrt.FlatFLState(shape((n, t)), {"step": shape((), jnp.int32)},
                             shape((e2, t)))
    batches = {"x": shape((r, 1, n, 32) + spec.input_shape),
               "y": shape((r, 1, n, 32), jnp.int32)}
    compiled = cycle.lower(state, batches, shape((r, e2), jnp.bool_),
                           shape((r, e2)), shape((r, n))).compile()
    _assert_kernel(compiled)
    # the round's scopes reach the chip's program, the kernel under the
    # aggregation's: a profile splits device time by them
    text = compiled.as_text()
    kernel = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert all(flrt.SCOPE_AGGREGATE in ln for ln in kernel)
    assert flrt.SCOPE_LOCAL_SGD in text and flrt.SCOPE_REFRESH in text
    # the 32-channel conv is the chip's native convolution at the
    # configured precision: no learned-filter conv has a bf16 result, and
    # there are no (.., 14, 14, 800) patches. The 1-channel conv's 25-wide
    # patches come from one highest-precision patch-extraction conv (it
    # may store bf16(x), which the default-precision dot rounds to
    # anyway), not a concatenate
    convs = [ln for ln in text.splitlines() if " convolution(" in ln]
    patch = [ln for ln in convs if "operand_precision={highest" in ln]
    assert len(patch) == 1 and ",28,28,25]" in patch[0].split("=")[1]
    learned = [ln for ln in convs if ln not in patch]
    assert [ln for ln in learned if "conv_general_dilated" in ln]
    assert not [ln for ln in learned if "= bf16[" in ln]
    concats = [ln for ln in text.splitlines() if " concatenate(" in ln]
    assert not [ln for ln in concats if ",14,14,800]" in ln]
    assert not [ln for ln in concats if ",28,28,25]" in ln]
    mem = compiled.memory_analysis()
    # the state and edge buffers fit one v5e's 16 GB with room to spare
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 8 * 2 ** 30, total
