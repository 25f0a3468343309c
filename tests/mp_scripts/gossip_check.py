"""Multi-device gossip backend checks. Run in a SUBPROCESS with

xla_force_host_platform_device_count=8 (tests/test_fl.py drives this);
the main pytest process must keep seeing 1 device."""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402
from jax import shard_map  # noqa: E402

from repro.core.consensus import metropolis_weights  # noqa: E402
from repro.core.graph import make_graph  # noqa: E402
from repro.fl.gossip import (gossip_dense, gossip_ring_ppermute,  # noqa: E402
                             init_ring_buffers, ring_coefficients)


def main():
    n = 8
    assert jax.device_count() == n, jax.device_count()
    mesh = jax.make_mesh((n,), ("silo",))

    ring = make_graph(n, [(i, (i + 1) % n) for i in range(n)])
    a = jnp.asarray(metropolis_weights(ring), jnp.float32)

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(n, 16, 32)), jnp.float32)
    params = {"w": w}  # leading silo axis, sharded over the mesh

    # ---- dense backend == matrix product ----
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=({"w": P("silo")}, None), out_specs={"w": P("silo")})
    def dense_step(p, amat):
        local = {"w": p["w"][0]}  # shed the silo axis inside the shard
        out = gossip_dense(local, amat, "silo")
        return {"w": out["w"][None]}

    got = dense_step(params, a)["w"]
    want = jnp.einsum("ij,jkl->ikl", a, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    print("dense-ok")

    # ---- ring ppermute backend: strong round == dense with ring MH ----
    cs, cl, cr = ring_coefficients(n)

    def ring_step(p, bufs, active_left, active_right):
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=({"w": P("silo")},
                      {"left": {"w": P("silo")}, "right": {"w": P("silo")}},
                      None, None, None),
            out_specs=({"w": P("silo")},
                       {"left": {"w": P("silo")}, "right": {"w": P("silo")}}))
        def inner(p, bufs, cs_, cl_, cr_):
            local = {"w": p["w"][0]}
            lb = {"w": bufs["left"]["w"][0]}
            rb = {"w": bufs["right"]["w"][0]}
            out, nb = gossip_ring_ppermute(
                local, {"left": lb, "right": rb},
                coeff_self=cs_, coeff_left=cl_, coeff_right=cr_,
                axis="silo", active_left=active_left,
                active_right=active_right)
            return ({"w": out["w"][None]},
                    {"left": {"w": nb["left"]["w"][None]},
                     "right": {"w": nb["right"]["w"][None]}})

        return inner(p, bufs, cs, cl, cr)

    bufs = {"left": {"w": w.copy()}, "right": {"w": w.copy()}}
    got, nb = ring_step(params, bufs, True, True)
    want = jnp.einsum("ij,jkl->ikl", a, w)
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    print("ring-strong-ok")

    # buffers now hold the true neighbours
    np.testing.assert_allclose(np.asarray(nb["left"]["w"]),
                               np.asarray(jnp.roll(w, 1, axis=0)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(nb["right"]["w"]),
                               np.asarray(jnp.roll(w, -1, axis=0)),
                               rtol=1e-6, atol=1e-6)
    print("ring-buffers-ok")

    # ---- weak round: NO collective; uses stale buffers ----
    w2 = jnp.asarray(rng.normal(size=(n, 16, 32)), jnp.float32)
    got2, _ = ring_step({"w": w2}, nb, False, False)
    want2 = (cs[:, None, None] * w2 +
             cl[:, None, None] * jnp.roll(w, 1, axis=0) +
             cr[:, None, None] * jnp.roll(w, -1, axis=0))
    np.testing.assert_allclose(np.asarray(got2["w"]), np.asarray(want2),
                               rtol=1e-5, atol=1e-6)
    print("ring-weak-ok")

    # ---- use_kernel: flat-packed fused combine == jnp path ----
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=({"w": P("silo")},
                  {"left": {"w": P("silo")}, "right": {"w": P("silo")}},
                  None, None, None),
        out_specs={"w": P("silo")},
        check_vma=False)  # pallas_call has no replication rule
    def ring_step_kernel(p, bufs, cs_, cl_, cr_):
        local = {"w": p["w"][0]}
        lb = {"w": bufs["left"]["w"][0]}
        rb = {"w": bufs["right"]["w"][0]}
        out, _ = gossip_ring_ppermute(
            local, {"left": lb, "right": rb},
            coeff_self=cs_, coeff_left=cl_, coeff_right=cr_,
            axis="silo", active_left=True, active_right=True,
            use_kernel=True)
        return {"w": out["w"][None]}

    got_k = ring_step_kernel(params, bufs, cs, cl, cr)["w"]
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    print("ring-kernel-ok")

    # ---- HLO check: weak round must not contain collective-permute ----
    import jax._src.test_util as _  # noqa: F401

    def lower_txt(active):
        fn = jax.jit(lambda p, b: ring_step(p, b, active, active))
        return fn.lower(params, bufs).as_text()

    strong_txt = lower_txt(True)
    weak_txt = lower_txt(False)
    names = ("collective-permute", "collective_permute", "ppermute")
    assert any(nm in strong_txt for nm in names), "no permute in strong HLO"
    assert not any(nm in weak_txt for nm in names), "permute leaked into weak HLO"
    print("hlo-ok")


if __name__ == "__main__":
    main()
