"""Pod-scale VC runtime on a 1x1x1 mesh: island weights (Eq. 2), survivor
masking, the vc_round contract, and compressed assimilation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import flat as F
from repro.core.vc_asgd import assimilation_weights
from repro.models.registry import build_model
from repro.optim import Adam
from repro.runtime.sharding import MeshPlan
from repro.runtime.vc_runtime import (compressed_assimilate, island_weights,
                                      make_vc_round, redistribute_flat,
                                      redistribute_per_leaf)
from repro.launch.mesh import make_mesh, make_pod_mesh


def test_island_weights_match_eq2():
    w, ws = island_weights(4, 0.9, jnp.ones((4,), bool))
    ref = assimilation_weights(4, 0.9)
    np.testing.assert_allclose(np.asarray(w), ref[1:], rtol=1e-6)
    assert abs(float(ws) - ref[0]) < 1e-6


def test_island_weights_survivor_mask():
    surv = jnp.asarray([True, False, True, True])
    w, ws = island_weights(4, 0.9, surv)
    assert float(w[1]) == 0.0
    assert abs(float(w.sum() + ws) - 1.0) < 1e-6      # still convex


def test_vc_round_runs_and_learns():
    cfg = get_reduced("internlm2-1.8b")
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = MeshPlan.build(cfg, mesh)
    opt = Adam(lr=1e-3)
    n_pods, k = 2, 2
    vc_round = make_vc_round(model, plan, n_pods, k, opt)
    key = jax.random.PRNGKey(0)
    server = model.init(key)
    islands = jax.tree.map(lambda s: jnp.stack([s] * n_pods), server)
    opts = jax.vmap(opt.init)(islands)
    toks = jax.random.randint(key, (n_pods, k, 4, 32), 0, cfg.vocab_size)
    batches = {"tokens": toks}
    with mesh:
        losses = []
        for rnd in range(4):
            server, islands, opts, m = vc_round(
                server, islands, opts, batches,
                jnp.asarray(0.5, jnp.float32), jnp.ones((n_pods,), bool))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_vc_round_dead_island_is_ignored():
    """A dead island's (stale) params must not affect the server."""
    cfg = get_reduced("internlm2-1.8b")
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = MeshPlan.build(cfg, mesh)
    opt = Adam(lr=1e-3)
    vc_round = make_vc_round(model, plan, 2, 1, opt)
    key = jax.random.PRNGKey(1)
    server = model.init(key)
    islands = jax.tree.map(lambda s: jnp.stack([s, s]), server)
    # poison island 0 with garbage
    islands = jax.tree.map(
        lambda x: x.at[0].set(jnp.full_like(x[0], 1e9)), islands)
    opts = jax.vmap(opt.init)(islands)
    toks = jax.random.randint(key, (2, 1, 2, 16), 0, cfg.vocab_size)
    with mesh:
        server2, _, _, _ = vc_round(server, islands, opts, {"tokens": toks},
                                    jnp.asarray(0.9, jnp.float32),
                                    jnp.asarray([False, True]))
    for leaf in jax.tree.leaves(server2):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
        assert np.abs(np.asarray(leaf, np.float32)).max() < 1e6


def test_redistribute_flat_matches_per_leaf_broadcast():
    """Step-3 redistribution on the bus is BIT-identical to the retained
    per-leaf tree.map broadcast oracle, mixed dtypes included."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    server = {"w": jax.random.normal(ks[0], (300, 41)),
              "b": jax.random.normal(ks[1], (9,), jnp.bfloat16),
              "d": {"m": jax.random.normal(ks[2], (2, 3, 4))}}
    n_pods = 3
    islands = jax.tree.map(
        lambda s: jnp.stack([s + 0.1 * (j + 1) for j in range(n_pods)]),
        server)
    isl_buf, spec = F.flatten_batched(islands)
    s_buf = F.flatten_like(server, spec)
    got = F.unflatten_batched(redistribute_flat(s_buf, n_pods), spec)
    oracle = redistribute_per_leaf(server, islands)
    for a, b in zip(jax.tree.leaves(oracle), jax.tree.leaves(got)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_redistribute_flat_sharded_1dev_matches():
    """The shard_map route (each device broadcasts only its own segment)
    equals the single-host broadcast bit-for-bit."""
    mesh = make_pod_mesh(1)
    buf = jax.random.normal(jax.random.PRNGKey(4), (2 * 8192,))
    plain = redistribute_flat(buf, 4)
    shard = redistribute_flat(buf, 4, mesh=mesh, shard_axis="pod")
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(shard))


def test_compressed_assimilate_error_feedback():
    key = jax.random.PRNGKey(2)
    server = {"w": jax.random.normal(key, (64, 32))}
    islands = {"w": jnp.stack([server["w"] + 0.1,
                               server["w"] - 0.2])}
    surv = jnp.ones((2,), bool)
    s1, res = compressed_assimilate(server, islands, 0.8, surv, density=0.25)
    # residuals exist and have island-major shape
    assert res["w"].shape == (2, 64, 32)
    # a second round with residual carry moves closer to the uncompressed
    from repro.runtime.vc_runtime import island_weights
    w, ws = island_weights(2, 0.8, surv)
    exact = ws * server["w"] + sum(
        float(w[j]) * islands["w"][j] for j in range(2))
    err1 = float(jnp.abs(s1["w"] - exact).mean())
    assert err1 < 0.05                                 # compression is close
