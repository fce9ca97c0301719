"""ShardedFlat (core/flat.py ShardedTreeSpec + runtime/sharding.py flat
ops): layout invariants, shard-vs-whole BIT-exactness of the flat kernels
under shard_map, the vc_round flat assimilation against the retained
per-leaf oracle, and sharded one-pass train records.

The multi-device parity sweep runs in a subprocess (slow-marked, like
tests/test_sharding_multi.py) so the main test process keeps one device.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core import flat as F
from repro.core import vc_asgd as V
from repro.launch.mesh import make_pod_mesh
from repro.optim import Adam
from repro.runtime import sharding as S
from repro.runtime.vc_runtime import (assimilate_flat,
                                      assimilate_islands_per_leaf,
                                      island_weights)

SRC = str(Path(__file__).resolve().parents[1] / "src")


def mixed_tree(key):
    ks = jax.random.split(key, 4)
    return {"w": jax.random.normal(ks[0], (300, 41), jnp.float32),
            "b": (jax.random.normal(ks[1], (9,), jnp.bfloat16),
                  jnp.arange(-3, 11, dtype=jnp.int32)),
            "deep": {"m": jax.random.normal(ks[2], (2, 3, 4), jnp.float32),
                     "v": jax.random.normal(ks[3], (130,), jnp.bfloat16)}}


# ---------------------------------------------------------------------------
# layout invariants + round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 3, 4, 7])
def test_sharded_layout_contract(n_shards):
    tree = mixed_tree(jax.random.PRNGKey(0))
    fp = F.flatten_sharded(tree, n_shards)
    sp = fp.spec
    assert isinstance(sp, F.ShardedTreeSpec)
    assert sp.padded == n_shards * sp.shard_len
    assert sp.shard_len % F.BLOCK == 0
    assert sp.padded >= sp.n
    # same leaf packing as the single-host layout (only tail pad differs)
    base = F.tree_spec(tree)
    assert sp.offsets == base.offsets and sp.sizes == base.sizes
    assert sp.n == base.n
    np.testing.assert_array_equal(np.asarray(fp.buf[sp.n:]), 0.0)
    # round trip with dtypes preserved
    back = F.unflatten(fp)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("n_shards", [1, 2, 5])
def test_shard_table_partitions_every_leaf_exactly_once(n_shards):
    tree = mixed_tree(jax.random.PRNGKey(1))
    sp = F.sharded_tree_spec(tree, n_shards)
    seen = {i: np.zeros(sz, bool) for i, sz in enumerate(sp.sizes)}
    for shard_i, segs in enumerate(sp.shard_table()):
        lo, hi = sp.shard_bounds(shard_i)
        for leaf_idx, leaf_off, length in segs:
            gstart = sp.offsets[leaf_idx] + leaf_off
            assert lo <= gstart and gstart + length <= hi   # truly local
            assert not seen[leaf_idx][leaf_off:leaf_off + length].any()
            seen[leaf_idx][leaf_off:leaf_off + length] = True
    for cov in seen.values():
        assert cov.all()


def test_shard_spec_rejects_bad_counts():
    sp = F.tree_spec(mixed_tree(jax.random.PRNGKey(2)))
    with pytest.raises(ValueError):
        F.shard_spec(sp, 0)


@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_property_sharded_roundtrip(data):
    n_leaves = data.draw(st.integers(min_value=1, max_value=5))
    shapes = [tuple(data.draw(st.integers(min_value=1, max_value=17))
                    for _ in range(data.draw(st.integers(min_value=1,
                                                         max_value=3))))
              for _ in range(n_leaves)]
    n_shards = data.draw(st.integers(min_value=1, max_value=6))
    key = jax.random.PRNGKey(data.draw(st.integers(min_value=0,
                                                   max_value=2 ** 16)))
    tree = {f"p{i}": jax.random.normal(jax.random.fold_in(key, i), shp)
            for i, shp in enumerate(shapes)}
    fp = F.flatten_sharded(tree, n_shards)
    assert fp.spec.padded == n_shards * fp.spec.shard_len
    back = F.unflatten(fp)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# shard-vs-whole parity on the in-process (1,) mesh (the multi-device sweep
# is the slow subprocess test below — same assertions, pod counts > 1)
# ---------------------------------------------------------------------------

def _f32_tree(key):
    ks = jax.random.split(key, 3)
    return {"a": jax.random.normal(ks[0], (301, 17)),
            "b": {"c": jax.random.normal(ks[1], (520,)),
                  "d": jax.random.normal(ks[2], (33, 40))}}


def test_sharded_assimilate_matches_single_host_1dev():
    mesh = make_pod_mesh(1)
    tree = _f32_tree(jax.random.PRNGKey(3))
    fp = F.flatten_sharded(tree, 1)
    clients = jnp.stack([fp.buf + 0.01 * (i + 1) for i in range(3)])
    w = V.assimilation_weights(3, 0.9)
    single = V.assimilate_many_flat(fp, clients, 0.9)
    shard = S.sharded_assimilate_flat(fp.buf, clients, w, mesh, "pod")
    np.testing.assert_array_equal(np.asarray(single.buf), np.asarray(shard))


def test_sharded_adam_matches_single_host_1dev():
    mesh = make_pod_mesh(1)
    tree = _f32_tree(jax.random.PRNGKey(4))
    fp = F.flatten_sharded(tree, 1)
    opt = Adam(lr=1e-3, weight_decay=0.01)
    fos = opt.init_flat(fp)
    g = jax.random.normal(jax.random.PRNGKey(5), fp.buf.shape) * 0.01
    for _ in range(3):
        fp1, fos1 = opt.update_flat(g, fos, fp)
        fp2, fos2 = opt.update_flat_sharded(g, fos, fp, mesh=mesh,
                                            axis="pod")
        np.testing.assert_array_equal(np.asarray(fp1.buf),
                                      np.asarray(fp2.buf))
        np.testing.assert_array_equal(np.asarray(fos1.m), np.asarray(fos2.m))
        np.testing.assert_array_equal(np.asarray(fos1.v), np.asarray(fos2.v))
        assert int(fos1.step) == int(fos2.step)
        fp, fos = fp1, fos1


def test_sharded_easgd_and_lerp_match_1dev():
    from repro.kernels import ref as R
    mesh = make_pod_mesh(1)
    fp = F.flatten_sharded(_f32_tree(jax.random.PRNGKey(6)), 1)
    reps = jnp.stack([fp.buf + 0.1, fp.buf - 0.2])
    c1, x1 = R.easgd_elastic(fp.buf, reps, 0.05)
    c2, x2 = S.sharded_easgd_flat(fp.buf, reps, 0.05, mesh, "pod")
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    l1 = R.vc_asgd_lerp(fp.buf, reps[0], 0.9)
    l2 = S.sharded_lerp_flat(fp.buf, reps[0], 0.9, mesh, "pod")
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_make_flat_train_step_mesh_matches_single_host():
    """The mesh-aware flat train step is bit-identical to the single-host
    one (the Adam update is per-shard elementwise)."""
    from repro.runtime.train import make_flat_train_step
    mesh = make_pod_mesh(1)
    tree = _f32_tree(jax.random.PRNGKey(7))
    opt = Adam(lr=1e-2)

    def loss_fn(p, batch):
        return sum(jnp.sum((x - 0.1) ** 2) for x in jax.tree.leaves(p))

    fp_a = F.flatten(tree)
    fp_b = F.flatten_sharded(tree, 1)
    step_a = make_flat_train_step(loss_fn, opt)
    step_b = make_flat_train_step(loss_fn, opt, mesh=mesh, shard_axis="pod")
    fos_a, fos_b = opt.init_flat(fp_a), opt.init_flat(fp_b)
    for _ in range(3):
        fp_a, fos_a, la = step_a(fp_a, fos_a, None)
        fp_b, fos_b, lb = step_b(fp_b, fos_b, None)
        assert float(la) == float(lb)
        # same logical prefix (padded tails differ only in layout length)
        n = fp_a.spec.n
        np.testing.assert_array_equal(np.asarray(fp_a.buf[:n]),
                                      np.asarray(fp_b.buf[:n]))


# ---------------------------------------------------------------------------
# vc_round assimilation: flat path vs the retained per-leaf oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dead", [None, 1])
def test_assimilate_flat_matches_per_leaf_oracle(dead):
    key = jax.random.PRNGKey(8)
    server = _f32_tree(key)
    n_pods = 3
    islands = jax.tree.map(
        lambda s: jnp.stack([s + 0.01 * (j + 1) for j in range(n_pods)]),
        server)
    surv = jnp.asarray([j != dead for j in range(n_pods)])
    if dead is not None:
        # a dead island may hold inf/nan — must not poison the server
        islands = jax.tree.map(
            lambda x: x.at[dead].set(jnp.inf), islands)
    w, w_s = island_weights(n_pods, 0.7, surv)
    oracle = assimilate_islands_per_leaf(server, islands, w, w_s)

    isl_buf, spec = F.flatten_batched(islands)
    s_buf = F.flatten_like(server, spec)
    out = F.unflatten(F.FlatParams(
        assimilate_flat(s_buf, isl_buf, w, w_s), spec))
    for a, b in zip(jax.tree.leaves(oracle), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_assimilate_flat_sharded_1dev_matches():
    mesh = make_pod_mesh(1)
    server = _f32_tree(jax.random.PRNGKey(9))
    islands = jax.tree.map(lambda s: jnp.stack([s + 0.1, s - 0.3]), server)
    surv = jnp.ones((2,), bool)
    w, w_s = island_weights(2, 0.8, surv)
    isl_buf, spec = F.flatten_batched(islands)
    s_buf = F.flatten_like(server, spec)
    plain = assimilate_flat(s_buf, isl_buf, w, w_s)
    sharded = assimilate_flat(s_buf, isl_buf, w, w_s, mesh=mesh,
                              shard_axis="pod")
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(sharded))


def test_assimilate_flat_kernel_close():
    """The fused Pallas route of the masked reduction stays numerically on
    top of the jnp form (bit-exactness is only pinned between the jnp
    forms — the kernel folds in a different order)."""
    server = _f32_tree(jax.random.PRNGKey(10))
    islands = jax.tree.map(lambda s: jnp.stack([s + 0.1, s - 0.3]), server)
    w, w_s = island_weights(2, 0.8, jnp.ones((2,), bool))
    isl_buf, spec = F.flatten_batched(islands)
    s_buf = F.flatten_like(server, spec)
    a = assimilate_flat(s_buf, isl_buf, w, w_s)
    b = assimilate_flat(s_buf, isl_buf, w, w_s, use_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# sharded one-pass train records
# ---------------------------------------------------------------------------

def test_sharded_train_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import (load_train_checkpoint,
                                  save_train_checkpoint)
    tree = _f32_tree(jax.random.PRNGKey(11))
    fp = F.flatten_sharded(tree, 4)
    opt = Adam(lr=1e-3)
    fos = opt.init_flat(fp)
    g = jax.random.normal(jax.random.PRNGKey(12), fp.buf.shape) * 0.01
    fp, fos = opt.update_flat(g, fos, fp)
    path = tmp_path / "train.msgpack"
    save_train_checkpoint(path, fp, fos, {"round": 3})
    fp2, fos2, extra = load_train_checkpoint(path, fp.spec)
    assert extra["round"] == 3
    assert isinstance(fp2.spec, F.ShardedTreeSpec)
    assert fp2.spec.n_shards == 4
    np.testing.assert_array_equal(np.asarray(fp.buf), np.asarray(fp2.buf))
    np.testing.assert_array_equal(np.asarray(fos.m), np.asarray(fos2.m))
    # a record written 4-way must not restore onto a 2-way layout
    with pytest.raises(ValueError, match="shard-layout"):
        load_train_checkpoint(path, F.shard_spec(F.tree_spec(tree), 2))


# ---------------------------------------------------------------------------
# multi-device parity sweep (subprocess, like test_sharding_multi.py)
# ---------------------------------------------------------------------------

def _run(py: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(py)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_shard_vs_whole_parity_every_pod_count():
    """assimilate_flat / adam_update_flat over the sharded bus are
    BIT-identical to the single-host flat path at every pod count the CPU
    mesh supports (1, 2, 4, 8), jnp and kernel routes.

    One fixed layout for the whole sweep (padded so 8 shards divide it):
    bit-exactness is a statement about the VALUES, so the buffers compared
    must be the same length — per-pod-count tail padding would compare
    different layouts, and XLA's elementwise codegen (FMA grouping) is
    length-dependent."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import flat as F
        from repro.core import vc_asgd as V
        from repro.launch.mesh import make_pod_mesh
        from repro.optim import Adam
        from repro.runtime import sharding as S
        from repro.kernels import ops as K

        key = jax.random.PRNGKey(0)
        tree = {f"p{i}": jax.random.normal(jax.random.fold_in(key, i),
                                           (257, 31 + i))
                for i in range(5)}
        opt = Adam(lr=1e-3, weight_decay=0.01)
        # one layout every pod count shards evenly (ShardedTreeSpec
        # geometry for 8 pods == BLOCK*8-padded flatten)
        fp8 = F.flatten_sharded(tree, 8)
        assert fp8.spec.padded == F.flatten(tree, pad_to=F.BLOCK * 8).buf.size
        clients = jnp.stack([fp8.buf + 0.01 * (i + 1) for i in range(3)])
        w = V.assimilation_weights(3, 0.9)
        g = jax.random.normal(jax.random.fold_in(key, 99),
                              fp8.buf.shape) * 0.01

        # the single-host flat path (what the runtime executes unsharded)
        single = V.assimilate_many_flat(fp8, clients, 0.9).buf
        single_k = K.fused_assimilate_flat(fp8.buf, clients, w)
        fos0 = opt.init_flat(fp8)
        p1, o1 = opt.update_flat(g, fos0, fp8)

        for n_pods in (1, 2, 4, 8):
            mesh = make_pod_mesh(n_pods)
            spec = F.shard_spec(F.tree_spec(tree), n_pods,
                                pad_to=F.BLOCK * (8 // n_pods))
            assert spec.padded == fp8.spec.padded
            sh = S.shard_flat(F.FlatParams(fp8.buf, spec), mesh)
            # every device owns exactly one contiguous segment
            assert len(sh.buf.sharding.device_set) == n_pods

            shard = S.sharded_assimilate_flat(sh.buf, clients, w,
                                              mesh, "pod")
            shard_k = S.sharded_assimilate_flat(sh.buf, clients, w, mesh,
                                                "pod", use_kernel=True)
            np.testing.assert_array_equal(np.asarray(single),
                                          np.asarray(shard))
            np.testing.assert_array_equal(np.asarray(single_k),
                                          np.asarray(shard_k))

            fos = F.init_opt_state(sh.spec)
            p2, o2 = opt.update_flat_sharded(g, fos, sh, mesh=mesh,
                                             axis="pod")
            pk, ok_ = opt.update_flat_sharded(g, fos, sh, mesh=mesh,
                                              axis="pod", use_kernel=True)
            np.testing.assert_array_equal(np.asarray(p1.buf),
                                          np.asarray(p2.buf))
            np.testing.assert_array_equal(np.asarray(o1.m), np.asarray(o2.m))
            np.testing.assert_array_equal(np.asarray(o1.v), np.asarray(o2.v))
            np.testing.assert_allclose(np.asarray(p1.buf), np.asarray(pk.buf),
                                       atol=1e-6)
            print("POD", n_pods, "OK")
        print("DONE")
    """)
    assert "DONE" in out
    for n in (1, 2, 4, 8):
        assert f"POD {n} OK" in out


@pytest.mark.slow
def test_vc_round_flat_sharded_on_pod_mesh():
    """make_vc_round with flat_shard_axis on a real (2,1,2) pod mesh:
    per-shard assimilation == unsharded flat assimilation bit-for-bit,
    loss decreases, and a masked island does not corrupt the server."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models.registry import build_model
        from repro.optim import Adam
        from repro.runtime.sharding import MeshPlan
        from repro.launch.mesh import make_mesh
        from repro.runtime.vc_runtime import make_vc_round

        cfg = get_reduced("internlm2-1.8b")
        model = build_model(cfg)
        mesh = make_mesh((2, 1, 2), ("pod", "data", "model"))
        plan = MeshPlan.build(cfg, mesh)
        opt = Adam(lr=1e-3)
        key = jax.random.PRNGKey(0)
        toks = jax.random.randint(key, (2, 2, 4, 32), 0, cfg.vocab_size)

        def play(axis):
            vc = make_vc_round(model, plan, 2, 2, opt,
                               flat_shard_axis=axis)
            with mesh:
                server = model.init(key)
                islands = jax.tree.map(lambda s: jnp.stack([s, s]), server)
                opts = jax.vmap(opt.init)(islands)
                losses = []
                for rnd in range(3):
                    surv = jnp.asarray([rnd != 1, True])
                    server, islands, opts, m = vc(
                        server, islands, opts, {"tokens": toks},
                        jnp.asarray(0.6, jnp.float32), surv)
                    losses.append(float(m["loss"]))
            return server, losses

        s_plain, l_plain = play(None)
        s_shard, l_shard = play("model")
        assert l_shard == l_plain, (l_shard, l_plain)
        for a, b in zip(jax.tree.leaves(s_plain), jax.tree.leaves(s_shard)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        ok = all(np.isfinite(np.asarray(l, np.float32)).all()
                 for l in jax.tree.leaves(s_shard))
        assert l_shard[-1] < l_shard[0] and ok
        print("LOSSES", l_shard, ok)
    """)
    assert "LOSSES" in out
