"""Multi-device sharding correctness — runs in subprocesses so the main
test process keeps a single CPU device (per the dry-run rules)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


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
def test_sharded_train_step_matches_single_device():
    """Same loss on a (2,2) mesh as on 1 device (GSPMD correctness)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models.registry import build_model
        from repro.optim import Adam
        from repro.runtime.sharding import MeshPlan
        from repro.launch.mesh import make_mesh
        from repro.runtime.train import make_train_step, shardings_for_train
        from repro.data import make_batch_for

        cfg = get_reduced("internlm2-1.8b").replace(compute_dtype="float32")
        model = build_model(cfg)
        opt = Adam(lr=1e-3)
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        batch = make_batch_for(cfg, 4, 64)

        # single device
        from repro.models.plan import NULL_PLAN
        loss1 = model.loss(params, batch)[0]

        mesh = make_mesh((2, 2), ("data", "model"))
        plan = MeshPlan.build(cfg, mesh)
        step = make_train_step(model, plan, opt)
        ins, outs = shardings_for_train(model, plan, opt, batch)
        with mesh:
            p2, o2, m = jax.jit(step, in_shardings=ins,
                                out_shardings=outs)(params, opt_state, batch)
        loss2 = m["loss"]
        print("LOSS", float(loss1), float(loss2))
        assert abs(float(loss1) - float(loss2)) < 2e-3, (loss1, loss2)
    """)
    assert "LOSS" in out


@pytest.mark.slow
def test_cp_arch_sharded_matches_single_device():
    """qwen-family (CP attention) on a (2,2) mesh == 1-device forward."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models.registry import build_model
        from repro.runtime.sharding import MeshPlan
        from repro.launch.mesh import make_mesh
        from repro.data import make_batch_for

        cfg = get_reduced("qwen2.5-14b").replace(compute_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = make_batch_for(cfg, 4, 64)
        lg1 = model.forward(params, batch)

        mesh = make_mesh((2, 2), ("data", "model"))
        # reduced config is tiny (d_model 80), so the planner would choose
        # "local"; force the CP path the full config takes (40 heads % 16)
        plan = MeshPlan.build(cfg, mesh, attn_mode="cp")
        assert plan.attn_mode == "cp", plan.attn_mode
        with mesh:
            lg2 = jax.jit(lambda p, b: model.forward(p, b, plan=plan))(params, batch)
        err = float(jnp.max(jnp.abs(lg1 - lg2)))
        print("ERR", err)
        assert err < 3e-3, err
    """)
    assert "ERR" in out


@pytest.mark.slow
def test_decode_cache_seq_sharded_matches():
    """Two-tier chunk-sharded decode on a mesh == single-device decode."""
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.models.registry import build_model
        from repro.runtime.sharding import MeshPlan
        from repro.launch.mesh import make_mesh
        from repro.data import make_batch_for

        cfg = get_reduced("mixtral-8x7b").replace(compute_dtype="float32")
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = make_batch_for(cfg, 4, 32)
        lg_p1, c1 = model.prefill(params, batch)
        tok = jnp.argmax(lg_p1[:, :cfg.vocab_size], -1).astype(jnp.int32)
        lg_d1, _ = model.decode_step(params, c1, tok, jnp.asarray(32, jnp.int32))

        mesh = make_mesh((2, 2), ("data", "model"))
        plan = MeshPlan.build(cfg, mesh, decode_batch=4)
        with mesh:
            lg_p2, c2 = jax.jit(lambda p, b: model.prefill(p, b, plan=plan))(params, batch)
            lg_d2, _ = jax.jit(lambda p, c, t, i: model.decode_step(
                p, c, t, i, plan=plan))(params, c2, tok, jnp.asarray(32, jnp.int32))
        e1 = float(jnp.max(jnp.abs(lg_p1 - lg_p2)))
        e2 = float(jnp.max(jnp.abs(lg_d1 - lg_d2)))
        print("ERRS", e1, e2)
        assert e1 < 3e-3 and e2 < 3e-3, (e1, e2)
    """)
    assert "ERRS" in out


@pytest.mark.slow
def test_vc_round_multi_pod_elasticity():
    """vc_round on a real (2,1,2) pod mesh: loss decreases AND a masked
    island does not corrupt the server (elastic fault tolerance)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models.registry import build_model
        from repro.optim import Adam
        from repro.runtime.sharding import MeshPlan
        from repro.launch.mesh import make_mesh
        from repro.runtime.vc_runtime import island_shardings, make_vc_round

        cfg = get_reduced("internlm2-1.8b")
        model = build_model(cfg)
        mesh = make_mesh((2, 1, 2), ("pod", "data", "model"))
        plan = MeshPlan.build(cfg, mesh)
        opt = Adam(lr=1e-3)
        vc_round = make_vc_round(model, plan, 2, 2, opt)
        key = jax.random.PRNGKey(0)
        with mesh:
            server = model.init(key)
            islands = jax.tree.map(lambda s: jnp.stack([s, s]), server)
            opts = jax.vmap(opt.init)(islands)
            toks = jax.random.randint(key, (2, 2, 4, 32), 0, cfg.vocab_size)
            losses = []
            for rnd in range(3):
                surv = jnp.asarray([rnd != 1, True])
                server, islands, opts, m = vc_round(
                    server, islands, opts, {"tokens": toks},
                    jnp.asarray(0.6, jnp.float32), surv)
                losses.append(float(m["loss"]))
            ok = all(np.isfinite(np.asarray(l, np.float32)).all()
                     for l in jax.tree.leaves(server))
        print("LOSSES", losses, ok)
        assert losses[-1] < losses[0] and ok
    """)
    assert "LOSSES" in out
