"""End-to-end behaviour tests: the whole paper pipeline at laptop scale —
work generation -> scheduling -> client training -> VC-ASGD assimilation ->
epoch rollover -> checkpoint/restart."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core.baselines import VCASGD
from repro.core.simulator import SimConfig, run_simulation
from repro.core.tasks import MLPTask, make_classification_data
from repro.core.vc_asgd import var_alpha
from repro.launch.mesh import make_mesh


def test_full_system_with_everything_on(tmp_path):
    """Preemptible + eventual consistency + var-alpha + heterogeneous fleet:
    the paper's full configuration, end to end."""
    task = MLPTask()
    data = make_classification_data(n_train=2000, n_val=500)
    cfg = SimConfig(n_param_servers=3, n_clients=5, tasks_per_client=2,
                    n_shards=10, max_epochs=4, local_steps=2,
                    preemptible=True, mean_lifetime_s=1500.0,
                    consistency="eventual", subtask_compute_s=150.0, seed=7)
    res = run_simulation(task, data, VCASGD(var_alpha()), cfg)
    assert res.epochs_done == 4
    assert res.final_accuracy > 0.25
    assert res.results_assimilated >= 40          # every shard, every epoch


def test_checkpoint_restart_mid_training(tmp_path):
    """Kill-and-resume: server params checkpointed after round r restore
    bit-exactly and training continues."""
    from repro.configs import get_reduced
    from repro.models.registry import build_model
    from repro.optim import Adam
    from repro.runtime.sharding import MeshPlan
    from repro.runtime.vc_runtime import make_vc_round

    cfg = get_reduced("internlm2-1.8b")
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = MeshPlan.build(cfg, mesh)
    opt = Adam(lr=1e-3)
    vc = make_vc_round(model, plan, 2, 1, opt)
    key = jax.random.PRNGKey(0)
    mgr = CheckpointManager(tmp_path, async_save=False)

    with mesh:
        server = model.init(key)
        islands = jax.tree.map(lambda s: jnp.stack([s, s]), server)
        opts = jax.vmap(opt.init)(islands)
        toks = jax.random.randint(key, (2, 1, 2, 32), 0, cfg.vocab_size)
        for rnd in range(2):
            server, islands, opts, _ = vc(server, islands, opts,
                                          {"tokens": toks},
                                          jnp.asarray(0.7, jnp.float32),
                                          jnp.ones((2,), bool))
            mgr.save(rnd + 1, server, {"round": rnd + 1})

        # crash; restore
        restored, extra, step = mgr.restore_or_init(server, lambda: None)
        assert step == 2 and extra["round"] == 2
        for a, b in zip(jax.tree.leaves(server), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        # and training continues from the restored copy
        server2, _, _, m = vc(restored, islands, opts, {"tokens": toks},
                              jnp.asarray(0.75, jnp.float32),
                              jnp.ones((2,), bool))
        assert np.isfinite(float(m["loss"]))
