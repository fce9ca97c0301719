"""The program's observability hooks (``repro.obs``): the compile counter,
the trainer's printed round time, and the named scopes of the round's
phases in the lowered round."""
import re
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import get_reduced
from repro.launch import train as T
from repro.launch.mesh import make_mesh
from repro.optim import Adam


def test_compile_counter_counts_a_fresh_jit_and_names_it():
    c = obs.compiles()
    assert obs.compiles() is c
    x = jnp.ones(7)
    n = c.count

    def thrice_plus_one(x):
        return 3 * x + 1

    f = jax.jit(thrice_plus_one)
    f(x)
    assert c.count == n + 1 and c.last == "jit(thrice_plus_one)"
    f(x)                                # the same shape: no compile
    assert c.count == n + 1


def test_printed_round_time_holds_the_previous_rounds_save(tmp_path,
                                                          monkeypatch):
    """A round is timed from the previous round's line to its own, so a
    slow save shows in the round after it."""
    class SlowSave(T.CheckpointManager):
        def save(self, *a, **k):
            super().save(*a, **k)
            time.sleep(0.4)

    monkeypatch.setattr(T, "CheckpointManager", SlowSave)
    lines = []
    T.train(get_reduced("internlm2-1.8b"), ckpt_dir=str(tmp_path),
            islands=1, rounds=2, local_steps=1, batch=2, seq=16,
            ckpt_every=1, mesh_shape=(1, 1), log=lines.append)
    secs = [float(m) for m in re.findall(r"loss=\S+ \((\d+\.\d)s\)",
                                         "\n".join(lines))]
    assert len(secs) == 2 and secs[1] >= 0.4


def test_round_phases_carry_named_scopes():
    cfg = get_reduced("internlm2-1.8b")
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    optimizer = Adam(lr=1e-3)
    model, _, step, shardings = T.build_round_step(cfg, mesh, 1, 2,
                                                   optimizer)
    server = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    with mesh:
        state = jax.eval_shape(
            lambda s: T.place_state(s, 1, optimizer, shardings), server)
        batches = {"tokens": jax.ShapeDtypeStruct((1, 2, 2, 16),
                                                  jnp.int32)}
        text = step.lower(*state, batches,
                          jax.ShapeDtypeStruct((), jnp.float32),
                          jax.ShapeDtypeStruct((1,), jnp.bool_)
                          ).as_text(debug_info=True)
    for scope in ("vc.local_steps", "vc.flatten", "vc.eq2",
                  "vc.redistribute", "vc.unflatten"):
        assert f"/{scope}/" in text or f'"{scope}/' in text, scope
