"""End-to-end VC-ASGD trainer.

Runs the paper's full loop at any scale the host provides: a mesh of
(pod, data, model), per-pod client islands doing local steps, Eq. 2
assimilation between rounds, timeout-free fault handling (an island that
fails a round is simply masked out of the assimilation), checkpoint /
restart of the server copy, and the epoch-varying alpha schedule.

``train(cfg, ...)`` is the loop; ``main()`` and ``chip_smoke.py`` both
call it.  Without ``--ckpt-dir`` every run starts fresh in a new
directory; pass an existing one to resume from its newest checkpoint.

CPU example (2 islands, reduced model):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \\
      --reduced --rounds 20 --local-steps 4 --islands 2
"""
from __future__ import annotations

import argparse
import tempfile
import time
from typing import Callable, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compile_cache, obs
from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_reduced
from repro.core.vc_asgd import var_alpha, const_alpha
from repro.data import make_batch_for
from repro.launch.mesh import make_mesh
from repro.models.common import ModelConfig
from repro.models.registry import build_model
from repro.optim import Adam
from repro.runtime.sharding import MeshPlan
from repro.runtime.vc_runtime import island_shardings, make_vc_round


def build_round_step(cfg: ModelConfig, mesh, n_pods: int, local_steps: int,
                     optimizer):
    """The jitted VC round on ``mesh`` (axes pod, data, model), with the
    model, its MeshPlan and the (server, islands, opt state) shardings
    the round takes.  All three states are donated: the round returns
    their successors, so the trainer never holds a state twice.  With
    more than one island the flat bus is sharded over ``pod``, so each
    island's device assimilates its own contiguous segment."""
    model = build_model(cfg)
    plan = MeshPlan.build(cfg, mesh, data_axis="data")
    vc_round = make_vc_round(model, plan, n_pods, local_steps, optimizer,
                             flat_shard_axis="pod" if n_pods > 1 else None)
    shardings = island_shardings(model, plan, n_pods, optimizer)
    server_sh, island_sh, opt_sh = shardings
    rep = NamedSharding(mesh, P())
    step = jax.jit(vc_round,
                   in_shardings=(server_sh, island_sh, opt_sh, None, rep,
                                 rep),
                   out_shardings=(server_sh, island_sh, opt_sh,
                                  {"loss": rep}),
                   donate_argnums=(0, 1, 2))
    return model, plan, step, shardings


def place_state(server, n_pods: int, optimizer, shardings):
    """(server, islands, opt state) for round one: every island starts
    from the server.  Placed as the round returns them, so round 2 reuses
    round 1's executable instead of compiling again."""
    isl = jax.tree.map(
        lambda s: jnp.broadcast_to(s[None], (n_pods, *s.shape)), server)
    opts = jax.vmap(optimizer.init)(isl)
    return jax.device_put((server, isl, opts), shardings)


def train(cfg: ModelConfig, *, ckpt_dir: str, islands: int = 1,
          rounds: int = 10, local_steps: int = 4, batch: int = 8,
          seq: int = 128, alpha: str = "var",
          lr: Union[float, Callable] = 1e-3, mesh_shape=(1, 1),
          preempt_round: int = -1, seed: int = 0, ckpt_every: int = 1,
          ckpt_keep: int = 3, log: Callable[[str], None] = print):
    """Train ``cfg`` for ``rounds`` VC rounds with Adam at ``lr`` (a rate
    or a schedule of the optimizer step), checkpointing the server to
    ``ckpt_dir`` every ``ckpt_every`` rounds and keeping the newest
    ``ckpt_keep`` (resuming from its newest checkpoint if it has one).
    Returns {"losses": per-round loss, "server": final server params,
    "start_round": the round it resumed at}."""
    n_pods = islands
    dm = tuple(mesh_shape)
    n_dev = len(jax.devices())
    if n_pods * dm[0] * dm[1] > n_dev:
        raise ValueError(f"need {n_pods * dm[0] * dm[1]} devices, "
                         f"have {n_dev}")
    mesh = make_mesh((n_pods, dm[0], dm[1]), ("pod", "data", "model"))
    optimizer = Adam(lr=lr)
    alpha_fn = var_alpha() if alpha == "var" else const_alpha(float(alpha))
    model, _, step_fn, shardings = build_round_step(cfg, mesh, n_pods,
                                                    local_steps, optimizer)

    ckpt = CheckpointManager(ckpt_dir, keep=ckpt_keep)
    key = jax.random.PRNGKey(seed)

    def init_server():
        return model.init(key)

    losses = []
    with mesh:
        server, _, start_round = ckpt.restore_or_init(
            jax.eval_shape(init_server) if ckpt.latest_step() else None,
            init_server)
        server, isl, opts = place_state(server, n_pods, optimizer,
                                        shardings)

        log(f"[train] {cfg.describe()}")
        log(f"[train] islands={n_pods} mesh={dict(mesh.shape)} "
            f"resume_round={start_round}")
        saved = start_round
        t_log = time.time()
        for rnd in range(start_round, rounds):
            with obs.step_span("vc.round", rnd):
                with obs.span("vc.batches", round=rnd):
                    batches = round_batches(cfg, n_pods, local_steps, batch,
                                            seq, seed=seed * 7919 + rnd)
                survivors = np.ones((n_pods,), bool)
                if rnd == preempt_round:
                    survivors[0] = False      # island 0 preempted this round
                    log(f"[train] round {rnd}: island 0 PREEMPTED "
                        f"(masked out of assimilation)")
                a = jnp.asarray(alpha_fn(rnd + 1), jnp.float32)
                with obs.span("vc.dispatch", round=rnd):
                    server, isl, opts, metrics = step_fn(
                        server, isl, opts, batches, a, jnp.asarray(survivors))
                with obs.span("vc.loss_wait", round=rnd):
                    loss = float(metrics["loss"])
                losses.append(loss)
                # a round runs from the previous round's line to this one,
                # so it holds the previous round's save
                t_prev, t_log = t_log, time.time()
                log(f"[train] round {rnd:3d} alpha={float(a):.3f} "
                    f"loss={loss:.4f} ({t_log - t_prev:.1f}s)")
                if (rnd + 1) % ckpt_every == 0:
                    saved = rnd + 1
                    ckpt.save(saved, server, {"round": saved})
        with obs.span("vc.ckpt.wait", step=saved):
            ckpt.wait()
    return {"losses": losses, "server": server, "start_round": start_round}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--islands", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--alpha", default="var",
                    help="'var' (paper schedule) or a float")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="1,2",
                    help="data,model mesh inside each island")
    ap.add_argument("--ckpt-dir", default=None,
                    help="resume from / checkpoint to this directory "
                         "(default: a fresh temporary directory)")
    ap.add_argument("--preempt-round", type=int, default=-1,
                    help="simulate island-0 preemption at this round")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="vcjax_ckpt_")
    train(cfg, ckpt_dir=ckpt_dir, islands=args.islands, rounds=args.rounds,
          local_steps=args.local_steps, batch=args.batch, seq=args.seq,
          alpha=args.alpha, lr=args.lr,
          mesh_shape=tuple(int(x) for x in args.mesh.split(",")),
          preempt_round=args.preempt_round, seed=args.seed)
    print("[train] done; server checkpoint at", ckpt_dir)
    return 0


def round_batches(cfg, n_pods, local_steps, batch, seq, seed):
    bs = []
    for p in range(n_pods):
        steps = [make_batch_for(cfg, batch, seq, seed=seed * 31 + p * 7 + s)
                 for s in range(local_steps)]
        bs.append(jax.tree.map(lambda *x: jnp.stack(x), *steps))
    return jax.tree.map(lambda *x: jnp.stack(x), *bs)


if __name__ == "__main__":
    raise SystemExit(main())
