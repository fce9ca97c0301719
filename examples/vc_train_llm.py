"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
with the pod-scale VC-ASGD runtime (islands + Eq. 2 assimilation +
checkpoint/restart + a mid-run simulated island preemption), through
``launch/train.py::train``.

It runs on the devices it finds: one island per device pair when there
are several (two islands at most), one island on a single chip.  On a CPU
host, give it virtual devices; shrink --d-model for a faster demo.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python examples/vc_train_llm.py --rounds 60
"""
import argparse
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax                          # noqa: E402

from repro import compile_cache                         # noqa: E402
from repro.launch.train import train                    # noqa: E402
from repro.models.common import BlockSpec, ModelConfig, uniform_groups  # noqa: E402
from repro.optim import cosine_schedule                 # noqa: E402


def hundred_m_config(d_model: int) -> ModelConfig:
    """~100M params at d_model=640: 10L, ff 2560, 32k vocab."""
    return ModelConfig(
        arch="demo-100m", family="dense", d_model=d_model,
        n_heads=d_model // 80, n_kv_heads=max(1, d_model // 160),
        d_ff=d_model * 4, vocab_size=32768,
        layer_groups=uniform_groups(10, BlockSpec()),
        norm="rmsnorm", mlp_act="swiglu", max_seq=2048,
        attn_q_block=256, attn_kv_block=256)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--islands", type=int, default=None,
                    help="default: 2 when there are 2+ devices, else 1")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=640)
    ap.add_argument("--preempt-round", type=int, default=25)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint directory (default: a fresh one)")
    args = ap.parse_args(argv)

    compile_cache.enable()
    n_dev = len(jax.devices())
    islands = args.islands or min(2, n_dev)
    per_island = n_dev // islands
    tp = 2 if per_island >= 2 else 1
    cfg = hundred_m_config(args.d_model)
    ckpt = args.ckpt or tempfile.mkdtemp(prefix="vc_llm_ckpt_")
    lr = cosine_schedule(3e-4, warmup=20,
                         total=args.rounds * args.local_steps)
    out = train(cfg, ckpt_dir=ckpt, islands=islands, rounds=args.rounds,
                local_steps=args.local_steps, batch=args.batch,
                seq=args.seq, lr=lr, mesh_shape=(per_island // tp, tp),
                preempt_round=args.preempt_round, ckpt_every=20,
                ckpt_keep=2)
    print(f"[llm] done: loss {out['losses'][0]:.4f} -> "
          f"{out['losses'][-1]:.4f}; server checkpoints in {ckpt}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
