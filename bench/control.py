#!/usr/bin/env python3
"""Readings that set the limits of the check: stand-ins for the program.

    python3 bench/control.py --workload internlm2-1.8b.local8 \\
        --seeds 11,12,13 --variants fp8,half_batch,alter_token

For each seed the float32 reference follows the cell's first rounds (as a
run's check does); then each variant, put in the program's place, follows
the same rounds and is compared with it by ``bench/check.py``:

- ``fp8``: the control, the reference computed in float8 where the
  program computes in bfloat16 (e4m3 forward, e5m2 backward), the step
  below the configuration's;
- ``bf16``: the reference in the program's own precision, a witness of
  what a sound program reads;
- ``half_batch``: half of every batch left out, the mean over the rest;
- ``alter_token``: one token of every sequence altered where it is made;
- ``no_exchange``: each island's share of the bus merged alone, as if the
  chips never exchanged their segments (cells with several islands).

A state left unchanged reads 1 on ``update_gap`` by construction and is
not run.  One JSON line per seed and variant goes to standard output.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as R  # noqa: E402

VARIANTS = {"fp8": {"cdt": "float8"},
            "bf16": {"cdt": "bfloat16"},
            "half_batch": {"half_batch": True},
            "alter_token": {"alter_token": True},
            "no_exchange": {"no_exchange": True}}


def readings_for(cell, seed: int, variants, devices, out=print) -> None:
    from bench import check as C
    from bench.vc_ref import seed_words
    M = cell.model
    dm = M.dims(cell.conf)
    init = M.make_init(dm)
    w0 = init(seed_words(seed))
    used = devices[:cell.traffic.islands]
    t0 = time.perf_counter()
    ref = C.follow(M, dm, cell.traffic, seed, w0, devices=used)
    ref_s = time.perf_counter() - t0
    for v in variants:
        t0 = time.perf_counter()
        got = C.follow(M, dm, cell.traffic, seed, w0, devices=used,
                       **VARIANTS[v])
        out(json.dumps({"cell": cell.name, "seed": seed, "variant": v,
                        "readings": C.readings(got, ref),
                        "ref_losses": ref.losses, "losses": got.losses,
                        "ref_steps": ref.steps, "steps": got.steps,
                        "ref_s": ref_s,
                        "variant_s": time.perf_counter() - t0}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="fp8,bf16,half_batch,alter_token")
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    variants = [v for v in args.variants.split(",") if v]
    bad = [v for v in variants if v not in VARIANTS]
    if bad:
        ap.error(f"unknown variants {bad}")
    try:
        # the reference is plain: islands take the chips there are, in turn
        devices = R.use_devices(1)
    except R.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    R.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        readings_for(cell, seed, variants, devices)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
