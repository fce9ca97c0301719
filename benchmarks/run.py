"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (contract) and writes ONE
canonical ``results/BENCH_<suite>.json`` per suite (plus the aggregated
claims in ``results/BENCH_claims.json``).

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig4,cost]
  PYTHONPATH=src python -m benchmarks.run --check

``--check`` is the perf gate: it re-runs every launch-count-bearing suite
and fails (exit 1) if any suite's pallas launch counts regressed versus
the committed baseline (results/BASELINE_launches.json) — the fused
single-launch structure is the one perf property this CPU container can
pin exactly.  It ALSO runs the fleet smoke scenario and fails if its
event-loop throughput drops below the baselined events/sec floor
(baseline * FLOOR_FRACTION, so CI noise doesn't flake the gate), and the
per-kernel ROOFLINE gate (results/BASELINE_roofline.json): compiled-HLO
traffic per compression kernel vs its hand-derived analytic minimum, plus
a loose measured-bandwidth floor (see docs/ROOFLINE.md).  Before any of
that it runs the STATIC tier — ``tools/vclint.py --json`` against
results/BASELINE_vclint.json (exit 2 if no baseline is pinned; see
docs/LINT.md) — so protocol/wire/kernel invariant violations fail the
gate without running a single benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

RESULTS = Path(__file__).resolve().parents[1] / "results"
RESULTS.mkdir(exist_ok=True)

# one canonical BENCH_*.json name per suite (bench fns that write their own
# canonical file use the same name, so there is exactly ONE copy on disk)
CANONICAL = {
    "flat": "BENCH_flat_assimilate",
    "flat_adam": "BENCH_flat_adam",
    "sharded_flat": "BENCH_sharded_flat",
}

BASELINE = RESULTS / "BASELINE_launches.json"
# suites that carry a numeric _launches dict, gated by --check
LAUNCH_SUITES = ("flat", "flat_adam", "sharded_flat", "compression")


def _out_path(name: str) -> Path:
    return RESULTS / f"{CANONICAL.get(name, 'BENCH_' + name)}.json"


def check_vclint() -> int:
    """Static tier of the gate: run ``tools/vclint.py --json`` and defer
    to its ratchet exit code (0 clean, 1 new violations vs
    results/BASELINE_vclint.json, 2 no baseline pinned — re-pin with
    ``tools/vclint.py --update-baseline``, which --update-baseline here
    also does)."""
    import subprocess
    root = RESULTS.parent
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "vclint.py"), "--json"],
        capture_output=True, text=True, cwd=root)
    try:
        doc = json.loads(proc.stdout)
        print(f"check vclint: {doc['total']} violations in "
              f"{doc['files_checked']} files "
              f"({len(doc['rules_run'])} rules)")
    except (json.JSONDecodeError, KeyError):
        print(proc.stdout, file=sys.stderr)
    if proc.returncode:
        err = proc.stderr.strip()
        print(f"STATIC REGRESSION {err or 'vclint gate failed'}",
              file=sys.stderr)
    return proc.returncode


def check_launches(benches) -> int:
    """Re-run the launch-bearing suites and compare their _launches dicts
    against the committed baseline.  A HIGHER count than baseline is a
    regression (a fused pass broke apart); lower is an improvement (run
    with --update-baseline to ratchet it down)."""
    if not BASELINE.exists():
        print(f"no baseline at {BASELINE}; run --update-baseline first",
              file=sys.stderr)
        return 2
    baseline = json.loads(BASELINE.read_text())
    failures = []
    for name in LAUNCH_SUITES:
        res = benches[name]()
        _out_path(name).write_text(json.dumps(res, indent=1, default=str))
        current = res.get("_launches", {})
        base = baseline.get(name, {})
        for path_name, count in current.items():
            allowed = base.get(path_name)
            if allowed is None:
                failures.append(f"{name}.{path_name}: no baseline entry "
                                f"(current={count})")
            elif count > allowed:
                failures.append(f"{name}.{path_name}: {count} launches > "
                                f"baseline {allowed}")
            else:
                print(f"check {name}.{path_name}: {count} <= {allowed} OK")
    # event-loop throughput floor (fleet smoke scenario)
    from benchmarks.fleet_bench import FLOOR_FRACTION, smoke_events_per_sec
    base_eps = baseline.get("fleet", {}).get("smoke_events_per_sec")
    if base_eps is None:
        failures.append("fleet.smoke_events_per_sec: no baseline entry")
    else:
        eps = smoke_events_per_sec()
        floor = base_eps * FLOOR_FRACTION
        if eps < floor:
            failures.append(f"fleet.smoke_events_per_sec: {eps:.0f} < "
                            f"floor {floor:.0f} (baseline {base_eps:.0f})")
        else:
            print(f"check fleet.smoke_events_per_sec: {eps:.0f} >= "
                  f"{floor:.0f} OK")
    # handout dedup floor (content-addressed cache must keep serving
    # many more bytes than it encodes on the smoke subscriber scenario)
    from benchmarks.handout_bench import (DEDUP_FLOOR_FRACTION,
                                          smoke_unique_to_served)
    base_dedup = baseline.get("handout", {}).get("smoke_unique_to_served")
    if base_dedup is None:
        failures.append("handout.smoke_unique_to_served: no baseline entry")
    else:
        dedup = smoke_unique_to_served()
        floor = base_dedup * DEDUP_FLOOR_FRACTION
        if dedup < floor:
            failures.append(f"handout.smoke_unique_to_served: {dedup:.1f}x "
                            f"< floor {floor:.1f}x (baseline "
                            f"{base_dedup:.1f}x)")
        else:
            print(f"check handout.smoke_unique_to_served: {dedup:.1f}x >= "
                  f"{floor:.1f}x OK")
    # per-kernel roofline gate (results/BASELINE_roofline.json)
    from benchmarks.roofline_report import check_kernel_rooflines
    rc = check_kernel_rooflines()
    if rc:
        failures.append("kernel roofline gate failed (see above)")
    if failures:
        for f in failures:
            print(f"PERF REGRESSION {f}", file=sys.stderr)
        return 1
    print("launch-count + events/sec + dedup + roofline check passed")
    return 0


def update_baseline(benches) -> None:
    from benchmarks.fleet_bench import smoke_events_per_sec
    from benchmarks.handout_bench import smoke_unique_to_served
    from benchmarks.roofline_report import (ROOFLINE_BASELINE,
                                            write_roofline_baseline)
    out = {}
    for name in LAUNCH_SUITES:
        res = benches[name]()
        _out_path(name).write_text(json.dumps(res, indent=1, default=str))
        out[name] = res.get("_launches", {})
    out["fleet"] = {"smoke_events_per_sec": round(smoke_events_per_sec(), 1)}
    out["handout"] = {
        "smoke_unique_to_served": round(smoke_unique_to_served(), 1)}
    BASELINE.write_text(json.dumps(out, indent=1))
    print(f"wrote {BASELINE}: {json.dumps(out)}")
    write_roofline_baseline()
    print(f"wrote {ROOFLINE_BASELINE}")
    import subprocess
    subprocess.run(
        [sys.executable, str(RESULTS.parent / "tools" / "vclint.py"),
         "--update-baseline"], check=True, cwd=RESULTS.parent)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale horizons (40 epochs, 50 shards)")
    ap.add_argument("--only", default="",
                    help="comma list: fig2,fig3,fig4,fig6,consistency,cost,"
                         "kernels,flat,flat_adam,sharded_flat,fleet,"
                         "compression,frontier,handout")
    ap.add_argument("--check", action="store_true",
                    help="fail if vclint or any BENCH_*.json launch count "
                         "regresses vs the committed baselines")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite results/BASELINE_launches.json (and the "
                         "vclint baseline) from a fresh run")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import paper_figs as F
    from benchmarks.fleet_bench import bench_fleet
    from benchmarks.frontier_bench import bench_frontier
    from benchmarks.handout_bench import bench_handout
    from benchmarks.kernel_bench import (bench_compression, bench_flat_adam,
                                         bench_flat_assimilate,
                                         bench_kernels, bench_sharded_flat)

    benches = {
        "fig2": lambda: F.fig2_distributed(quick),
        "fig3": lambda: F.fig3_server_scaling(quick),
        "fig4": lambda: F.fig4_alpha(quick),
        "fig6": lambda: F.fig6_vs_serial(quick),
        "consistency": lambda: F.consistency_bench(quick),
        "cost": lambda: F.cost_bench(quick),
        "kernels": bench_kernels,
        "flat": bench_flat_assimilate,
        "flat_adam": bench_flat_adam,
        "sharded_flat": bench_sharded_flat,
        "compression": bench_compression,
        "fleet": lambda: bench_fleet(quick),
        "frontier": lambda: bench_frontier(quick),
        "handout": lambda: bench_handout(quick),
    }

    if args.check:
        raise SystemExit(check_vclint() or check_launches(benches))
    if args.update_baseline:
        update_baseline(benches)
        return

    print("name,us_per_call,derived")
    all_claims = {}
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        res = fn()
        dt_us = (time.perf_counter() - t0) * 1e6
        _out_path(name).write_text(json.dumps(res, indent=1, default=str))
        claims = res.pop("_claims", None) if isinstance(res, dict) else None
        if name in ("kernels", "flat", "flat_adam", "sharded_flat",
                    "compression"):
            for k, v in res.items():
                if k.startswith("_"):
                    continue
                print(f"{name}.{k},{v['us_per_call']},{v['derived']}")
        else:
            ok = (all(claims.values()) if claims else True)
            n_claims = len(claims) if claims else 0
            n_ok = sum(claims.values()) if claims else 0
            fails = ("" if ok else " FAILED:"
                     + str([k for k, v in claims.items() if not v]))
            print(f"{name},{dt_us:.0f},claims:{n_ok}/{n_claims}{fails}")
        if claims:
            all_claims[name] = claims
    if all_claims:
        # merge-on-write: a partial --only run must not drop the claims
        # recorded by suites it didn't run
        path = RESULTS / "BENCH_claims.json"
        merged = json.loads(path.read_text()) if path.exists() else {}
        merged.update(all_claims)
        path.write_text(json.dumps(merged, indent=1))


if __name__ == "__main__":
    main()
