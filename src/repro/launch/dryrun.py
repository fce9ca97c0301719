"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and extract memory / cost / collective analysis.

A CPU-only tool: it compiles for 512 virtual CPU devices, so it checks
shardings and collectives but says nothing about Mosaic or HBM fit (for
those, tests/test_tpu_compile.py compiles for a described TPU).  It pins
``JAX_PLATFORMS=cpu`` and the device-count flag before ANY other import
(jax locks the device count on first init).
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, get_config                    # noqa: E402
from repro.configs.shapes import (SHAPES, cell_applicable,     # noqa: E402
                                  input_specs, tune_for_shape)
from repro.launch.mesh import make_production_mesh             # noqa: E402
from repro.models.registry import build_model                  # noqa: E402
from repro.optim import Adam                                   # noqa: E402
from repro.runtime import hlo_analysis as H                    # noqa: E402
from repro.runtime.sharding import MeshPlan                    # noqa: E402
from repro.runtime.train import (make_decode_step,             # noqa: E402
                                 make_prefill_step, make_train_step,
                                 microbatch_specs, shardings_for_decode,
                                 shardings_for_prefill, shardings_for_train)

TRAIN_ACCUM = {  # microbatch count per arch for train_4k (memory knob)
    "default": 2, "qwen2.5-14b": 4, "mixtral-8x7b": 4, "jamba-v0.1-52b": 4,
}

RESULTS = Path(__file__).resolve().parents[3] / "results"
RESULTS.mkdir(exist_ok=True)


def lower_cell(arch: str, cell_name: str, multi_pod: bool,
               attn_mode_override=None, extra_tag: str = "",
               moe_ep: bool = False, accum_override=None,
               zero_dp: bool = False, remat="full"):
    """Lower + compile one (arch, shape, mesh) cell. Returns a result dict."""
    cfg = tune_for_shape(get_config(arch), SHAPES[cell_name])
    cell = SHAPES[cell_name]
    skip = cell_applicable(cfg, cell)
    if skip:
        return {"arch": arch, "cell": cell_name, "multi_pod": multi_pod,
                "status": "skip", "reason": skip}
    if cell.kind in ("prefill", "decode"):
        # serving: bf16 weights, replicated over data / TP over model —
        # FSDP-sharded serve params would all-gather weights every step
        cfg = cfg.replace(param_dtype="bfloat16")

    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axis = ("pod", "data") if multi_pod else "data"
    moe_ep = moe_ep and cfg.moe is not None and cell.kind != "decode"
    if moe_ep:
        from repro.runtime.sharding import ep_tune
        dp = int(np.prod([mesh.shape[a] for a in
                          (data_axis if isinstance(data_axis, tuple)
                           else (data_axis,))]))
        cfg = ep_tune(cfg, dp)
    plan = MeshPlan.build(
        cfg, mesh, data_axis=data_axis, attn_mode=attn_mode_override,
        decode_batch=cell.global_batch if cell.kind == "decode" else None,
        moe_ep=moe_ep, zero_dp=zero_dp)
    if cell.kind in ("prefill", "decode"):
        plan.fsdp = False
    model = build_model(cfg)
    optimizer = Adam(lr=3e-4)
    t0 = time.time()

    with mesh:
        if cell.kind == "train":
            accum = accum_override or TRAIN_ACCUM.get(arch,
                                                      TRAIN_ACCUM["default"])
            batch = input_specs(cfg, cell, plan)
            if accum > 1:
                batch = microbatch_specs(batch, accum)
            remat_mode = True if remat == "full" else remat
            step = make_train_step(model, plan, optimizer, accum=accum,
                                   remat=remat_mode)
            p_specs = model.param_specs()
            o_specs = jax.eval_shape(optimizer.init, p_specs)
            ins, outs = shardings_for_train(model, plan, optimizer, batch,
                                            accum=accum)
            lowered = jax.jit(step, in_shardings=ins, out_shardings=outs,
                              donate_argnums=(0, 1)  # params/opt update in place
                              ).lower(p_specs, o_specs, batch)
        elif cell.kind == "prefill":
            batch = input_specs(cfg, cell, plan)
            step = make_prefill_step(model, plan)
            p_specs = model.param_specs()
            cache_specs = jax.eval_shape(
                lambda p, b: model.prefill(p, b, plan=plan)[1], p_specs, batch)
            ins, outs = shardings_for_prefill(model, plan, batch, cache_specs)
            lowered = jax.jit(step, in_shardings=ins,
                              out_shardings=outs).lower(p_specs, batch)
        else:  # decode
            specs = input_specs(cfg, cell, plan)
            step = make_decode_step(model, plan)
            p_specs = model.param_specs()
            ins, outs = shardings_for_decode(model, plan, specs["caches"],
                                             cell.global_batch)
            lowered = jax.jit(step, in_shardings=ins, out_shardings=outs,
                              donate_argnums=(1,)    # cache updated in place
                              ).lower(p_specs, specs["caches"],
                                      specs["token"], specs["pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    hc = H.analyze_hlo_text(txt)
    n_dev = int(np.prod(list(mesh.shape.values())))

    res = {
        "arch": arch, "cell": cell_name, "multi_pod": multi_pod,
        "attn_mode": plan.attn_mode, "cache_mode": plan.cache_mode,
        "status": "ok", "tag": extra_tag,
        "n_devices": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device": (mem.argument_size_in_bytes
                                + mem.temp_size_in_bytes),
        },
        "xla_cost": {"flops": ca.get("flops", 0.0),
                     "bytes_accessed": ca.get("bytes accessed", 0.0),
                     "transcendentals": ca.get("transcendentals", 0.0)},
        "hlo": {
            "dot_flops": hc.dot_flops,
            "hbm_bytes": hc.hbm_bytes,
            "collective_bytes": dict(hc.collective_bytes),
            "collective_count": dict(hc.collective_count),
            "total_collective_bytes": hc.total_collective_bytes,
            "while_trips": hc.while_trips,
        },
    }
    rt = H.roofline_terms(hc.dot_flops, hc.hbm_bytes, hc.total_collective_bytes)
    res["roofline"] = {
        "compute_s": rt.compute_s, "memory_s": rt.memory_s,
        "collective_s": rt.collective_s, "dominant": rt.dominant,
        "bound_s": rt.bound_s,
    }
    return res


def lower_vc_round(arch: str, multi_pod: bool = True, local_steps: int = 4):
    """Lower the paper-technique VC round (island local steps + Eq.2
    assimilation + redistribution) on the multi-pod mesh."""
    from repro.runtime.vc_runtime import (island_shardings, make_vc_round)
    cfg = tune_for_shape(get_config(arch), SHAPES["train_4k"])
    cell = SHAPES["train_4k"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_pods = mesh.shape.get("pod", 1)
    plan = MeshPlan.build(cfg, mesh, data_axis="data")
    model = build_model(cfg)
    optimizer = Adam(lr=3e-4)
    vc_round = make_vc_round(model, plan, n_pods, local_steps, optimizer)

    per_island_batch = cell.global_batch // n_pods
    batch1 = input_specs(cfg, cell, plan)
    batches = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            (n_pods, local_steps, per_island_batch, *s.shape[1:]), s.dtype),
        batch1)

    p_specs = model.param_specs()
    islands = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_pods, *s.shape), s.dtype), p_specs)
    opts = jax.eval_shape(lambda p: jax.vmap(optimizer.init)(p), islands)
    server_sh, island_sh, opt_sh = island_shardings(model, plan, n_pods,
                                                    optimizer)
    b_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, P("pod", None, "data",
                                        *([None] * (len(s.shape) - 3)))),
        batches)
    rep = NamedSharding(mesh, P())
    surv_sh = rep

    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            vc_round,
            in_shardings=(server_sh, island_sh, opt_sh, b_sh, rep, surv_sh),
            out_shardings=(server_sh, island_sh, opt_sh, {"loss": rep}),
        ).lower(p_specs, islands, opts, batches,
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((n_pods,), jnp.bool_))
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hc = H.analyze_hlo_text(compiled.as_text())
    return {
        "arch": arch, "cell": f"vc_round_x{local_steps}",
        "multi_pod": multi_pod, "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "mem": {"peak_per_device": mem.argument_size_in_bytes
                + mem.temp_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "argument_bytes": mem.argument_size_in_bytes},
        "hlo": {"dot_flops": hc.dot_flops, "hbm_bytes": hc.hbm_bytes,
                "total_collective_bytes": hc.total_collective_bytes,
                "collective_bytes": dict(hc.collective_bytes)},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--cell", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(RESULTS / "dryrun.json"))
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--vc-round", action="store_true",
                    help="also lower the VC-ASGD island round per arch")
    ap.add_argument("--attn-mode", default=None,
                    help="override planner attention mode (perf experiments)")
    ap.add_argument("--moe-ep", action="store_true",
                    help="expert-parallel MoE dispatch (perf experiments)")
    ap.add_argument("--zero-dp", action="store_true",
                    help="pure-DP ZeRO plan: model axis folded into data")
    ap.add_argument("--remat", default="full", choices=["full", "dots"])
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = ARCHS if args.arch == "all" else [args.arch]
    cells = list(SHAPES) if args.cell == "all" else [args.cell]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    out_path = Path(args.out)
    results = {}
    if args.resume and out_path.exists():
        results = json.loads(out_path.read_text())

    for arch in archs:
        for cell in cells:
            for mp in meshes:
                key = f"{arch}|{cell}|{'multi' if mp else 'single'}" + \
                    (f"|{args.tag}" if args.tag else "")
                if args.resume and key in results and \
                        results[key].get("status") in ("ok", "skip"):
                    continue
                t0 = time.time()
                try:
                    res = lower_cell(arch, cell, mp,
                                     attn_mode_override=args.attn_mode,
                                     extra_tag=args.tag, moe_ep=args.moe_ep,
                                     accum_override=args.accum,
                                     zero_dp=args.zero_dp, remat=args.remat)
                except Exception as e:  # noqa: BLE001
                    res = {"arch": arch, "cell": cell, "multi_pod": mp,
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                res["wall_s"] = round(time.time() - t0, 1)
                results[key] = res
                out_path.write_text(json.dumps(results, indent=1))
                status = res["status"]
                extra = (res.get("reason") or res.get("error", ""))[:90]
                mem = res.get("mem", {}).get("peak_per_device", 0) / 2 ** 30
                dom = res.get("roofline", {}).get("dominant", "-")
                print(f"[{status:5s}] {key:45s} {res['wall_s']:7.1f}s "
                      f"peak={mem:6.2f}GiB dom={dom} {extra}", flush=True)
        if args.vc_round:
            key = f"{arch}|vc_round|multi"
            if not (args.resume and key in results
                    and results[key].get("status") == "ok"):
                try:
                    res = lower_vc_round(arch)
                except Exception as e:  # noqa: BLE001
                    res = {"arch": arch, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                results[key] = res
                out_path.write_text(json.dumps(results, indent=1))
                print(f"[{res['status']:5s}] {key}", flush=True)

    ok = sum(1 for r in results.values() if r["status"] == "ok")
    skip = sum(1 for r in results.values() if r["status"] == "skip")
    err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"\ndry-run complete: {ok} ok / {skip} skip / {err} error "
          f"-> {out_path}")
    return 0 if err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
