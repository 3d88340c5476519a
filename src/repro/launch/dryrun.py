import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
the production mesh of placeholder devices, print memory/cost analysis, and
derive the roofline terms.

The XLA_FLAGS line above is FIRST — before any other import — because jax
locks the device count on first init. Do not set it globally: smoke tests
and benches must see one device.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape decode_32k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
  python -m repro.launch.dryrun --arch kimi-k2-1t-a32b --shape train_4k \
      --causal-skip --tag opt1
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import (ALL_ARCHS, TrainConfig, get_config, get_shape,
                           runnable_cells, SHAPES, StepKind)
from repro.dist import steps as steps_mod
from repro.launch import hlo, jaxpr_analysis, roofline
from repro.launch.mesh import PRODUCTION_KIND, make_production_mesh, peaks
from repro.launch.specs import input_specs


def build_step(cfg, shape, mesh, *, causal_skip=False, zero1=True,
               grad_compression="none", attn_chunk=1024, attn_p_bf16=False,
               microbatches=1, opt_int8=False, exact_retrieval=False,
               pure_dp=False, a2a_int8=False, datastore_scale=1.0,
               attn_impl="xla"):
    """Returns (jitted step, ShapeDtypeStruct args) for this cell."""
    import dataclasses
    if exact_retrieval and cfg.retrieval.enabled:
        cfg = dataclasses.replace(cfg, retrieval=dataclasses.replace(
            cfg.retrieval, local_k=cfg.retrieval.k))
    if datastore_scale != 1.0 and cfg.retrieval.enabled:
        cfg = dataclasses.replace(cfg, retrieval=dataclasses.replace(
            cfg.retrieval,
            datastore_size=int(cfg.retrieval.datastore_size * datastore_scale)))
    tc = TrainConfig(zero1=zero1, grad_compression=grad_compression,
                     microbatches=microbatches, opt_int8=opt_int8)
    args = input_specs(cfg, shape, tc)
    with mesh:
        if shape.step == StepKind.TRAIN:
            step_fn, _, _ = steps_mod.make_train_step(
                cfg, mesh, tc, causal_skip=causal_skip,
                attn_p_bf16=attn_p_bf16, pure_dp=pure_dp,
                moe_a2a_int8=a2a_int8, donate=False)
        elif shape.step == StepKind.PREFILL:
            step_fn, _ = steps_mod.make_prefill_step(
                cfg, mesh, shape.seq_len, causal_skip=causal_skip,
                attn_p_bf16=attn_p_bf16, attn_chunk=attn_chunk,
                attn_impl=attn_impl)
        else:
            step_fn, _, _ = steps_mod.make_serve_step(
                cfg, mesh, shape.seq_len, global_batch=shape.global_batch)
    return step_fn, args


def lower_cell(cfg, shape, mesh, **kw):
    step_fn, args = build_step(cfg, shape, mesh, **kw)
    with mesh:
        return step_fn.lower(*args)


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             causal_skip: bool = False, zero1: bool = True,
             grad_compression: str = "none", attn_chunk: int = 1024,
             attn_p_bf16: bool = False, microbatches: int = 1,
             opt_int8: bool = False, exact_retrieval: bool = False,
             pure_dp: bool = False, a2a_int8: bool = False,
             datastore_scale: float = 1.0, attn_impl: str = "xla",
             mesh=None, hlo_path: str = "") -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    chips = len(mesh.devices.reshape(-1))
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)

    t0 = time.time()
    step_fn, step_args = build_step(
        cfg, shape, mesh, causal_skip=causal_skip, zero1=zero1,
        grad_compression=grad_compression, attn_chunk=attn_chunk,
        attn_p_bf16=attn_p_bf16, microbatches=microbatches,
        opt_int8=opt_int8, exact_retrieval=exact_retrieval,
        pure_dp=pure_dp, a2a_int8=a2a_int8, datastore_scale=datastore_scale,
        attn_impl=attn_impl)
    with mesh:
        lowered = step_fn.lower(*step_args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    print(mem)                                    # proves it fits
    cost = compiled.cost_analysis() or {}
    print({k: cost[k] for k in ("flops", "bytes accessed") if k in cost})
    mem_stats = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
    }
    # per-device residency: args are sharded; temp is per-device already
    mem_stats["per_device_bytes"] = (
        (mem_stats["argument_bytes"] - mem_stats["alias_bytes"]) / chips
        + mem_stats["temp_bytes"])
    mem_stats["fits_hbm"] = (mem_stats["per_device_bytes"]
                             < peaks(PRODUCTION_KIND)["hbm_bytes"])

    hlo_text = compiled.as_text()
    if hlo_path:
        import gzip
        with gzip.open(hlo_path, "wt") as f:
            f.write(hlo_text)
    # collectives + residency from the compiled HLO; flops + HBM traffic from
    # the jaxpr (dtype-faithful — the CPU backend computes bf16 in f32)
    stats = hlo.analyze(hlo_text)
    with mesh:
        jstats = jaxpr_analysis.analyze_step(step_fn, step_args, chips)
    stats["hlo_flops"] = stats["flops"]
    stats["hlo_io_bytes"] = stats["io_bytes"]
    stats["flops"] = jstats["flops"]
    stats["io_bytes"] = jstats["io_bytes"]
    report = roofline.build_report(
        cfg, shape, mesh_name, chips, stats, memory_stats=mem_stats,
        cost_flops=float(cost.get("flops", 0.0)))
    rec = report.as_dict()
    rec.update(lower_s=t_lower, compile_s=t_compile,
               causal_skip=causal_skip, zero1=zero1,
               grad_compression=grad_compression, attn_chunk=attn_chunk,
               attn_p_bf16=attn_p_bf16, microbatches=microbatches,
               opt_int8=opt_int8, exact_retrieval=exact_retrieval,
               pure_dp=pure_dp, a2a_int8=a2a_int8,
               datastore_scale=datastore_scale, attn_impl=attn_impl,
               multi_pod=multi_pod)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--attn-p-bf16", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--opt-int8", action="store_true")
    ap.add_argument("--exact-retrieval", action="store_true")
    ap.add_argument("--pure-dp", action="store_true")
    ap.add_argument("--a2a-int8", action="store_true")
    ap.add_argument("--datastore-scale", type=float, default=1.0)
    ap.add_argument("--attn-impl", default="xla", choices=["xla", "flash"])
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--grad-compression", default="none")
    ap.add_argument("--attn-chunk", type=int, default=1024)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_tag = "2x16x16" if args.multi_pod else "16x16"

    if args.all:
        cells, skipped = runnable_cells([get_config(a) for a in ALL_ARCHS])
        for a, s, why in skipped:
            print(f"SKIP {a} x {s}: {why}")
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape_name in cells:
        tag = f"{arch}__{shape_name}__{mesh_tag}" + (f"__{args.tag}" if args.tag else "")
        path = os.path.join(args.out, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            print(f"== {tag}: exists, skipping")
            continue
        print(f"== {tag}")
        try:
            rec = run_cell(arch, shape_name, multi_pod=args.multi_pod,
                           causal_skip=args.causal_skip,
                           zero1=not args.no_zero1,
                           grad_compression=args.grad_compression,
                           attn_chunk=args.attn_chunk,
                           attn_p_bf16=args.attn_p_bf16,
                           microbatches=args.microbatches,
                           opt_int8=args.opt_int8,
                           exact_retrieval=args.exact_retrieval,
                           pure_dp=args.pure_dp, a2a_int8=args.a2a_int8,
                           datastore_scale=args.datastore_scale,
                           attn_impl=args.attn_impl, mesh=mesh,
                           hlo_path=os.path.join(args.out, tag + ".hlo.gz"))
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"   dominant={rec['dominant']} bound={rec['step_time_bound_s']:.4f}s "
                  f"roofline_frac={rec['roofline_frac']:.3f} "
                  f"per_dev={rec['memory_stats']['per_device_bytes']/1e9:.2f}GB "
                  f"compile={rec['compile_s']:.1f}s")
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
            failures.append((tag, repr(e)))
            traceback.print_exc()
            with open(path + ".failed", "w") as f:
                f.write(traceback.format_exc())
    if failures:
        print(f"{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
