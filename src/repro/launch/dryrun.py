import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")   # a CPU rehearsal

# --- everything below may import jax -------------------------------------
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.analysis.analytic import analytic_roofline         # noqa: E402
from repro.analysis.roofline import roofline_terms            # noqa: E402
from repro.configs import get_config, list_archs              # noqa: E402
from repro.launch.mesh import INPUT_SHAPES, make_production_mesh  # noqa: E402
from repro.launch.steps import lower_step, effective_config   # noqa: E402

# (arch, shape) pairs that are structurally skipped (encoder-only has no
# autoregressive decode) — recorded, not silently dropped.
STRUCTURAL_SKIPS = {
    ("hubert-xlarge", "decode_32k"): "encoder-only: no decode step",
    ("hubert-xlarge", "long_500k"): "encoder-only: no decode step",
}


def run_one(arch: str, shape_name: str, multi_pod: bool, layout: str,
            out_dir: str, microbatches: int = 1) -> dict:
    seq, batch, kind = INPUT_SHAPES[shape_name]
    rec = {
        "arch": arch, "shape": shape_name, "kind": kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "layout": layout, "seq": seq, "batch": batch,
    }
    if (arch, shape_name) in STRUCTURAL_SKIPS:
        rec["status"] = "skipped"
        rec["reason"] = STRUCTURAL_SKIPS[(arch, shape_name)]
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            tag = f"{arch}_{shape_name}_{rec['mesh']}_{layout}"
            with open(os.path.join(out_dir, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    cfg = get_config(arch)
    t0 = time.time()
    try:
        kw = {"microbatches": microbatches} if kind == "train" else {}
        lowered = lower_step(kind, cfg, mesh, layout, batch, seq,
                             shape_name=shape_name, **kw)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        if mem is not None:
            rec["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes")
                if hasattr(mem, k)
            }
            print(f"  memory_analysis: {rec['memory_analysis']}")
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        hlo = compiled.as_text()
        eff_cfg = effective_config(cfg, shape_name)
        # primary roofline: analytic model (XLA cost_analysis counts scan
        # bodies once — see analysis/analytic.py docstring)
        rec["roofline"] = analytic_roofline(
            eff_cfg, batch, seq, kind, mesh, layout)
        # structural cross-check from the partitioned HLO
        rec["hlo_roofline"] = roofline_terms(
            cost, hlo, n_chips, cfg=eff_cfg, batch=batch, seq=seq, kind=kind)
        rec["cost_analysis"] = {
            k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "bytes accessed", "optimal_seconds")
                or k.startswith("bytes accessed"))
        }
        rec["status"] = "ok"
        r = rec["roofline"]
        print(f"  analytic: compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"dominant={r['dominant']} "
              f"mfu_ub={r['mfu_upper_bound']:.2f}")
    except Exception as e:  # noqa: BLE001 — a dry-run failure is a bug report
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"  ERROR {type(e).__name__}: {str(e)[:400]}")
    rec["total_s"] = round(time.time() - t0, 1)

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{rec['mesh']}_{layout}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def dryrun_sweep(archs="all", shapes="all", meshes="single",
                 layout="fsdp_tp", microbatches: int = 1,
                 out: str = "experiments/dryrun") -> list:
    """The full (arch x shape x mesh) sweep; the body behind both the
    ``repro.api`` dryrun runner and this module's CLI shim."""
    arch_list = list_archs() if archs == "all" else archs.split(",")
    shape_list = list(INPUT_SHAPES) if shapes == "all" else [shapes]
    mesh_list = {"single": [False], "multi": [True],
                 "both": [False, True]}[meshes]

    results = []
    for arch in arch_list:
        for shape in shape_list:
            for mp in mesh_list:
                mesh_tag = "2x16x16" if mp else "16x16"
                print(f"[dryrun] {arch} x {shape} x {mesh_tag} x {layout}",
                      flush=True)
                rec = run_one(arch, shape, mp, layout, out, microbatches)
                results.append(rec)
                print(f"  -> {rec['status']} ({rec.get('total_s', 0)}s)",
                      flush=True)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = len(results) - n_ok - n_skip
    print(f"\n[dryrun] ok={n_ok} skipped={n_skip} error={n_err}")
    for r in results:
        if r["status"] == "error":
            print(f"  FAILED: {r['arch']} x {r['shape']} x {r['mesh']}: "
                  f"{r['error'][:200]}")
    return results


def main():
    # thin shim over the repro.api registry (RunSpec in, RunReport out)
    ap = argparse.ArgumentParser(description="multi-pod lowering dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all",
                    choices=["all", *INPUT_SHAPES])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--layout", default="fsdp_tp", choices=["fsdp_tp", "fsdp_sp", "dp"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    from repro.api import RunSpec, run
    report = run(RunSpec(kind="dryrun", arch=args.arch, overrides={
        "shape": args.shape, "mesh": args.mesh, "layout": args.layout,
        "microbatches": args.microbatches, "out": args.out}))
    if not report.ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
