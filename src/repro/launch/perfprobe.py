import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")   # a CPU rehearsal

# perf-iteration probe: lower+compile one (arch x shape x layout) cell and
# report MEASURED quantities — trip-count-scaled collective bytes from the
# partitioned HLO, memory_analysis temp/argument sizes — alongside the
# analytic roofline.  Used by the §Perf hillclimb loop.
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402

from repro.analysis.analytic import analytic_roofline                  # noqa: E402
from repro.analysis.hlo import collective_bytes_scaled                 # noqa: E402
from repro.configs import get_config                                   # noqa: E402
from repro.launch.mesh import INPUT_SHAPES, make_production_mesh       # noqa: E402
from repro.launch.steps import effective_config, lower_step            # noqa: E402


def probe(arch: str, shape: str, layout: str, *, multi_pod: bool = False,
          microbatches: int = 1, save: str = None) -> dict:
    seq, batch, kind = INPUT_SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    t0 = time.time()
    kw = {"microbatches": microbatches} if kind == "train" else {}
    lowered = lower_step(kind, cfg, mesh, layout, batch, seq,
                         shape_name=shape, **kw)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    coll = collective_bytes_scaled(hlo)
    mem = compiled.memory_analysis()
    eff = effective_config(cfg, shape)
    rec = {
        "arch": arch, "shape": shape, "layout": layout,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "microbatches": microbatches,
        "compile_s": round(time.time() - t0, 1),
        "measured_collective_bytes_per_chip": coll["total"],
        "measured_collective_s": coll["total"] / 50e9,
        "collectives": {k: v for k, v in coll.items() if k != "_counts"},
        "collective_counts": coll["_counts"],
        "temp_gb": getattr(mem, "temp_size_in_bytes", 0) / 1e9,
        "arg_gb": getattr(mem, "argument_size_in_bytes", 0) / 1e9,
        "analytic": analytic_roofline(eff, batch, seq, kind, mesh, layout),
    }
    print(f"[{arch} x {shape} x {layout}"
          f"{' x mb' + str(microbatches) if microbatches > 1 else ''}] "
          f"compile={rec['compile_s']}s")
    print(f"  measured collectives/chip: {coll['total'] / 1e9:.2f} GB "
          f"(={rec['measured_collective_s'] * 1e3:.0f} ms @50GB/s) "
          f"{ {k: round(v / 1e9, 2) for k, v in coll.items() if isinstance(v, int) and k != 'total'} }")
    print(f"  temp={rec['temp_gb']:.1f} GB  args={rec['arg_gb']:.2f} GB  "
          f"analytic compute={rec['analytic']['compute_s'] * 1e3:.0f}ms")
    if save:
        os.makedirs(os.path.dirname(save) or ".", exist_ok=True)
        with open(save, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    # thin shim over the repro.api registry (RunSpec in, RunReport out)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--layout", default="fsdp_tp",
                    choices=["dp", "fsdp_tp", "fsdp_sp"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--save", default=None)
    args = ap.parse_args()

    from repro.api import RunSpec, run
    overrides = {"shape": args.shape, "layout": args.layout,
                 "multi_pod": args.multi_pod,
                 "microbatches": args.microbatches}
    if args.save:
        overrides["save"] = args.save
    report = run(RunSpec(kind="perfprobe", arch=args.arch,
                         overrides=overrides))
    if not report.ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
