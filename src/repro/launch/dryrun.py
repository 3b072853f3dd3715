import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/roofline inputs.

The lines above MUST stay first: jax locks the platform and the device count
at first backend initialization, and the dry-run needs 512 placeholder host
devices on the CPU backend so `jax.make_mesh` can build the (2,16,16)
production mesh.  These flags are set ONLY here (smoke tests and benchmarks
see 1 device), and the dry-run never takes an accelerator.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch codeqwen1.5-7b \
      --shape train_4k [--multipod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod]

Output: one JSON per cell under --out with
  memory_analysis   (bytes per device: args/outputs/temps/generated code)
  cost_analysis     (XLA's own numbers, for reference — undercounts loops)
  hlo_costs         (trip-count-aware flops / hbm bytes / collective bytes,
                     from repro.launch.hlo_analysis — feeds §Roofline)
  model_flops       (6*N(_active)*tokens for the cell)
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

import repro.configs as configs
from repro.launch import hlo_analysis, steps
from repro.launch.mesh import make_production_mesh
from repro.models.config import ALL_SHAPES, shape_applicable


def run_cell(cfg, shape, mesh, out_dir, tag, **knob_overrides):
    t0 = time.time()
    record = {
        "arch": cfg.name,
        "shape": shape.name,
        "tag": tag,
        "status": "ok",
    }
    try:
        lowered, meta = steps.lower_cell(cfg, shape, mesh, **knob_overrides)
        record.update(meta)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        record["memory_analysis"] = {
            "argument_size_in_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_in_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_in_bytes": getattr(
                mem, "generated_code_size_in_bytes", None
            ),
        }
        ca = compiled.cost_analysis() or {}
        record["cost_analysis"] = {
            k: float(v)
            for k, v in ca.items()
            if isinstance(v, (int, float)) and k in ("flops", "bytes accessed")
        }
        costs = hlo_analysis.analyze(compiled.as_text())
        record["hlo_costs"] = {
            "flops_per_chip": costs.flops,
            "dot_flops_per_chip": costs.dot_flops,
            "hbm_bytes_per_chip": costs.hbm_bytes,
            "collective_bytes_per_chip": costs.collective_bytes,
            "collective_breakdown": costs.collective_breakdown,
            "num_partitions": costs.num_partitions,
            "warnings": costs.warnings[:20],
        }
        # model flops for this cell (6*N_active*D tokens)
        from repro.models import transformer as T

        # model_flops_per_token = 6*N_active (fwd+bwd); inference is fwd-only
        # = 2*N_active; prefill processes seq_len tokens, decode exactly one.
        if shape.kind == "train":
            tokens, mult = shape.global_batch * shape.seq_len, 1.0
        elif shape.kind == "prefill":
            tokens, mult = shape.global_batch * shape.seq_len, 1.0 / 3.0
        else:
            tokens, mult = shape.global_batch * 1, 1.0 / 3.0
        fpt = T.model_flops_per_token(cfg)
        record["model_flops"] = fpt * tokens * mult
        record["timings"] = {"lower_s": t_lower, "compile_s": t_compile}
        print(
            f"[dryrun] {tag} {cfg.name} x {shape.name}: OK "
            f"(lower {t_lower:.1f}s compile {t_compile:.1f}s, "
            f"{costs.flops:.3g} flops/chip, "
            f"{costs.collective_bytes:.3g} coll B/chip)",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc(limit=20)
        print(f"[dryrun] {tag} {cfg.name} x {shape.name}: FAIL {e}", flush=True)

    os.makedirs(out_dir, exist_ok=True)
    fname = f"{cfg.name}_{shape.name}_{tag}.json".replace("/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=1, default=str)
    return record["status"] == "ok"


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--multipod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--out", default="results/dryrun")
    p.add_argument("--knob", action="append", default=[],
                   help="key=value CellKnobs override (e.g. microbatches=8)")
    args = p.parse_args()

    overrides = {}
    for kv in args.knob:
        k, v = kv.split("=", 1)
        overrides[k] = {"true": True, "false": False}.get(v.lower(), None)
        if overrides[k] is None:
            overrides[k] = int(v) if v.isdigit() else v

    meshes = []
    if args.both_meshes:
        meshes = [("pod1", make_production_mesh(multi_pod=False)),
                  ("pod2", make_production_mesh(multi_pod=True))]
    else:
        tag = "pod2" if args.multipod else "pod1"
        meshes = [(tag, make_production_mesh(multi_pod=args.multipod))]

    arch_names = configs.names() if (args.all or not args.arch) else [args.arch]
    shapes = (
        ALL_SHAPES
        if (args.all or not args.shape)
        else [s for s in ALL_SHAPES if s.name == args.shape]
    )

    ok = fail = skip = 0
    for name in arch_names:
        cfg = configs.get(name)
        for shape in shapes:
            applicable, reason = shape_applicable(cfg, shape)
            if not applicable:
                print(f"[dryrun] SKIP {cfg.name} x {shape.name}: {reason}", flush=True)
                rec = {
                    "arch": cfg.name, "shape": shape.name, "status": "skip",
                    "reason": reason,
                }
                os.makedirs(args.out, exist_ok=True)
                for tag, _ in meshes:
                    with open(
                        os.path.join(args.out, f"{cfg.name}_{shape.name}_{tag}.json"),
                        "w",
                    ) as f:
                        json.dump(dict(rec, tag=tag), f, indent=1)
                skip += 1
                continue
            for tag, mesh in meshes:
                if run_cell(cfg, shape, mesh, args.out, tag, **overrides):
                    ok += 1
                else:
                    fail += 1
    print(f"[dryrun] DONE ok={ok} fail={fail} skip={skip}", flush=True)
    raise SystemExit(1 if fail else 0)


if __name__ == "__main__":
    main()
