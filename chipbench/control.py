"""The control of the correctness check, on the chip.

The configurations state that a late bid is delivered on the side output.
The program has a path that breaks that guarantee, ``late_policy="drop"``;
with it switched on, the check must come out not correct.  This script runs
a cell with that path on each seed, in one process, and prints each run's
compared numbers as one JSON line::

    python3 chipbench/control.py --workload q12_tumble.saturate \\
        --seconds 5 --seeds 1 2 3

It needs a TPU, as ``run.py`` does.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs stay inside the checkout, not under /tmp
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "chipbench", ".out", "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench import harness

    _, cell, _, _ = harness.load_cell(args.workload)
    problem = harness.chip_problem(cell["chips"])
    if problem:
        print(f"control: {problem}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    harness.enable_compile_cache()
    for seed in args.seeds:
        r = harness.run_cell(
            args.workload, seed, args.seconds, False, t_start=time.perf_counter(),
            late_policy="drop",
            log=lambda m: print(f"[{dev.device_kind}] {m}", file=sys.stderr),
        )
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": r["correct"], "check": r["check"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
