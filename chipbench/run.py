"""Run one cell of the keyed state plane's benchmark on one TPU chip.

Usage, from the root of a checkout on a machine with a TPU::

    python3 chipbench/run.py --workload q12_tumble.saturate --seed 7 \\
        --seconds 20 --trace 0

Every line on standard error is labelled with the device kind and count.
The last lines on standard error are the numbers that decide ``correct``,
each with its limit; the last line on standard output is the result, one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``) and, last, ``check``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, measured
with tracing off; with ``--trace 1`` they are its per-layer metrics, read
from the program's spans and a device trace of the window.  The run exits
non-zero, and prints no result, when JAX finds no TPU or fewer chips than
the cell asks for, or when the Pallas kernels would not run compiled.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout's root (for ``chipbench``) and its ``src`` (for the program);
# the script's own directory goes, so no module here shadows another
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs stay inside the checkout, not under /tmp
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "chipbench", ".out", "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from chipbench import harness

    _, cell, _, _ = harness.load_cell(args.workload)
    problem = harness.chip_problem(cell["chips"])
    if problem:
        print(f"chipbench: {problem}", file=sys.stderr)
        return 1
    devices = jax.devices()
    label = f"[{devices[0].device_kind} x{len(devices)}]"

    def log(msg):
        print(f"{label} {msg}", file=sys.stderr, flush=True)

    log(f"compile cache: {harness.enable_compile_cache()}")
    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, log=log,
    )
    for name, c in result["check"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
