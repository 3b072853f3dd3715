"""Record a cell's trace for the tests of its per-layer readers.

Usage, from the root of a checkout on a machine with a TPU::

    python3 chipbench/record.py --workload q12_tumble.saturate --seed 7

The cell runs traced, with the Pallas kernels compiled, at the tiny size of
its window kind (``recording.tiny``) for ``--seconds``, after an untraced
run of the same seed has built its programs, and the traced window is
written to ``chipbench/fixtures/<cell>.json`` and ``<cell>.xplane.pb``
(``chipbench/recording.py``).  ``about`` in the JSON file keeps the
arguments, the seed and the commit.  The run exits non-zero and writes no
fixture where JAX finds no TPU, where the run is not correct against the
reference, where the window built a program, or where the pair would hold
more than 1 MB (a shorter ``--seconds`` makes it smaller).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs stay inside the checkout, not under /tmp
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "chipbench", ".out", "tpu_logs"))
STAGE = os.path.join(ROOT, "chipbench", ".out", "record")
#: the most a recording's two files may hold together, so that the
#: checkouts stay small
MAX_BYTES = 1_000_000


def _git_commit():
    try:
        return subprocess.run(["git", "rev-parse", "HEAD"], cwd=ROOT, check=True,
                              capture_output=True, text=True).stdout.strip()
    except (OSError, subprocess.CalledProcessError):
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.1,
                    help="length of the traced window (default 0.1)")
    ap.add_argument("--commit", default=None,
                    help="the commit of the checkout (default: git's HEAD; needed "
                         "where the checkout is no git repository)")
    args = ap.parse_args(argv)
    commit = args.commit or _git_commit()
    if commit is None:
        ap.error("the checkout is no git repository: give --commit")

    import jax

    from chipbench import harness, recording

    _, cell, config, _ = harness.load_cell(args.workload)
    problem = harness.chip_problem(cell["chips"])
    if problem:
        print(f"record: {problem}", file=sys.stderr)
        return 1
    kind = jax.devices()[0].device_kind

    def log(msg):
        print(f"[{kind}] {msg}", file=sys.stderr, flush=True)

    harness.enable_compile_cache()
    overrides = recording.tiny(config["window"]["kind"])
    about = {
        "what": f"a traced window of {args.workload} at the tiny size of its "
                f"window kind, recorded by chipbench/record.py",
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "args": sys.argv[1:] if argv is None else list(argv),
        "overrides": overrides, "commit": commit, "device": kind,
    }
    shutil.rmtree(STAGE, ignore_errors=True)
    os.makedirs(STAGE)

    built = []

    def keep(win, anchor, xplane):
        built.append(win.compiles)
        recording.save(args.workload, win, anchor, xplane, about, STAGE)

    # At the tiny size every warm-up chunk builds programs, so the run's
    # rehearsal, sized from the warm-up's chunk time, would fall short and
    # the window would build the rest.  A first, untraced run of the same
    # seed builds them; the recorded run's warm-up then reads steady chunks.
    harness.run_cell(args.workload, args.seed, args.seconds, False,
                     t_start=time.perf_counter(), log=log, overrides=overrides)
    r = harness.run_cell(args.workload, args.seed, args.seconds, True, t_start=T_START,
                         log=log, overrides=overrides, on_trace=keep)
    staged = recording.paths(args.workload, STAGE)
    size = sum(os.path.getsize(p) for p in staged)
    summary = {"workload": args.workload, "seed": args.seed, "correct": r["correct"],
               "compiles": built[0], "bytes": size, "metrics": r["metrics"],
               "check": r["check"]}
    print(json.dumps(summary), flush=True)
    if not r["correct"] or built[0] or size > MAX_BYTES:
        log(f"not recorded: correct {r['correct']}, {built[0]} programs built in "
            f"the window, {size} bytes (at most {MAX_BYTES})")
        return 1
    os.makedirs(recording.FIXTURES_DIR, exist_ok=True)
    for src, dst in zip(staged, recording.paths(args.workload)):
        shutil.move(src, dst)
    log(f"recorded {args.workload}: {size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
