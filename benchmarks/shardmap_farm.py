"""Paper §5 methodology on real JAX: the accumulator farm under `shard_map`
with 16 placeholder host devices, run in a SUBPROCESS so the device-count flag
never leaks into this process.

On a 1-core container wall-clock scaling is not meaningful; what this
benchmark establishes is (a) the pattern executes end-to-end under SPMD with
the exact collective schedule the flush period prescribes (all-reduce sites /
dynamic flush counts from the compiled HLO) and (b) per-step overhead.
"""

from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import Row

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run() -> list[Row]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "benchmarks", "_shardmap_farm_child.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"shardmap_farm child exited {proc.returncode}: "
            f"{proc.stderr.strip()[-2000:]}"
        )
    rows = []
    for line in proc.stdout.strip().splitlines():
        parts = line.split(",", 2)
        if len(parts) == 3:
            rows.append(Row(parts[0], float(parts[1]), parts[2]))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    emit(run())
