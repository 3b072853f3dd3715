"""``chip_smoke.py`` on the CPU: its run at a tiny size with the Pallas
kernels in interpret mode is bit-exact against the oracle, and its entry
point refuses to run without a TPU."""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

TINY = dict(
    n_w=8, resize_to=5, num_slots=64, capacity=64, max_probes=16,
    size=64, slide=16, lateness=4, num_keys=300, disorder=8,
    chunk=128, num_chunks=12, seed=3,
    min_standing=64, min_table_rows=8 * 64,
)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_run_is_bit_exact_in_interpret_mode():
    smoke = _load_smoke()
    lines = []
    ops.use_kernels("interpret")
    try:
        summary = smoke.run(TINY, log=lines.append)
    finally:
        ops.use_kernels("auto")
    assert any(line.startswith("oracle check: bit-exact") for line in lines)
    assert sum(line.startswith("resize ") for line in lines) == 2
    assert summary["emissions"] > 0 and summary["late"] > 0
    assert summary["spill"] > 0  # the tiny table overflows into the host tier
    assert summary["table_rows"] == 8 * 64


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_entry_point_fails_without_tpu(tmp_path, where):
    """No TPU: non-zero exit and no result line, both from the repository
    and from a directory holding the script and nothing else."""
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(script), timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
