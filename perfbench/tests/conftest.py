"""The benchmark's own tests, run by path on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q

They use the small configurations under ``tests/data``."""
import os
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import bench  # noqa: E402

DATA = HERE / "data"
if DATA not in bench.SEARCH:
    bench.SEARCH.insert(0, DATA)


@pytest.fixture
def data_root():
    return DATA

# tests leave JAX's persistent compilation cache off
bench.configure_jax = lambda: None


def run_cell(workload: str, seed: int = 7, seconds: float = 0.5,
             hooks=None) -> dict:
    import run
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])
    return run.run(args, require_tpu=False, root=DATA, hooks=hooks)
