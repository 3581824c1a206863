"""The benchmark's own tests: `python -m pytest benchmark/tests -q` on the
CPU. They take nothing from `tests/`."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY = os.path.join(HERE, "tiny")
#: the device line of a run that skips the harness's look for a chip
CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="session")
def tiny_manifest():
    with open(os.path.join(TINY, "manifest.json")) as f:
        return json.load(f)


@pytest.fixture
def run_tiny(tiny_manifest):
    """Drive the rest of a run at the tiny size: the harness's drivers,
    readers and comparison as `run.py` calls them, without the look for a
    chip (the peaks are the v5e's; no device number of such a run means
    anything)."""
    import time

    from benchmark import run

    def go(workload, seed=7, seconds=1.0):
        return run.run_cell(workload, seed, seconds, False,
                            device=dict(CPU_DEVICE),
                            t_start=time.perf_counter(), bench_dir=TINY,
                            manifest=tiny_manifest)
    return go
