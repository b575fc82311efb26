"""Shared set-up for the benchmark's own tests (run by hand, on the CPU):

    python -m pytest chipbench/tests -q

They drive the harness at tiny sizes with the chip check skipped.
"""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# tiny stand-ins for each cell's sizes: (file under chipbench/, changes)
TINY = {
    "traffic/long_lists.json": {"groups": [8, 9], "lists_per_group": 3},
    "traffic/short_lists.json": {"groups": [7], "lists_per_group": 5},
}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like root: BENCHMARK.json, chipbench/ at tiny sizes, and
    the program's src/ (linked)."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    for rel, change in TINY.items():
        path = tmp_path / "chipbench" / rel
        data = json.loads(path.read_text())
        data.update(change)
        path.write_text(json.dumps(data))
    return tmp_path


@pytest.fixture
def cpu():
    import jax

    return jax.devices("cpu")[:1]
