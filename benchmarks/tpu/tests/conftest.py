"""Fixtures of the benchmark's own tests: a copy of the benchmark with tiny
cells beside the real ones, run on the CPU."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]          # benchmarks/tpu
REPO = HERE.parents[1]
# appended, so that the benchmark's ``trace.py`` never hides the standard
# library's module of that name from other tests
for p in (str(HERE), str(REPO / "src")):
    if p not in sys.path:
        sys.path.append(p)

from bench_fixtures import (  # noqa: E402
    TINY_CELLS, TINY_METRICS, TINY_QWEN, TINY_TRAFFIC)


@pytest.fixture
def tiny_bench(tmp_path):
    """``(here, root)`` of a copy of the benchmark whose BENCHMARK.json
    also lists the tiny cells; their files and mixes are added, none is
    edited."""
    root = tmp_path / "checkout"
    here = root / "benchmarks" / "tpu"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (here / "configs" / "qwen3_tiny.json").write_text(json.dumps(TINY_QWEN))
    shutil.copy(here / "configs" / "qwen3_14b_d10.py",
                here / "configs" / "qwen3_tiny.py")
    bench["configs"].append({"name": "qwen3_tiny", "source": "test",
                             "file": "benchmarks/tpu/configs/qwen3_tiny.json",
                             "reduced": [], "why": "test"})
    for name, mix in TINY_TRAFFIC.items():
        (here / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for name, cell in TINY_CELLS.items():
        (here / "cells" / f"{name}.json").write_text(json.dumps(cell))
        bench["workloads"].append({"name": name, "config": cell["config"],
                                   "traffic": cell["traffic"], "chips": 1,
                                   "why": "test"})
    for section, entries in TINY_METRICS.items():
        have = {m["name"]: m for m in bench[section]}
        for m in entries:
            if m["name"] in have:
                have[m["name"]]["workloads"] += m["workloads"]
            else:
                bench[section].append(m)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return here, root


@pytest.fixture
def cpu():
    import jax

    return jax.devices("cpu")[:1]
