"""Every entry of the real ``BENCHMARK.json`` is whole: its cell is found
by name, every number its traffic compares has a limit, every per-layer
metric listed for it has a reader, and every end-to-end metric listed for
it is one its traffic reports."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import harness

REPO = Path(__file__).resolve().parents[3]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_entry_is_whole(name):
    cell = harness.find_cell(name)
    limits = cell.params.get("limits", {})
    unset = [k for k in cell.traffic.CHECKS
             if not isinstance(limits.get(k), (int, float))]
    assert not unset, f"{name}: no limit for {unset}"
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"]).read), m["name"]
    listed = {m["name"] for m in cell.end_to_end} - {"setup_s"}
    assert listed and listed <= set(cell.traffic.END_TO_END), listed


def test_each_pair_of_config_and_traffic_is_given_once():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs), pairs


@pytest.mark.parametrize("traffic",
                         sorted({w["traffic"] for w in BENCH["workloads"]}))
def test_every_mix_is_data_for_a_generator(traffic):
    """A mix is parameters only: its generator is a module that exists,
    and no cell's own file repeats a parameter of the mix."""
    here = REPO / "benchmarks" / "tpu"
    mix = json.loads((here / "traffic" / f"{traffic}.json").read_text())
    assert (here / "traffic" / f"{mix['generator']}.py").is_file()
    for w in BENCH["workloads"]:
        if w["traffic"] == traffic:
            own = json.loads((here / "cells" / f"{w['name']}.json")
                             .read_text())
            assert not set(own) & set(mix) - {"why"}, w["name"]
