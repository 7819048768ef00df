"""What every cell shares: finding its files by name, the device check, the
compile clock, the peaks table and the result line.

A cell ``<cell>`` of ``BENCHMARK.json`` is the file ``cells/<cell>.json``;
it names its configuration (``configs/<config>.json`` with its builder
``configs/<config>.py``), its traffic mix and the limits of its checks.
A mix ``<traffic>`` is the data file ``traffic/<traffic>.json``: the
parameters of one general generator, which it names
(``traffic/<generator>.py``).  A per-layer metric ``<metric>`` is read by
``layer_metrics/<metric>.py``.  Adding any of them is adding files and
``BENCHMARK.json`` entries.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
#: the checkout the benchmark runs from (``BENCHMARK.json`` lives here)
ROOT = HERE.parents[1]


class NoDevice(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path, name: Optional[str] = None) -> ModuleType:
    """Import ``path`` as a module of its own (file names such as
    ``device_idle.verify.py`` are not importable by name)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, found by name."""

    name: str
    entry: Dict                   # the BENCHMARK.json workload entry
    params: Dict                  # traffic/<traffic>.json, cells/<cell>.json
    config: Dict                  # configs/<config>.json
    config_module: ModuleType     # configs/<config>.py
    traffic: ModuleType           # traffic/<generator>.py
    end_to_end: List[Dict] = field(default_factory=list)
    per_layer: List[Dict] = field(default_factory=list)


def _applies(metric: Dict, cell: str, e2e_names) -> bool:
    """A per-layer metric is read in the cells it lists, or else in every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def find_cell(name: str, bench: Optional[Dict] = None,
              here: Path = HERE, root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else read_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have: {', '.join(sorted(entries))})")
    entry = entries[name]
    own = read_json(here / "cells" / f"{name}.json")
    if own["config"] != entry["config"] or own["traffic"] != entry["traffic"]:
        raise ValueError(f"cells/{name}.json disagrees with BENCHMARK.json "
                         "on its config or traffic")
    mix = read_json(here / "traffic" / f"{entry['traffic']}.json")
    shared = set(mix) & set(own) - {"why"}
    if shared:
        raise ValueError(f"cells/{name}.json repeats the mix's "
                         f"{sorted(shared)}")
    params = {**mix, **own}
    config = read_json(here / "configs" / f"{entry['config']}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(
        name=name, entry=entry, params=params, config=config,
        config_module=load_module(here / "configs" / f"{entry['config']}.py"),
        traffic=load_module(here / "traffic" / f"{mix['generator']}.py"),
        end_to_end=e2e, per_layer=layer)


def reader(metric: str, here: Path = HERE) -> ModuleType:
    return load_module(here / "layer_metrics" / f"{metric}.py")


def peaks(device_kind: str, here: Path = HERE) -> Dict:
    table = read_json(here / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have: {', '.join(sorted(table))})")
    return table[device_kind]


def require_devices(chips: int):
    """The TPU chips JAX sees, or exit non-zero at once."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"benchmark: needs a TPU; JAX found platform "
                       f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"benchmark: the cell needs {chips} chip(s); JAX "
                       f"found {len(devs)}")
    return devs[:chips]


class CompileClock:
    """XLA compile seconds and counts, and persistent-cache hits and
    misses, read from JAX's own monitoring events.  A compile event fires
    for a program loaded from the cache too, so compiles are the events
    less the hits."""

    def __init__(self):
        self.compile_s = 0.0
        self.events = 0
        self.hits = 0
        self.misses = 0

    def install(self) -> "CompileClock":
        import jax.monitoring as mon

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
                self.events += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        return self

    def snapshot(self):
        return self.compile_s, self.hits, self.misses, self.events


@contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@dataclass
class Check:
    """One number compared against its limit (passes when ``value <=
    limit``).  A cell whose limit is not yet set from readings on the
    chip gives ``None`` and never passes."""

    name: str
    value: float
    limit: Optional[float]

    @property
    def ok(self) -> bool:
        return (self.limit is not None and self.value == self.value
                and self.value <= self.limit)


@dataclass
class Context:
    """What a traffic module and a layer-metric reader are handed."""

    cell: Cell
    seed: int
    devices: list
    peaks: Dict


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict], device: Dict,
                checks: List[Check], breakdown: Optional[Dict] = None) -> str:
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)
