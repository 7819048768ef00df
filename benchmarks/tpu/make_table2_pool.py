#!/usr/bin/env python3
"""Regenerate the mapping pool of the ``table2`` configuration.

    PYTHONPATH=src python benchmarks/tpu/make_table2_pool.py [--workers 4]

Compiles every TABLE2 workload (``core/workloads.py``) with every job of
the evaluation grid that ``configs/table2.json`` names, at mapper seed 0,
on the host, as ``python -m repro.core.collect`` does for a full sweep,
and writes one ``CompileResult`` JSON per job that stored mappings under
``data/table2/``: the store a full sweep leaves behind, whose mappings its
post-sweep verification (``collect --batch-verify``) sends in one call.
A job that maps nothing (or, as the spatial mapper mostly does, keeps no
mapping in its artifact) is not written, as that call skips it.  The
benchmark loads these files and never runs place and route itself, so a
later change to the mapper cannot change the cell's traffic.
"""
from __future__ import annotations

import argparse
import json
import sys
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

HERE = Path(__file__).resolve().parent
CONFIG = HERE / "configs" / "table2.json"
OUT = HERE / "data" / "table2"


def compile_job(task):
    """``(file name, CompileResult JSON or None, verified)`` of one job."""
    name, unroll, job, arch, mapper, seed = task
    from repro.compiler import compile
    from repro.core.workloads import workload_by_name

    res = compile(workload_by_name(name, unroll), arch=arch, mapper=mapper,
                  seed=seed, verify=True)
    stem = f"{name}_u{unroll}__{job}"
    if not res.mapped or not res.mappings:
        return stem, None, False
    data = res.to_json()
    # the benchmark needs the mapping, not the compile's own timings,
    # caches or lowered forms
    for key in ("timings", "pass_stats", "route_cache", "compiled_sim",
                "provenance"):
        data[key] = None
    return stem, data, bool(res.verified)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--workers", type=int, default=4)
    args = ap.parse_args(argv)
    cfg = json.loads(CONFIG.read_text())

    from repro.core.workloads import TABLE2

    tasks = [(w.name, w.unroll, f["job"], f["arch"], f["mapper"],
              cfg["mapper_seed"])
             for w in TABLE2 for f in cfg["fabrics"]]
    OUT.mkdir(parents=True, exist_ok=True)
    for old in OUT.glob("*.json"):
        old.unlink()
    unmapped, unverified = [], []
    with ProcessPoolExecutor(args.workers) as ex:
        for stem, data, verified in ex.map(compile_job, tasks):
            if data is None:
                unmapped.append(stem)
                continue
            if not verified:
                unverified.append(stem)
            (OUT / f"{stem}.json").write_text(
                json.dumps(data, sort_keys=True, separators=(",", ":")))
    files = list(OUT.glob("*.json"))
    size = sum(p.stat().st_size for p in files)
    n = sum(len(json.loads(p.read_text())["mappings"]) for p in files)
    print(f"{len(files)} of {len(tasks)} jobs stored {n} mappings, written "
          f"to {OUT} ({size} bytes)")
    print(f"no mapping stored ({len(unmapped)}): {' '.join(unmapped)}")
    print(f"stored but not verified by the compile ({len(unverified)}): "
          f"{' '.join(unverified)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
