"""Builder of ``table2``: the pool of mappings a full sweep of the paper's
TABLE2 kernels stores, on the fabrics of the evaluation grid.

The pool (``data/table2/*.json``, written by ``make_table2_pool.py``)
holds one ``CompileResult`` per (kernel, job) that stored mappings; each
of its mappings is one pool entry.  The sizes, source
and deployment are in ``table2.json`` beside this file; the plain
reference is ``reference/cgra.py``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent


@dataclass
class Entry:
    name: str       # <kernel>_u<unroll>__<job>[<mapping number>]
    record: dict    # the stored mapping record (DFG, ii, time, routes)
    mapping: object  # the program's Mapping, rebuilt and validated


def load_pool(c: Dict) -> List[Entry]:
    from repro.compiler.artifact import CompileResult

    files = sorted((HERE.parent / c["pool"]).glob("*.json"))
    if not files:
        raise FileNotFoundError(f"{c['name']}: empty pool {c['pool']}")
    out = []
    for path in files:
        data = json.loads(path.read_text())
        mappings = CompileResult.from_json(data).rebuild_mappings()
        for s, (record, mapping) in enumerate(zip(data["mappings"],
                                                  mappings)):
            out.append(Entry(f"{path.stem}[{s}]", record, mapping))
    return out
