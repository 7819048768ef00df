#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of the machine it runs on.

    python3 benchmarks/tpu/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout.  Exits non-zero, and prints no result, when
JAX finds no TPU or fewer chips than the cell asks for.  Set-up (imports,
JAX start, the cell's inputs and weights, compiles or cache loads, one
warm-up call) is ``setup_s``; then the window runs for ``--seconds``.
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
its per-layer metrics, ``busy_s``/``window_s`` and a ``breakdown``.  Once
the window has closed and the device memory peak is read, the traffic's
plain reference decides ``correct``; each number compared is printed with
its limit, last on standard error and last in the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent

import harness  # noqa: E402
from harness import log  # noqa: E402

#: where a traced run writes its profile, under the checkout's root
#: (replaced by every traced run, removed once reduced)
TRACE_DIR = ".bench_traces"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def start_program(root: Path, cache: bool = True):
    """Put the program on the path and place its compile cache; every
    program the window runs is kept there, so a later run loads it."""
    sys.path.insert(0, str(root / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    if not cache:
        return "off"

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def device_info(devices, trace=None) -> dict:
    dev = devices[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    if trace is not None:
        out["busy_s"] = trace.busy_s
        out["window_s"] = trace.window_s
    return out


def layer_metrics(cell, tr, run, ctx, here: Path) -> dict:
    out = {}
    for m in cell.per_layer:
        value = harness.reader(m["name"], here).read(tr, run, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, devices=None, here: Path = HERE,
         root: Path = harness.ROOT) -> int:
    """``devices``, ``here`` and ``root`` are for tests: they skip the look
    for a chip, find the benchmark's files elsewhere and leave the compile
    cache off."""
    args = parse(argv)
    cell = harness.find_cell(args.workload, here=here, root=root)
    on_chip = devices is None
    if on_chip:
        devices = harness.require_devices(cell.entry["chips"])
    cache = start_program(root, cache=on_chip)
    clock = harness.CompileClock().install()
    ctx = harness.Context(cell=cell, seed=args.seed, devices=devices,
                          peaks=harness.peaks(devices[0].device_kind, here))
    log(f"{cell.name}: {devices[0].device_kind} x{len(devices)}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}; compile cache "
        f"{cache}")
    run = cell.traffic.Traffic(ctx)
    run.setup()
    setup_s = time.perf_counter() - T_START
    c0, h0, m0, n0 = clock.snapshot()
    log(f"set-up {setup_s:.3f} s: compile {c0:.2f} s, cache {h0} hit(s) / "
        f"{m0} miss(es)")

    tr = breakdown = None
    if args.trace:
        import jax

        trace_dir = root / TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        with harness.span("window"):
            run.window(args.seconds)
        jax.profiler.stop_trace()
    else:
        run.window(args.seconds)
    c1, h1, _, n1 = clock.snapshot()
    log(f"window {run.window_s:.3f} s: {run.attempted} attempted, "
        f"{run.failed} failed; compiles inside it: {n1 - n0 - (h1 - h0)} "
        f"({c1 - c0:.2f} s), persistent-cache loads {h1 - h0}")
    if args.trace:
        tr = harness.load_module(HERE / "trace.py").load(
            str(trace_dir), cell.traffic.SPANS, "window")
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = layer_metrics(cell, tr, run, ctx, here)
        breakdown = {"device_ops": [list(x) for x in tr.top_ops(10)],
                     "idle_gaps": [list(x) for x in tr.idle_by_label(10)]}
    else:
        e2e = dict(run.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = device_info(devices, tr)
    for name, m in metrics.items():
        log(f"{name}: {m['value']!r} {m['unit']}")
    run.release()
    t = time.perf_counter()
    checks = run.checks()
    log(f"reference and comparison {time.perf_counter() - t:.3f} s")
    correct = all(c.ok for c in checks) and run.failed == 0
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
            f"{'' if c.ok else ' FAILED'}")
    print(harness.result_line(correct=correct, attempted=run.attempted,
                              failed=run.failed, metrics=metrics,
                              device=device, checks=checks,
                              breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.NoDevice as e:
        print(e, file=sys.stderr)
        sys.exit(3)
