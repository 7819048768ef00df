"""``cycle_loop_ms``: device milliseconds of the simulator's cycle loop per
``simulate_batch`` call (``sim/step.py:_jit_runner``, the program XLA
names ``jit_run``), from the trace.  Moves ``verify_mappings_per_s``."""

MODULE = "run"


def read(tr, run, ctx):
    secs, n = tr.module_seconds(MODULE)
    return secs / n * 1e3 if n else None
