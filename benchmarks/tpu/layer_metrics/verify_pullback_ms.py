"""``verify_pullback_ms``: milliseconds per ``simulate_batch`` call of the
program's span ``sim.pullback`` (``sim/step.py:run_bucket_jnp``: the
loop's three outputs copied to the host, values upcast to float64), from
the ``BatchResult`` of each traced call.  Moves
``verify_mappings_per_s``."""

import sim_spans

SPAN = "sim.pullback"


def read(tr, run, ctx):
    return sim_spans.span_ms(run, SPAN)
