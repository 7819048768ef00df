"""``verify_check_ms``: milliseconds per ``simulate_batch`` call of the
program's span ``sim.check`` (``sim/batch.py:_bucket_verdicts``: the
tolerance compare against the reference values and the per-mapping
verdicts), from the ``BatchResult`` of each traced call.  Moves
``verify_mappings_per_s``."""

import sim_spans

SPAN = "sim.check"


def read(tr, run, ctx):
    return sim_spans.span_ms(run, SPAN)
