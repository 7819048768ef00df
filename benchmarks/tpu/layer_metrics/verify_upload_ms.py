"""``verify_upload_ms``: milliseconds per ``simulate_batch`` call of the
program's span ``sim.upload`` (``sim/step.py:run_bucket_jnp``: the 13
bucket arrays sent to the device, until they are there), from the
``BatchResult`` of each traced call.  Moves ``verify_mappings_per_s``."""

import sim_spans

SPAN = "sim.upload"


def read(tr, run, ctx):
    return sim_spans.span_ms(run, SPAN)
