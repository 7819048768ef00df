"""``verify_idle_unspanned_ms``: milliseconds per ``simulate_batch`` call
in which the chip was idle inside the harness's span and none of the
program's phase spans (``sim.upload``, ``sim.cycle_loop``,
``sim.pullback``, ``sim.check``, ...) ran: idle time the program's spans
do not explain.  Moves ``verify_mappings_per_s``."""

import sim_spans


def read(tr, run, ctx):
    return sim_spans.idle_unspanned_ms(tr, run)
