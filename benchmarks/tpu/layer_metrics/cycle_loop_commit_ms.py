"""``cycle_loop_commit_ms``: device milliseconds per call of the cycle
loop's ops (program ``jit_run``) under the named scope ``commit``
(``sim/step.py:_jit_runner``, phase 2 of a simulated cycle: route-step
writes made readable for the next cycle), by self time.  Moves
``verify_mappings_per_s``."""

import sim_spans


def read(tr, run, ctx):
    return sim_spans.phase_total_ms(tr, run, "commit")
