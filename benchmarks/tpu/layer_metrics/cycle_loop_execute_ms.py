"""``cycle_loop_execute_ms``: device milliseconds per call of the cycle
loop's ops (program ``jit_run``) under the named scope ``execute``
(``sim/step.py:_jit_runner``, phase 1 of a simulated cycle: operand read,
presence check, ALU, value write), by self time.  Moves
``verify_mappings_per_s``."""

import sim_spans


def read(tr, run, ctx):
    return sim_spans.phase_total_ms(tr, run, "execute")
