"""``verify_host_ms``: milliseconds per ``simulate_batch`` call spent off
the cycle loop (packing the device arguments, upload, pull-back and the
verdict checks): the call's host span less the cycle loop's device time
inside it, on the trace's clock.  Moves ``verify_mappings_per_s``."""

MODULE = "run"
SPAN = "simulate_batch"


def read(tr, run, ctx):
    calls = tr.span_calls(SPAN)
    loops = tr.module_calls(MODULE)
    if not calls:
        return None
    off = []
    for sp in calls:
        inside = sum(min(e, sp.end) - max(s, sp.start) for s, e in loops
                     if s < sp.end and e > sp.start)
        off.append(sp.end - sp.start - inside)
    return sum(off) / len(off) / 1e6
