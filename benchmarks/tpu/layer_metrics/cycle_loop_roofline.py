"""``cycle_loop_roofline``: the cycle loop's share of its memory roofline.

The least bytes the traced calls had to move (``work.mapping_bytes`` of
every pool mapping: each execution's operand reads and value write, and
one read of each mapping's description), over the chip's HBM bandwidth,
over the cycle loop's device time in the trace.  The count does not
depend on how the program gathers or scatters.  Moves
``verify_mappings_per_s``."""

MODULE = "run"


def read(tr, run, ctx):
    secs, n = tr.module_seconds(MODULE)
    if not n or n != len(run.results) or secs <= 0:
        return None
    moved = len(run.results) * run.call_bytes()
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / secs
