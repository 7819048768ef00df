"""``device_idle.verify``: share of the traced window in which no op ran
on the chip, while the verify sweep ran.  Moves
``verify_mappings_per_s``."""


def read(tr, run, ctx):
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
