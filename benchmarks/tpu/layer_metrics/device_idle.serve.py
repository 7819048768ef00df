"""``device_idle.serve``: share of the traced window in which no op ran on
the chip, while the serving loop (``serve/loop.py``) ran.  The breakdown's
``idle_gaps`` say what the host was doing.  Moves
``output_tokens_per_s``."""


def read(tr, run, ctx):
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
