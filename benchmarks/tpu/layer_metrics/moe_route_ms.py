"""``moe_route_ms``: device milliseconds a decode step of the expert
dispatch, the ops under the named scopes ``moe/route`` (router, top-k,
sorting the routes by expert, gathering their rows) and ``moe/combine``
(un-sorting and weighting the experts' rows), over every MoE layer of
the step.  Moves ``output_tokens_per_s``."""

import serve_scopes


def read(tr, run, ctx):
    n = serve_scopes.steps_in_window(tr, run)
    split = serve_scopes.scope_ms(tr, run)
    if n is None or not split:
        return None
    ms = split.get("moe/route", 0.0) + split.get("moe/combine", 0.0)
    return ms / n if ms > 0 else None
