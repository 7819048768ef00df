"""``moe_pad_share``: share of the rows handed to the expert layers'
grouped matmuls that are no route to a held expert, over the window's
calls: ``1 - moe_routed / moe_rows``, the program's own counters
(``models/moe.py``, returned by ``generate``), prefill and decode
together.  Moves ``output_tokens_per_s``."""


def read(tr, run, ctx):
    infos = getattr(run, "infos", [])
    if not infos or any("moe_rows" not in i for i in infos):
        return None
    routed = sum(int(i["moe_routed"].sum()) for i in infos)
    rows = sum(int(i["moe_rows"].sum()) for i in infos)
    return 100.0 * (1.0 - routed / rows) if rows else None
