"""serve_fill_ratio: lanes holding a query over lanes dispatched, in
percent, over the window, from the server's statsz counters (``routing``
and ``padded_lanes_total``, the window's difference)."""


def read(ctx):
    offered = ctx.counters.get("lanes_offered")
    if not offered:
        return None
    return 100.0 * ctx.counters["lanes_used"] / offered
