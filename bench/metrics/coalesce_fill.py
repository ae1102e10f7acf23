"""coalesce_fill.<cell kind>: the share of dispatched rows that were real
queries, not shape-class padding, over the window, in percent:
coalesced_rows / (coalesced_rows + padded_rows) from the coalescer's own
counters."""


def read(ctx):
    b, a = ctx.run.co_before, ctx.run.co_after
    rows = a["coalesced_rows"] - b["coalesced_rows"]
    pad = a["padded_rows"] - b["padded_rows"]
    return None if rows + pad == 0 else 100.0 * rows / (rows + pad)
