"""90th percentile of the time from an answered request's dispatch into
the engine to the first control sync that showed its first token (obs
``prefill`` spans), over the window.  A program that ends ``prefill``
at the dispatch instead marks no ``lock_wait``: nothing to read."""
import readers


def read(ctx):
    if not any("lock_wait" in b.stages for b in ctx.breakdowns):
        return None
    return readers.span_p90(ctx, "prefill")
