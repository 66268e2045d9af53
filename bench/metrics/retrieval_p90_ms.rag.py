"""90th percentile of the BM25 lookup per answered request (obs
``retrieval`` spans), over the window."""
import readers


def read(ctx):
    return readers.span_p90(ctx, "retrieval")
