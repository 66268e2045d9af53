"""90th percentile of the time answered requests waited in the
gateway's arrival queue (obs ``queue_wait`` spans), over the window."""
import readers


def read(ctx):
    return readers.span_p90(ctx, "queue_wait")
