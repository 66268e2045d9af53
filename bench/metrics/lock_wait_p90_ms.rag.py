"""90th percentile of the time answered requests waited, from arrival,
for the gateway lock before their enqueue (obs ``lock_wait`` spans,
nested in ``queue_wait``), over the window."""
import readers


def read(ctx):
    return readers.span_p90(ctx, "lock_wait")
