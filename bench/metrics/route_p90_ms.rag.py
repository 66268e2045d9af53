"""90th percentile of the routing (state features and router MLP) of
the batch each answered request was routed in (obs ``route`` spans,
nested in ``admission``), over the window."""
import readers


def read(ctx):
    return readers.span_p90(ctx, "route")
