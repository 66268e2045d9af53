"""The Pallas paged flash-decode kernel's share of its HBM roofline:
the least time at peak bandwidth for the pages its live slots must
read, over its device time."""
import readers


def read(ctx):
    return readers.paged_roofline(ctx)
