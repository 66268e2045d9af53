"""Share of the traced slice in which no operation ran on the chip."""
import readers


def read(ctx):
    return readers.idle_share(ctx)
