"""Device time of one decode step: the decode-chunk program's device
time over its ``sync_every`` steps."""
import readers


def read(ctx):
    return readers.program_ms(ctx, readers.DECODE,
                              int(ctx.cfg["serving"]["sync_every"]))
