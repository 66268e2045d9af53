"""Model FLOPs of the unpadded prompts prefilled and the tokens decoded
in the traced slice, over the slice times the chips' bf16 peak."""
import readers


def read(ctx):
    return readers.mfu(ctx)
