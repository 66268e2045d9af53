"""Device time of one execution of the prefill program (a group of
``prefill_batch`` prompts padded to the prompt cap)."""
import readers


def read(ctx):
    return readers.program_ms(ctx, readers.PREFILL)
