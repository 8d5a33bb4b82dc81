"""step_mfu: model operations of the traced dispatches (FFN at the
window's computed-tile fraction) over the traced window at the chip's
bf16 peak."""
from chipbench import readers


def read(run):
    return readers.step_mfu(run)
