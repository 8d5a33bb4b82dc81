"""step_hbm_share: bytes the traced dispatches must move (weights once,
the predictor's reads, live KV or recurrent state) over the traced
window at the chip's HBM bandwidth."""
from chipbench import readers


def read(run):
    return readers.step_hbm_share(run)
