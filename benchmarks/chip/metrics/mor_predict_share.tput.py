"""mor_predict_share: mor_tile_mask device time over the MoR FFN
kernels' device time in the trace: what the prediction costs."""
from chipbench import readers


def read(run):
    return readers.predict_share(run)
