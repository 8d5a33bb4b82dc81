"""mor_ffn_roofline: least time of the traced MoR FFN kernel calls
(mor_tile_mask, gather_matmul, masked_matmul_kdim where the model calls
it) on this chip over their device time in the trace."""
from chipbench import readers


def read(run):
    return readers.roofline(run, readers.MOR_KERNELS)
