"""paged_attn_roofline: least time of the traced gqa_paged_flash calls on
this chip over their device time in the trace."""
from chipbench import readers


def read(run):
    return readers.roofline(run, ("gqa_paged_flash",))
