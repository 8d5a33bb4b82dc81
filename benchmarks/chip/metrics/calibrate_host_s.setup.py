"""calibrate_host_s: seconds of the set-up's MoR calibration spent
outside its forward passes (clustering, predictor build, weight
permutation), from the program's own spans."""
from chipbench import spans


def read(run):
    return spans.calibrate_host_s(run)
