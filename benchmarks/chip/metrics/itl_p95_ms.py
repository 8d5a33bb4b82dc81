"""itl_p95_ms: 95th percentile of the gaps between consecutive
deliveries of one request, over every gap ending in the window."""


def read(run):
    return run.window["itl_p95_ms"]
