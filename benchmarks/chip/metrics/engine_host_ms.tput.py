"""engine_host_ms: host milliseconds per traced dispatch in the engine's
step and drain, outside its waits on the device (token fetch, step
enqueue), from the program's own spans."""
from chipbench import spans


def read(run):
    return spans.engine_host_ms(run)
