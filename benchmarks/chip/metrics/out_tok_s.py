"""out_tok_s: generated tokens delivered to the host inside the window,
over the window's seconds (host clock)."""


def read(run):
    return run.window["out_tok_s"]
