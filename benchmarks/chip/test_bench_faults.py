"""The output check catches a broken timed path.  Each test drives the
rest of a run at reduced size on the CPU (the harness's look for a chip
skipped) with one fault planted in the program underneath, and sees
``correct`` come out false: a token altered where it is sampled, and a
step that hands back its cache (the KV pages) unchanged."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import benchtest_util as U  # noqa: E402

CELLS = ["granite-3-2b.mor.decode"]


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("jc")))
    with U.KeepJaxConfig():
        yield U.load_run()
    mp.undo()


def _altered_token(monkeypatch, cfg_vocab):
    from repro.serving import engine as eng_mod
    sample = eng_mod.sample_step

    def wrong(*a, **kw):
        nxt, extra = sample(*a, **kw)
        return (nxt + 1) % cfg_vocab, extra
    monkeypatch.setattr(eng_mod, "sample_step", wrong)


def _state_unchanged(monkeypatch):
    from repro.models import transformer as mod
    step = mod.prefill_chunk

    def frozen(params, cfg, tokens, cache, **kw):
        logits, _, aux = step(params, cfg, tokens, cache, **kw)
        return logits, cache, aux
    monkeypatch.setattr(mod, "prefill_chunk", frozen)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_fault_fails_the_check(run, monkeypatch, cell, fault):
    from repro.configs import get_config, reduce_config
    cfg = reduce_config(get_config(U.small_cell(run, cell).config["arch"]))
    if fault == "token_altered":
        _altered_token(monkeypatch, cfg.vocab_size)
    else:
        _state_unchanged(monkeypatch)
    res, _ = U.run_small(run, cell, 2**31 + 3)
    assert res["correct"] is False
    c = res["compared"]["max_logit_gap"]
    assert c["value"] > 10 * c["limit"]
