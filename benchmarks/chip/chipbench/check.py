"""What decides ``correct``: the served tokens against the reference.

Once the window has closed, a sample of the requests the engine
finished, drawn from the seed and always holding the longest one, is
run through the plain float32 reference once each (prompt and served
tokens, teacher-forced).  At every served position the reading is how
far the served token's reference logit lies below the reference's best
logit there; the number compared is the widest such gap.  A greedy
engine that computes what the configuration states serves the
reference's best token up to the rounding of its own precision, so its
gaps stay small; a wrong token, a lost cache or a coarser precision
serves tokens the reference ranks far below its best.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from chipbench import reference
from chipbench.window import ReqLog


@dataclass
class Served:
    prompt: np.ndarray
    tokens: np.ndarray

    @property
    def seq(self) -> np.ndarray:
        return np.concatenate([self.prompt, self.tokens]).astype(np.int32)


def sample(finished: Sequence[Tuple[ReqLog, Served]], w0: float,
           seed: int, tokens: int, min_requests: int, max_requests: int
           ) -> List[Served]:
    """Requests finished inside the window (last token at or after
    ``w0``) first: the longest of them, then others drawn from the
    seed, until ``tokens`` served tokens and ``min_requests`` are
    reached; where the window finished too few, the rest come from the
    requests finished before it, drawn the same way."""
    if not finished:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))

    def drawn(idx):
        return [idx[i] for i in rng.permutation(len(idx))]
    late = [i for i, (r, _) in enumerate(finished) if r.token_t[-1] >= w0]
    early = [i for i, (r, _) in enumerate(finished) if r.token_t[-1] < w0]
    first = max(late or early, key=lambda i: len(finished[i][1].seq))
    rest = drawn([i for i in late if i != first]) + \
        drawn([i for i in early if i != first])
    picked = [first]
    got = len(finished[first][1].tokens)
    for i in rest:
        if len(picked) >= max_requests or (
                got >= tokens and len(picked) >= min_requests):
            break
        picked.append(i)
        got += len(finished[i][1].tokens)
    return [finished[i][1] for i in picked]


def served_gaps(kind: str, params, picked: Sequence[Served], pad_to: int,
                arch: Dict, control: bool = False
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Gaps at the served positions of every picked request: (program's
    served tokens, the fp8 control's first choices; zeros unless
    ``control``)."""
    got, ctl = [], []
    for s in picked:
        g, c = reference.gaps(kind, params, s.seq, pad_to, arch, control)
        lo = len(s.prompt) - 1                # position that served token 0
        got.append(g[lo:lo + len(s.tokens)])
        ctl.append(c[lo:lo + len(s.tokens)])
    return np.concatenate(got), np.concatenate(ctl)


def verdict(max_gap: float, limit: float, n_tokens: int) -> Dict:
    return {"correct": bool(n_tokens > 0 and np.isfinite(max_gap)
                            and max_gap <= limit),
            "compared": {"max_logit_gap": {"value": float(max_gap),
                                           "limit": float(limit)}}}
