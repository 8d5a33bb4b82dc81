"""Operations and bytes per kernel call and per engine dispatch, from
shapes alone.

Every count is the least the call needs: each weight read once, each
activation read and written once, only the rows and key positions that
hold real tokens.  So ``count / peak`` is a lower bound on the call's
time, and a roofline share built on it cannot pass 100% unless the
measured time leaves out part of the work.

Shapes come from the benchmark's own configuration file (``Shape``),
never from the program.  A dispatch is the engine's one compiled step:
``rows`` = slots x chunk (chunk 1 for a decode-only dispatch), every
slot at its own position ``pos`` consuming ``n_valid`` tokens.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

BF16 = 2
F32 = 4
TILE_M = 8            # MoR tile rows (cfg.mor.tile_m)

Cost = Tuple[float, float]            # (operations, bytes)


@dataclass(frozen=True)
class Shape:
    kind: str          # "gqa_glu" (granite)
    layers: int
    d: int
    d_ff: int
    vocab: int
    heads: int = 0
    kv_heads: int = 0
    head_dim: int = 0

    @classmethod
    def from_config(cls, c: Dict) -> "Shape":
        if c["reference"] == "gqa_glu":
            return cls("gqa_glu", c["num_hidden_layers"], c["hidden_size"],
                       c["intermediate_size"], c["vocab_size"],
                       c["num_attention_heads"], c["num_key_value_heads"],
                       c["hidden_size"] // c["num_attention_heads"])
        raise ValueError(f"unknown reference {c['reference']!r}")


# -- kernels (one call) -------------------------------------------------------

def mor_tile_mask(M: int, K: int, N: int) -> Cost:
    """Sign dot of ±1 activations and weight signs on the MXU: the whole
    (K, N) weight, the (M, K) activations, the (M, N) int8 proxy
    verdicts; the (M/8, N) tile mask out."""
    flops = 2.0 * M * K * N
    byts = K * N * BF16 + M * K * BF16 + M * N + (M // TILE_M) * N * F32
    return flops, byts


def gather_matmul(M: int, K: int, N: int, live: float) -> Cost:
    """Live tiles only: ``live`` of the weight, of the MACs, of the out."""
    flops = 2.0 * M * K * N * live
    byts = live * K * N * BF16 + M * K * BF16 + live * M * N * BF16
    return flops, byts


def masked_matmul_kdim(M: int, K: int, N: int, live: float) -> Cost:
    """Down projection skipping dead contraction blocks: ``live`` of the
    (K, N) weight rows and of the (M, K) hidden, the whole (M, N) out."""
    flops = 2.0 * M * K * N * live
    byts = live * K * N * BF16 + live * M * K * BF16 + M * N * BF16
    return flops, byts


def gqa_paged_flash(s: Shape, pos: np.ndarray, n_valid: np.ndarray) -> Cost:
    """Each valid query row attends to every key up to its own position;
    each slot with a valid row reads its keys and values (and their
    int32 position tags) once."""
    pos = np.asarray(pos, np.float64)
    nv = np.asarray(n_valid, np.float64)
    live = nv > 0
    # sum over valid rows i < nv of (pos + i + 1) keys
    keys_attended = (nv * pos + nv * (nv + 1) / 2.0).sum()
    flops = 4.0 * keys_attended * s.heads * s.head_dim
    n_keys = ((pos + nv) * live).sum()
    byts = (n_keys * (2 * s.kv_heads * s.head_dim * BF16 + F32)
            + 2 * nv.sum() * s.heads * s.head_dim * BF16)
    return flops, byts


# -- one dispatch -------------------------------------------------------------

def ffn_kernels(s: Shape, rows: int, live: float) -> Dict[str, Cost]:
    """Per layer: the MoR kernels one FFN forward calls in kernel mode."""
    g = gather_matmul(rows, s.d, s.d_ff, live)           # gate and up
    return {"mor_tile_mask": mor_tile_mask(rows, s.d, s.d_ff),
            "gather_matmul": (2 * g[0], 2 * g[1]),
            "masked_matmul_kdim": masked_matmul_kdim(rows, s.d_ff, s.d,
                                                     live)}


def dispatch_kernels(s: Shape, rows: int, pos, n_valid, live: float
                     ) -> Dict[str, Cost]:
    """Every layer's kernel calls of one dispatch, summed per kernel."""
    per = ffn_kernels(s, rows, live)
    per["gqa_paged_flash"] = gqa_paged_flash(s, pos, n_valid)
    return {k: (s.layers * f, s.layers * b) for k, (f, b) in per.items()}


def matmul_params(s: Shape, live: float) -> Tuple[float, float]:
    """(params every token multiplies by per layer, head params) with the
    FFN at its computed-tile fraction ``live``."""
    d, f = s.d, s.d_ff
    attn = d * s.heads * s.head_dim * 2 + 2 * d * s.kv_heads * s.head_dim
    return attn + 3 * d * f * live, d * s.vocab


def dispatch_model_flops(s: Shape, pos, n_valid, live: float) -> float:
    """Model operations of the path taken: every valid token through
    every layer's matmuls (FFN at ``live``), attention over its keys,
    and the output head."""
    nv = np.asarray(n_valid, np.float64)
    tokens = nv.sum()
    per_layer, head = matmul_params(s, live)
    flops = 2.0 * tokens * (s.layers * per_layer + head)
    return flops + s.layers * gqa_paged_flash(s, pos, n_valid)[0]


def dispatch_bytes(s: Shape, pos, n_valid, live: float) -> float:
    """Bytes one dispatch must move: every weight once (the FFN's at
    ``live``, plus the predictor's full read of the gated weight and of
    its proxy columns), the output head, and the live KV (keys, values,
    position tags) of every slot that runs."""
    per_layer, head = matmul_params(s, live)
    predictor = 2 * s.d * s.d_ff                       # sign read + proxies
    byts = (s.layers * (per_layer + predictor) + head) * BF16
    return byts + s.layers * gqa_paged_flash(s, pos, n_valid)[1]
