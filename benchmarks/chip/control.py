"""Readings that set a cell's output limit: the program's and the
control's widest logit gap, seed by seed, in one process.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 11,12,13 --seconds 2

Each seed is a whole run of the cell (set-up from that seed, the
warm-up, a short window at the cell's own load) followed by the output
check, which here also runs the control: the reference at float8 e4m3
(``chipbench.reference``), read at every position of the same prompts
and served tokens, and judged by the same verdict against the cell's
limit (``control_correct``, which has to read false).  The lower
reading of the limit is the program's largest over the seeds, the
upper one the control's smallest.  The benchmark's own runs never run
the control.  One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench  # noqa: E402
from chipbench import check  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    root = Path.cwd()
    try:
        cell = bench.load_cell(root, args.workload)
        bench.import_program(root)
        bench.check_device(cell.chips)
    except bench.Refused as e:
        print(f"[control] refused: {e}", file=sys.stderr)
        return 2
    limit = float(cell.config["correct"]["max_logit_gap"])
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = bench.run_cell(cell, seed, args.seconds, False,
                                 control=True,
                                 cache_dir=str(root / bench.CACHE_DIR))
        except bench.Refused as e:
            print(f"[control] seed {seed} refused: {e}", file=sys.stderr)
            return 2
        rd = res["_readings"]
        g, c = rd["served_gaps"], rd["control_gaps"]
        ctl = check.verdict(float(c.max()) if c.size else np.nan, limit,
                            int(c.size))
        print(json.dumps({
            "workload": cell.name, "seed": seed, "tokens": int(g.size),
            "program_max_gap": float(g.max()) if g.size else None,
            "control_max_gap": float(c.max()) if c.size else None,
            "program_p99_gap": float(sorted(g)[int(0.99 * (g.size - 1))])
            if g.size else None,
            "control_p50_gap": float(sorted(c)[c.size // 2])
            if c.size else None,
            "limit": limit,
            "program_correct": res["correct"],
            "control_correct": ctl["correct"],
            "setup": rd["setup"]}), flush=True)
        res = None
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
