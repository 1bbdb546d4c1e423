#!/usr/bin/env python3
"""Readings that set a cell's ``max_logit_gap`` limit: for each seed, a
run of the cell (a short window at its own load), then the served tokens'
widest gap and the control's over the same sample.  The control is the
reference computed with float8 matmuls, the precision step below the
configuration's bfloat16, judged by the same comparison as the program
(``control_correct``), which it has to fail.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

Prints one JSON line per seed.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]

from bench.run import parse, place_compile_cache, run  # noqa: E402


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    args = p.parse_args()
    place_compile_cache()
    for seed in args.seeds.split(","):
        res = run(parse(["--workload", args.workload, "--seed", seed,
                         "--seconds", str(args.seconds)]), control=True)
        print(json.dumps({"seed": int(seed),
                          "served_gap": res["checks"]["max_logit_gap"]["value"],
                          "control_gap": res["control"]["gap"],
                          "compared": res["info"]["compared"],
                          "correct": res["correct"],
                          "control_correct": res["control"]["correct"]}),
              flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
