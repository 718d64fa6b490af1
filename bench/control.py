"""Readings that set the limits of a serving cell's correctness check.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed, in this one process: a short window of the cell at its own
load through the program, then the check with the fp8 control in the
served tokens' place.  One JSON line per seed: the readings of the served
tokens (the lower reading's candidates), the control's readings on the
same prompts and tokens, under the float32 reference, of the tokens that
the fp8 reference puts first (the upper reading's candidates), and
``correct`` as the harness decides it for the control, which has to be
false.  Benchmark runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        harness.require_program()
        harness.checkout_env()
        device = harness.require_chips(cell.chips)
        harness.enable_cache()
        harness.model_sizes_agree(cell.config)
    except harness.HarnessError as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    from bench.drivers.serve_batches import serve
    for seed in (int(s) for s in args.seeds.split(",")):
        out = serve(cell, seed, args.seconds, False, control=True)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "device": device["kind"], **out["readings"],
                          **{f"control_{k}": v for k, v in
                             out["control_readings"].items()},
                          "control_correct": out["correct"],
                          "serve_tokens_per_s":
                              out["end_to_end"]["serve_tokens_per_s"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
