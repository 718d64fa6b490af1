"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything runs in this one process on the machine it is started on.  The
cell's files are found by name (see ``bench/harness.py``).  With
``--trace 0`` the result line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and a breakdown of the traced window.
Exit status 0 and a JSON result as the last line of stdout; on any other
status no result is printed: no TPU, fewer chips than the cell needs, the
program missing from the checkout, or a compile inside the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        harness.require_program()
        harness.checkout_env()
        device = harness.require_chips(cell.chips)
        harness.say(f"device: {device}")
        harness.say(f"compile cache: {harness.enable_cache()}")
        harness.model_sizes_agree(cell.config)
        result = harness.driver(cell.traffic).run(
            cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t_start=T_START)
    except harness.HarnessError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    if args.trace:
        metrics = harness.read_per_layer(cell, result["record"])
        device = {**device, "busy_s": result["trace"]["busy_s"],
                  "window_s": result["trace"]["window_s"]}
    else:
        metrics = {m["name"]: {"value": result["end_to_end"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    harness.emit(correct=result["correct"], attempted=result["attempted"],
                 failed=result["failed"], metrics=metrics, device=device,
                 checks=result["checks"],
                 breakdown=result["trace"]["breakdown"] if args.trace
                 else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
