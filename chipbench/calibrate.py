"""Readings that the limits of a cell's check are set from.

    python3 -m chipbench.calibrate --workload <name> --seeds 1,2,... \
        --control-seeds 3,4,5 --seconds <s> [--control-calls <n>]

In one process, so that set-up is paid once: for each seed, the program
runs the cell's traffic for ``--seconds`` and its outputs are checked as a
benchmark run checks them (the lower readings); for each control seed,
the reference computed in bfloat16 takes the program's place for as many
calls as a run checks, and is checked the same way (the upper readings).
Prints one JSON line per reading.  The benchmark's own runs never run the
control.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from chipbench import harness  # noqa: E402


def program_reading(cell, seed: int, seconds: float) -> dict:
    mseed = harness.model_seed(seed)
    model = harness.build_model(cell.config, mseed)
    mode = importlib.import_module(
        f"chipbench.modes.{cell.traffic['mode']}").Mode(
            model, cell.config, cell.traffic, mseed)
    mode.warm_up()
    t0 = time.perf_counter()
    calls = 0
    while time.perf_counter() - t0 < seconds:
        mode.call()
        calls += 1
    mode.release()
    del model
    ref = importlib.import_module(
        f"chipbench.reference.{cell.config['reference']}")
    numbers, checked, failed = mode.check(ref, cell.limits)
    return {"who": "program", "seed": seed, "calls": checked,
            "numbers": numbers}


def control_reading(cell, seed: int, calls: int) -> dict:
    mseed = harness.model_seed(seed)
    mode = importlib.import_module(
        f"chipbench.modes.{cell.traffic['mode']}").Mode(
            None, cell.config, cell.traffic, mseed)
    ref = importlib.import_module(
        f"chipbench.reference.{cell.config['reference']}")
    mode.control(ref, calls)
    numbers, checked, failed = mode.check(ref, cell.limits)
    return {"who": "control", "seed": seed, "calls": checked,
            "numbers": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.calibrate")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-calls", type=int, default=0,
                    help="calls per control; default: as many as the "
                         "first program reading checked")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.device_info(cell.chips)
    harness.configure_jax()
    calls = args.control_calls
    for s in filter(None, args.seeds.split(",")):
        r = program_reading(cell, int(s), args.seconds)
        calls = calls or r["calls"]
        print(json.dumps(r), flush=True)
    for s in filter(None, args.control_seeds.split(",")):
        print(json.dumps(control_reading(cell, int(s), calls or 2)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
