"""Record the trace that test_trace.py and test_scopes.py read, on the
chip:

    python3 -m chipbench.tests.record_trace [--out DIR]

Two 10-step calls of izh100k.run (10^5 neurons) inside the benchmark's
window span, profiled as a ``--trace 1`` run profiles, then the optimized
HLO text of the program the calls ran.  Writes into DIR, by default beside
this file in data/:

    izh100k_run_2calls.xplane.pb     the XSpace
    izh100k_run_2calls.json          its opcode-based reduction
    izh100k_run_2calls.hlo.txt.gz    the run program's optimized HLO
    izh100k_run_2calls.scopes.json   its reduction by named scope

and prints the reduction by scope, the per-layer shares and the seconds
the HLO text took to fetch after the window as one JSON line.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench import scopes as sc  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.modes import run  # noqa: E402

NAME = "izh100k_run_2calls"
CALLS = 2


def record(cell: harness.Cell, out: Path, seed: int) -> dict:
    mode = run.Mode(harness.build_model(cell.config, seed), cell.config,
                    cell.traffic, seed)
    mode.warm_up()
    logdir = tempfile.mkdtemp()
    with tr.profiled(logdir):
        with jax.profiler.TraceAnnotation(harness.SPAN + tr.WINDOW):
            t0 = time.perf_counter()
            for _ in range(CALLS):
                mode.call()
            window_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    hlo = mode.program_hlo()
    hlo_s = time.perf_counter() - t0
    shutil.copy(tr.find_xspace(logdir), out / f"{NAME}.xplane.pb")
    (out / f"{NAME}.json").write_text(
        json.dumps(tr.reduce_dir(logdir, harness.SPAN), indent=1))
    (out / f"{NAME}.hlo.txt.gz").write_bytes(
        gzip.compress(hlo.encode(), mtime=0))
    r = sc.reduce_dir(logdir, hlo, harness.SPAN)
    (out / f"{NAME}.scopes.json").write_text(json.dumps(r, indent=1))
    shutil.rmtree(logdir, ignore_errors=True)
    return {"window_s": window_s, "net_steps": CALLS * mode.steps,
            "hlo_fetch_s": hlo_s, "shares": sc.shares(r), **r}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(Path(__file__).parent / "data"))
    out = Path(ap.parse_args(argv).out)
    out.mkdir(parents=True, exist_ok=True)
    cell = harness.load_cell("izh100k.run")
    harness.device_info(cell.chips)
    harness.configure_jax()
    print(json.dumps(record(cell, out, harness.model_seed(20261017))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
