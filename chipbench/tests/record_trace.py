"""Record the small trace that test_trace.py reads, on the chip:

    python3 -m chipbench.tests.record_trace [--out DIR]

Two 10-step calls of izh100k.run (10^5 neurons) inside the benchmark's
window span, profiled as a ``--trace 1`` run profiles; writes the XSpace
and its reduction (the test's expected values) into DIR, by default
beside this file in data/.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.modes import run  # noqa: E402

NAME = "izh100k_run_2calls"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(Path(__file__).parent / "data"))
    out = Path(ap.parse_args(argv).out)
    out.mkdir(parents=True, exist_ok=True)
    cell = harness.load_cell("izh100k.run")
    harness.device_info(cell.chips)
    harness.configure_jax()
    seed = harness.model_seed(20261017)
    mode = run.Mode(harness.build_model(cell.config, seed), cell.config,
                    cell.traffic, seed)
    mode.warm_up()
    logdir = tempfile.mkdtemp()
    with tr.profiled(logdir):
        with jax.profiler.TraceAnnotation(harness.SPAN + tr.WINDOW):
            for _ in range(2):
                mode.call()
    shutil.copy(tr.find_xspace(logdir), out / f"{NAME}.xplane.pb")
    r = tr.reduce_dir(logdir, harness.SPAN)
    (out / f"{NAME}.json").write_text(json.dumps(r, indent=1))
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
