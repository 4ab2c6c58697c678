"""One run of one cell: set-up, the measured window, the check, the line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything else is found by name:

    chipbench/configs/<configuration>.json   the network as it is run
    chipbench/traffic/<traffic>.json         the calls, with their mode
    chipbench/modes/<mode>.py                how a mode drives and checks
    chipbench/reference/<reference>.py       the network's plain reference
    chipbench/limits/<workload>.json         the limits of the check
    chipbench/metrics/<metric>.py            one metric's reduction

The system under test is the ``repro`` package in ``src/`` of the
checkout; the benchmark takes from it only the model, its outputs and the
kernel names in the profiler's trace.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax

from chipbench import check
from chipbench import trace as tr
from chipbench import work

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"
SPAN = "chipbench."        # prefix of the benchmark's host spans
# A traced run profiles this much of the window (and at least one call):
# the profiler drops events once a few million have been recorded.
TRACE_SECONDS = 1.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(workload: str, benchmark: Path = BENCHMARK) -> Cell:
    bench = _json(benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {benchmark.name}; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_json(ROOT / conf["file"]),
        traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_json(HERE / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def device_info(chips: int, require_chip: bool = True) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if require_chip and d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {d0.platform!r} "
                     f"({d0.device_kind}); the benchmark runs on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds and count of JAX's compile events (tracing, lowering,
    compiling) while it is installed."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event == "/jax/core/compile/backend_compile_duration":
                self.events += 1


def configure_jax() -> str:
    """The compile cache in the checkout (or where JAX_COMPILATION_CACHE_DIR
    says), every program of the cell cached however quick its compile.
    Returns the cache directory."""
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def model_seed(seed: int) -> int:
    """The seed the network is built from: JAX keys hold 32 bits."""
    return int(seed) % (1 << 32)


def build_model(config: dict, seed: int):
    """The configuration's network through the normal path:
    ``<model>.spec(cfg)`` with a spike probe on each recorded population,
    then ``ModelSpec.build``."""
    mod = importlib.import_module(f"repro.core.models.{config['model']}")
    cls = getattr(mod, config["model_class"])
    cfg = cls(seed=seed, **config["model_config"])
    spec = mod.spec(cfg)
    for pop in config["record_spikes"]:
        spec.probe(f"{pop}_spikes", pop, "spikes")
    model = spec.build(dt=cfg.dt, seed=cfg.seed, init=config["init"])
    jax.block_until_ready([(g.ell.g, g.dense) for g in model.network.synapses])
    return model


def propagation_plan(model) -> list:
    """Each synapse group's representation, propagation mode and event
    capacity, as the program chose them."""
    return [{"group": g.name, "representation": g.representation,
             "mode": g.propagation_mode, "capacity": g.event_capacity}
            for g in model.network.synapses]


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True) -> dict:
    """One run; returns the result line's object."""
    from repro.kernels import ops

    device = device_info(cell.chips, require_chip)
    peak = work.peaks(device["kind"]) if require_chip else None
    cache = configure_jax()
    clock = CompileClock()
    mseed = model_seed(seed)
    log(f"device {device}; kernel backend {ops.backend()}; "
        f"compile cache {cache}")

    c0, t0 = clock.seconds, time.perf_counter()
    model = build_model(cell.config, mseed)
    build_wall = time.perf_counter() - t0
    build_compile = clock.seconds - c0
    for p in propagation_plan(model):
        log(f"propagation {p}")
    mode_mod = importlib.import_module(
        f"chipbench.modes.{cell.traffic['mode']}")
    mode = mode_mod.Mode(model, cell.config, cell.traffic, mseed)
    mode.warm_up()
    setup_s = time.perf_counter() - t_start
    setup_compile = clock.seconds
    log(f"set-up {setup_s:.3f} s: build {build_wall:.3f} s "
        f"({build_compile:.3f} s compiling), compiling in all "
        f"{setup_compile:.3f} s")

    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    events0 = clock.events
    with tr.profiled(logdir):
        with jax.profiler.TraceAnnotation(SPAN + "window"):
            t0 = time.perf_counter()
            calls = steps = 0
            while True:
                steps += mode.call()
                calls += 1
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
    window_compiles = clock.events - events0
    log(f"window: {calls} calls, {steps} network-steps in {elapsed:.3f} s; "
        f"{window_compiles} compiles inside the window")

    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    spikes = mode.spike_totals()
    mode.release()
    del model
    ref = importlib.import_module(
        f"chipbench.reference.{cell.config['reference']}")
    layout = ref.layout(cell.config["model_config"])
    for pop, n in spikes.items():
        hz = n / (layout["populations"][pop]["n"] * steps
                  * float(cell.config["model_config"]["dt"]) * 1e-3)
        log(f"window rate {pop} {hz:.3f} Hz ({n} spikes)")

    reduced = None
    if trace:
        reduced = tr.reduce_dir(logdir, SPAN)
        shutil.rmtree(logdir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    t0 = time.perf_counter()
    numbers, checked, failed_calls = mode.check(ref, cell.limits)
    log(f"reference check of {checked} calls: "
        f"{time.perf_counter() - t0:.3f} s")
    numbers["window_compiles"] = window_compiles
    correct = not check.over(numbers, cell.limits)
    # the numbers are the run's; a wrong run names its calls that breach
    failed = 0 if correct else max(failed_calls, 1)

    ctx = {
        "window": {"seconds": elapsed, "net_steps": steps, "calls": calls},
        "setup": {"setup_s": setup_s, "compile_s": setup_compile,
                  "build_s": build_wall - build_compile},
        "trace": reduced, "peak": peak,
        "work": work.necessary(layout, spikes, steps),
    }
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        reader = importlib.import_module(
            "chipbench.metrics." + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = {k: {"value": v, "limit": cell.limits.get(k, 0)}
              for k, v in numbers.items()}
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    result = {"correct": bool(correct), "attempted": checked,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result
