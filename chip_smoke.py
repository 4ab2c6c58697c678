#!/usr/bin/env python3
"""Smoke run of the simulator on a TPU: the quickest proof it still starts.

Drives the main path once, through the entry points a user calls, at the
size computational neuroscience runs: the paper's Izhikevich cortical
network with 100,000 neurons and 1,000 synapses per neuron
(``IzhikevichNetConfig(n_total=100_000, n_conn=1_000)``), its connectivity
generated on the device (``init="device"``).

One process owns the chip through every phase:

1. device check: no TPU, no run (exit 1 before any result line);
2. offline run: ``OFFLINE_STEPS`` steps to ``block_until_ready``; the
   result must be finite with both population rates inside
   ``RATE_BAND_HZ``;
3. kernels: the compiled (never interpreted) Pallas ELL kernels against
   ``repro.kernels.ref`` within 1e-5, at every synapse group's shape, at
   its event-capacity shape and at one fused-delay shape; then
   one served request's window of the full model on the Pallas backend
   against the reference backend (spike counts must be equal);
4. serving: the model behind a ``Gateway`` and its HTTP front door on
   localhost; every POST /v1/simulate must answer 200, and one served
   stream's spike counts must equal an offline ``model.run`` with the same
   stimulus and seed.

``--chips 4`` runs only the sharded path and what it is compared with: the
same network built on a 4-device mesh (fused local construction), its
arrays checked to be spread over the four chips, its spike counts compared
with the one-chip build in the same process, and a few requests served
through the gateway on the sharded build.

Timings printed on the way are smoke output, not benchmark numbers.  The
last line of stdout is the verdict, one JSON object.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded path on four chips

The compile cache follows JAX_COMPILATION_CACHE_DIR when it is set, and is
otherwise kept in .jax_cache/ at the root of the checkout.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent

N_TOTAL, N_CONN = 100_000, 1_000
# On a v5e the reference backend's ELL scatter took ~0.46 s per step of
# this network; the step counts below keep one smoke run within a few
# minutes of the chip.
OFFLINE_STEPS = 500
RATE_BAND_HZ = (1.0, 100.0)     # neither silent nor saturated
KERNEL_TOL = 1e-5               # rtol and atol, the kernels' contract
KERNEL_SPIKE_P = 0.05           # firing fraction of the kernel probes
DELAY_SLOTS = 16
SERVE_STREAMS = 4
SERVE_CHUNK = 5
SERVE_STEPS = 10                # two chunks per request
SERVE_REQUESTS = 4
SHARDED_STEPS = 30


class SmokeFailure(Exception):
    """A phase produced a wrong result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from its
    monitoring events while the clock is installed."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def timed(clock: CompileClock, fn, *args, **kwargs):
    """(result, wall seconds, compile seconds) of fn, blocked until ready."""
    c0, t0 = clock.seconds, time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0, clock.seconds - c0


def device_check(chips: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's first device is {d0.platform!r} "
            f"({d0.device_kind}); this smoke run needs the chip")
    if len(devs) < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} devices, "
                           f"JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def build(cfg, clock: CompileClock, mesh=None):
    from repro.core.models.izhikevich_net import compile_model
    model, wall, comp = timed(
        clock, lambda: _ready_model(compile_model(cfg, mesh=mesh,
                                                  init="device")))
    slots = sum(g.ell.n_pre * g.ell.max_conn for g in model.network.synapses)
    log(f"build ({'sharded' if mesh is not None else 'one device'}): "
        f"{wall:.2f} s wall, {comp:.2f} s of it compiling; "
        f"{len(model.network.synapses)} synapse groups, {slots} ELL slots "
        "(smoke timing)")
    return model


def _ready_model(model):
    jax.block_until_ready([(g.ell.g, g.ell.post_ind, g.ell.valid)
                           for g in model.network.synapses])
    return model


def offline_phase(model, n_steps: int, clock: CompileClock):
    """Phase 2: a long offline run, finite, rates inside the band."""
    res, wall, comp = timed(clock, model.run, n_steps)
    rates = {k: float(v) for k, v in res.rates_hz.items()}
    log(f"offline run: {n_steps} steps in {wall:.2f} s wall, "
        f"{comp:.2f} s of it compiling, {wall - comp:.2f} s running "
        "(smoke timing)")
    log(f"offline run: finite={bool(res.finite)} rates_hz="
        + ", ".join(f"{k}={v:.3f}" for k, v in sorted(rates.items())))
    check(bool(res.finite), "offline run is not finite")
    lo, hi = RATE_BAND_HZ
    for k, v in rates.items():
        check(lo <= v <= hi, f"population {k!r} rate {v:.3f} Hz outside "
              f"the band [{lo}, {hi}] Hz")
    return res


def _agree(name: str, out, ref) -> None:
    out, ref = np.asarray(out), np.asarray(ref)
    check(out.shape == ref.shape, f"{name}: shape {out.shape} != {ref.shape}")
    err = float(np.max(np.abs(out - ref))) if out.size else 0.0
    scale = float(np.max(np.abs(ref))) if ref.size else 0.0
    ok = bool(np.allclose(out, ref, rtol=KERNEL_TOL, atol=KERNEL_TOL))
    log(f"kernel {name}: max|pallas-ref|={err:.3e} (max|ref|={scale:.3e}) "
        f"{'within' if ok else 'OUTSIDE'} rtol=atol={KERNEL_TOL:g}")
    check(ok, f"kernel {name} disagrees with kernels/ref.py")


def kernel_phase(model, seed: int = 0) -> None:
    """Phase 3a: compiled Pallas kernels vs the reference, at the model's
    group shapes, event-capacity shapes and one fused-delay shape."""
    from repro.kernels import ref as R
    from repro.kernels.ell_spmv import ell_spmv_delay_pallas, ell_spmv_pallas

    key = jax.random.PRNGKey(seed)
    for i, grp in enumerate(model.network.synapses):
        ell = grp.ell
        spk = (jax.random.uniform(jax.random.fold_in(key, i), (1, ell.n_pre))
               < KERNEL_SPIKE_P).astype(jnp.float32)
        shape = f"{grp.name} ({ell.n_pre}, {ell.max_conn}, {ell.n_post})"
        _agree(shape, ell_spmv_pallas(ell.g, ell.post_ind, ell.valid, spk,
                                      n_post=ell.n_post),
               R.ell_spmv_ref(ell.g, ell.post_ind, ell.valid, spk,
                              ell.n_post))
        cap = grp.event_capacity
        if cap:
            # the compacted problem: `cap` rows, the spiking ones first and
            # a silent tail, as ops.ell_spmv_event hands it to the kernel
            live = (jnp.arange(cap) < cap // 4)[None, :]
            spk_c = jnp.where(live, 1.0, 0.0).astype(jnp.float32)
            rows = slice(0, cap)
            _agree(f"{grp.name} event ({cap}, {ell.max_conn}, {ell.n_post})",
                   ell_spmv_pallas(ell.g[rows], ell.post_ind[rows],
                                   ell.valid[rows], spk_c,
                                   n_post=ell.n_post),
                   R.ell_spmv_ref(ell.g[rows], ell.post_ind[rows],
                                  ell.valid[rows], spk_c, ell.n_post))

    # fused delay kernel on the smallest group with random per-synapse
    # delays over DELAY_SLOTS ring slots
    grp = min(model.network.synapses, key=lambda g: g.ell.n_pre * g.ell.n_post)
    ell = grp.ell
    dkey, skey = jax.random.split(jax.random.fold_in(key, 99))
    delay = jax.random.randint(dkey, ell.g.shape, 0, DELAY_SLOTS, jnp.int32)
    spk = (jax.random.uniform(skey, (1, ell.n_pre))
           < KERNEL_SPIKE_P).astype(jnp.float32)
    _agree(f"{grp.name} delay ({ell.n_pre}, {ell.max_conn}, {ell.n_post}, "
           f"n_slots={DELAY_SLOTS})",
           ell_spmv_delay_pallas(ell.g, ell.post_ind, ell.valid, delay, spk,
                                 n_post=ell.n_post, n_slots=DELAY_SLOTS),
           R.ell_spmv_delay_ref(ell.g, ell.post_ind, ell.valid, delay, spk,
                                ell.n_post, DELAY_SLOTS))


def _stim(model, seed: int, n_steps: int = SERVE_STEPS) -> dict:
    """A served request's stimulus: noise into the inhibitory population."""
    n_inh = model.network.populations["inh"].n
    rng = np.random.default_rng(seed)
    return {"inh": np.round(2.0 * rng.standard_normal((n_steps, n_inh)),
                            3).astype(np.float32)}


def pallas_model_phase(cfg, ref_model, clock: CompileClock) -> None:
    """Phase 3b: the full model on the Pallas backend vs the reference
    backend over one served request's window (the reference program is
    the one the serving check reuses).  The backend is read when the run
    is traced, so the Pallas model is a build of its own, traced while
    REPRO_USE_PALLAS=1."""
    import os

    from repro.kernels import ops
    stim = _stim(ref_model, 0)
    prev = os.environ.get("REPRO_USE_PALLAS")
    os.environ["REPRO_USE_PALLAS"] = "1"
    try:
        check(ops.backend() == "pallas", "REPRO_USE_PALLAS=1 not honoured")
        pmodel = build(cfg, clock)
        pres, wall, comp = timed(clock, pmodel.run, SERVE_STEPS, stim=stim)
    finally:
        if prev is None:
            del os.environ["REPRO_USE_PALLAS"]
        else:
            os.environ["REPRO_USE_PALLAS"] = prev
    del pmodel
    rres = ref_model.run(SERVE_STEPS, stim=stim)
    log(f"pallas backend: {SERVE_STEPS} steps in {wall:.2f} s wall, "
        f"{comp:.2f} s of it compiling (smoke timing)")
    for k in sorted(rres.spike_counts):
        a = np.asarray(pres.spike_counts[k])
        b = np.asarray(rres.spike_counts[k])
        diff = int(np.count_nonzero(a != b))
        log(f"pallas vs ref, {SERVE_STEPS} steps, population {k!r}: "
            f"{int(a.sum())} vs {int(b.sum())} spikes, {diff} neurons differ")
        check(diff == 0, f"population {k!r}: Pallas and reference backends "
              f"disagree on {diff} neurons' spike counts")
    check(bool(pres.finite), "Pallas-backend run is not finite")


async def _http(host: str, port: int, method: str, path: str,
                body: dict | None = None):
    reader, writer = await asyncio.open_connection(host, port)
    payload = b"" if body is None else json.dumps(body).encode()
    writer.write((f"{method} {path} HTTP/1.1\r\nHost: smoke\r\n"
                  f"Content-Length: {len(payload)}\r\n\r\n").encode()
                 + payload)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, data = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), data


def serving_phase(model, clock: CompileClock, n_requests: int = SERVE_REQUESTS,
                  n_steps: int = SERVE_STEPS, tag: str = "") -> None:
    """Phase 4: the model behind the gateway's HTTP front door on
    localhost; every request answers 200 and one stream is exact against
    its offline run."""
    from repro.launch.gateway import Gateway
    from repro.launch.gateway_http import GatewayHTTP
    from repro.launch.snn_serve import _check_exact

    gw = Gateway(chunk=SERVE_CHUNK, buckets=(SERVE_STREAMS,),
                 max_queue=4 * SERVE_STREAMS)
    _, wall, comp = timed(clock, gw.register, "izhikevich", model,
                          stim_pops=("inh",))
    log(f"{tag}serving: gateway registered and warm in {wall:.2f} s wall, "
        f"{comp:.2f} s of it compiling (smoke timing)")
    bodies = [{"model": "izhikevich", "n_steps": n_steps, "seed": 100 + i,
               "stim": {k: v.tolist()
                        for k, v in _stim(model, i, n_steps).items()}}
              for i in range(n_requests)]

    async def scenario():
        srv = GatewayHTTP(gw, "127.0.0.1", 0)
        host, port = await srv.start()
        try:
            replies = await asyncio.gather(
                *(_http(host, port, "POST", "/v1/simulate", b)
                  for b in bodies))
            health = await _http(host, port, "GET", "/healthz")
        finally:
            await srv.stop()
        return replies, health

    t0 = time.perf_counter()
    replies, (h_status, h_body) = asyncio.run(scenario())
    wall = time.perf_counter() - t0
    statuses = [s for s, _ in replies]
    log(f"{tag}serving: {n_requests} POST /v1/simulate of {n_steps} steps "
        f"over HTTP in {wall:.2f} s wall, statuses {statuses} "
        "(smoke timing)")
    check(gw.failure is None, f"gateway failed: {gw.failure}")
    check(h_status == 200, f"/healthz answered {h_status}: {h_body[:200]!r}")
    for (status, data), b in zip(replies, bodies):
        check(status == 200, f"POST /v1/simulate answered {status}: "
              f"{data[:300]!r}")
        out = json.loads(data)
        check(out["status"] == "done" and out["steps_served"] == n_steps,
              f"request seed={b['seed']} ended {out['status']} after "
              f"{out['steps_served']}/{n_steps} steps")

    # one stream against its offline run (same stim, same seed)
    done = {r.seed: r for r in gw.collect_finished()}
    first = json.loads(replies[0][1])
    req = done[bodies[0]["seed"]]
    for k, v in req.spike_counts.items():
        check(first["spike_counts"][k] == np.asarray(v).tolist(),
              f"HTTP reply and gateway record differ for population {k!r}")
    failures = _check_exact(model, req)
    total = {k: int(np.asarray(v).sum()) for k, v in req.spike_counts.items()}
    log(f"{tag}serving: stream seed={req.seed} spikes {total}; served vs "
        f"offline model.run: {'bit-exact' if not failures else failures}")
    check(not failures, "; ".join(failures))


def sharded_phase(cfg, clock: CompileClock, n_chips: int) -> None:
    """--chips 4: the network on an n_chips mesh against the one-chip build
    in the same process, then a few requests through the gateway on the
    sharded build."""
    from repro.launch.mesh import make_snn_mesh

    mesh = make_snn_mesh(n_chips)
    sharded = build(cfg, clock, mesh=mesh)
    single = build(cfg, clock)

    devices = set(mesh.devices.flat)
    for gname, blk in sharded.engine.blocks.items():
        for name, arr in blk.items():
            placed = {s.device for s in arr.addressable_shards}
            check(placed == devices and len(arr.addressable_shards) == n_chips,
                  f"connectivity block {gname}/{name} is on "
                  f"{sorted(str(d) for d in placed)}, not spread over the "
                  f"{n_chips} mesh devices")
    log(f"sharded build: every connectivity block of "
        f"{len(sharded.engine.blocks)} groups has one shard on each of the "
        f"{n_chips} devices")

    sres, wall, comp = timed(clock, sharded.run, SHARDED_STEPS)
    log(f"sharded run: {SHARDED_STEPS} steps in {wall:.2f} s wall, "
        f"{comp:.2f} s of it compiling (smoke timing)")
    for k, v in sres.state.neurons.items():
        for var, arr in v.items():
            placed = {s.device for s in arr.addressable_shards}
            check(placed == devices, f"state {k}.{var} is on "
                  f"{len(placed)} devices, not {n_chips}")
    ores = single.run(SHARDED_STEPS)
    check(bool(sres.finite) and bool(ores.finite), "a run is not finite")
    for k in sorted(ores.spike_counts):
        a = np.asarray(sres.spike_counts[k])
        b = np.asarray(ores.spike_counts[k])
        diff = int(np.count_nonzero(a != b))
        log(f"{n_chips} chips vs 1 chip, {SHARDED_STEPS} steps, population "
            f"{k!r}: {int(a.sum())} vs {int(b.sum())} spikes, {diff} neurons "
            "differ")
        check(diff == 0, f"population {k!r}: the sharded build disagrees "
              f"with the one-chip build on {diff} neurons")
    del single, ores
    serving_phase(sharded, clock, tag=f"{n_chips}-chip ")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a 4-chip mesh")
    args = ap.parse_args(argv)

    try:
        device = device_check(args.chips)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.models.izhikevich_net import IzhikevichNetConfig
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    log(f"jax {jax.__version__}; kernel backend: {ops.backend()}; "
        f"compile cache: {cache}")

    clock = CompileClock()
    cfg = IzhikevichNetConfig(n_total=N_TOTAL, n_conn=N_CONN)
    t0 = time.perf_counter()
    try:
        if args.chips > 1:
            sharded_phase(cfg, clock, args.chips)
        else:
            model = build(cfg, clock)
            offline_phase(model, OFFLINE_STEPS, clock)
            kernel_phase(model)
            pallas_model_phase(cfg, model, clock)
            serving_phase(model, clock)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s "
        f"({clock.seconds:.1f} s compiling)")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
