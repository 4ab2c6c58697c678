"""Reduction of a profiler trace to the per-layer numbers.

A traced run records the window with ``jax.profiler``; the XSpace it
writes is read with ``jax.profiler.ProfileData`` alone.  From it:

* the window: the benchmark's ``chipbench.window`` host span;
* busy: the union of the intervals in which an op ran on a device (each
  device's ``XLA Ops`` line), clipped to the window and averaged over the
  devices;
* each op's self time: a control-flow op (``while``, ``conditional``)
  spans the ops of its body on the same line, so an op's time is its span
  less the spans of the ops nested in it.  A key function classes each op
  by its module (the enclosing event on the device's ``XLA Modules`` line,
  ``jit__run(1538...)`` -> ``jit__run``) and its HLO text, and self times
  are summed by class: by default the op's short name
  (``device_ops``), or its named scope (``chipbench.scopes``);
* propagation's time: ops whose HLO text in the trace names a scatter, a
  gather, a dot or convolution, or a Pallas ``ell_spmv`` kernel, and the
  fusions XLA's TPU backend emits with its custom emitters
  (``kind=kCustom``: in these programs the scatter-add, the row gathers
  and the event compaction of the event path);
* idle gaps: the stretches of the window in which no op ran, each named
  after the host span that overlaps it most: of the benchmark's
  (``dispatch``, ``fetch``; else ``other``) in ``idle_gaps``, of the
  program's (``repro.<name>``, the profiler annotation of
  ``repro.obs.trace.span``; of equal overlaps the innermost, else
  ``none``) in ``idle_gaps_program``.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict

import jax

TOP = 10
WINDOW = "window"
PROGRAM_PREFIX = "repro."  # the program's profiler spans
_FINGERPRINT = re.compile(r"\(\d+\)$")
PROP = re.compile(r"scatter|gather|\bdot\b|dot[._-]|convolution|ell_spmv"
                  r"|kind=kCustom", re.IGNORECASE)


@contextlib.contextmanager
def profiled(logdir):
    """Profile the block into ``logdir``; a no-op for None.  Raises when
    the profiler cannot start: a traced run must not pass without its
    trace."""
    if logdir is None:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xspace(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(files, key=os.path.getmtime)


def is_propagation(name: str) -> bool:
    return bool(PROP.search(name))


def instruction(name: str) -> str:
    """``%fusion.18 = f32[80000] fusion(...), ...`` -> ``fusion.18``."""
    return name.split(" = ", 1)[0].lstrip("%")


def short_name(name: str) -> str:
    """``%fusion.18 = f32[80000] fusion(...), kind=kCustom, ...`` ->
    ``fusion.18 (kCustom)``: the instruction and its fusion kind."""
    head = instruction(name)
    kind = re.search(r"kind=(k\w+)", name)
    return f"{head} ({kind.group(1)})" if kind else head


def self_times(events):
    """(start, end, self seconds, name) of each op on one line; an op's
    self time excludes the ops nested inside its span."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][1]:
            stack.pop()
        rec = [s, e, e - s, name]
        if stack:
            stack[-1][2] -= e - s
        out.append(rec)
        stack.append(rec)
    return out


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_lines(plane):
    return [ln for ln in plane.lines if ln.name == "XLA Ops"]


def _module_name(event_name: str) -> str:
    """``jit__run(15388027131515875373)`` -> ``jit__run``."""
    return _FINGERPRINT.sub("", event_name)


def _enclosing(modules, s: float) -> str:
    for a, b, name in modules:
        if a <= s < b:
            return name
    return ""


def by_short_name(module: str, name: str) -> str:
    return short_name(name)


def reduce_profile(pd, span_prefix: str, key=by_short_name) -> dict:
    """The per-layer numbers of one ProfileData (see the module doc).
    ``key(module, op's HLO text)`` classes each op; ``ops_s`` holds the
    self seconds of every class, ``device_ops`` the longest."""
    host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events
            if ev.name.startswith((span_prefix, PROGRAM_PREFIX))]
    windows = [(s, e) for n, s, e in host if n == span_prefix + WINDOW]
    if not windows:
        raise ValueError(f"no {span_prefix}{WINDOW} span in the trace")
    w0, w1 = windows[0]
    bench = [(n[len(span_prefix):], s, e) for n, s, e in host
             if n.startswith(span_prefix) and n != span_prefix + WINDOW]
    program = [(n[len(PROGRAM_PREFIX):], s, e) for n, s, e in host
               if n.startswith(PROGRAM_PREFIX)]

    devices = [p for p in pd.planes
               if p.name.startswith("/device:") and _op_lines(p)]
    if not devices:
        raise ValueError("no device op line in the trace")
    ops_s = defaultdict(float)
    prop_ns = busy_ns = 0.0
    gaps = []
    for plane in devices:
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                          _module_name(ev.name))
                         for line in plane.lines
                         if line.name == "XLA Modules" for ev in line.events)
        ivs = []
        for line in _op_lines(plane):
            evs = []
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    evs.append((s, e, ev.name))
            for s, e, own, name in self_times(evs):
                ivs.append((s, e))
                ops_s[key(_enclosing(modules, s), name)] += own
                if is_propagation(name):
                    prop_ns += own
        merged = _merge(ivs)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n = len(devices)
    ops_s = {k: v / 1e9 / n for k, v in ops_s.items()}
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "prop_s": prop_ns / 1e9 / n,
        "ops_s": ops_s,
        "device_ops": top(ops_s.items()),
        "idle_gaps": _labelled(gaps, bench, "other", n),
        "idle_gaps_program": _labelled(gaps, program, "none", n),
    }


def top(items) -> list:
    """The TOP longest of (name, seconds) pairs, longest first."""
    return [[k, v] for k, v in sorted(items, key=lambda kv: -kv[1])[:TOP]]


def _labelled(gaps, spans, unlabelled: str, n: int) -> list:
    return top((_label(s, e, spans, unlabelled), (e - s) / 1e9 / n)
               for s, e in gaps)


def _label(s: float, e: float, spans, unlabelled: str) -> str:
    """The span overlapping [s, e) most; of equal overlaps the shortest
    (the innermost)."""
    best, most = unlabelled, (0.0, 0.0)
    for name, a, b in spans:
        ov = (min(b, e) - max(a, s), a - b)
        if ov[0] > 0 and ov > most:
            best, most = name, ov
    return best


def reduce_dir(logdir: str, span_prefix: str, key=by_short_name) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xspace(logdir)),
                          span_prefix, key)
