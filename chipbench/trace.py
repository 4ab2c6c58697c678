"""Reduction of a profiler trace to the per-layer numbers.

A traced run records the window with ``jax.profiler``; the XSpace it
writes is read with ``jax.profiler.ProfileData`` alone.  From it:

* the window: the benchmark's ``chipbench.window`` host span;
* busy: the union of the intervals in which an op ran on a device (each
  device's ``XLA Ops`` line), clipped to the window and averaged over the
  devices;
* each op's self time: a control-flow op (``while``, ``conditional``)
  spans the ops of its body on the same line, so an op's time is its span
  less the spans of the ops nested in it;
* propagation's time: ops whose HLO text in the trace names a scatter, a
  gather, a dot or convolution, or a Pallas ``ell_spmv`` kernel, and the
  fusions XLA's TPU backend emits with its custom emitters
  (``kind=kCustom``: in these programs the scatter-add, the row gathers
  and the event compaction of the event path).  Until the program names
  its scopes, the opcode is all the trace says;
* idle gaps: the stretches of the window in which no op ran, each named
  after the benchmark's host span (``dispatch``, ``fetch``) that overlaps
  it most, or ``other``.
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


def short_name(name: str) -> str:
    """``%fusion.18 = f32[80000] fusion(...), kind=kCustom, ...`` ->
    ``fusion.18 (kCustom)``: the instruction and its fusion kind."""
    head = name.split(" = ", 1)[0].lstrip("%")
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


def reduce_profile(pd, span_prefix: str) -> dict:
    """The per-layer numbers of one ProfileData (see the module doc)."""
    host = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(span_prefix):
                    host.append((ev.name[len(span_prefix):], ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"no {span_prefix}{WINDOW} span in the trace")
    w0, w1 = windows[0]
    spans = [(n, s, e) for n, s, e in host if n != WINDOW]

    devices = [p for p in pd.planes
               if p.name.startswith("/device:") and _op_lines(p)]
    if not devices:
        raise ValueError("no device op line in the trace")
    op_s = defaultdict(float)
    prop_ns = busy_ns = 0.0
    gaps = []
    for plane in devices:
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
                op_s[short_name(name)] += own / 1e9
                if is_propagation(name):
                    prop_ns += own
        merged = _merge(ivs)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n = len(devices)
    labelled = sorted(((_label(s, e, spans), (e - s) / 1e9)
                       for s, e in gaps), key=lambda g: -g[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "prop_s": prop_ns / 1e9 / n,
        "device_ops": [[k, v / n] for k, v in
                       sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[k, v / n] for k, v in labelled[:TOP]],
    }


def _label(s: float, e: float, spans) -> str:
    best, most = "other", 0.0
    for name, a, b in spans:
        ov = min(b, e) - max(a, s)
        if ov > most:
            best, most = name, ov
    return best


def reduce_dir(logdir: str, span_prefix: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xspace(logdir)),
                          span_prefix)
