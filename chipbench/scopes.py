"""Device time by the program's named scopes.

The simulation step names its layers with ``jax.named_scope`` (the
vocabulary is in ``repro.obs.trace``'s docstring: ``prop/<group>`` with
``compact``, ``event``, ``fallback`` or ``dense`` below it,
``neuron/<pop>``, ``rng``, ``custom/<name>``, ``probe``, ``health``,
``exchange``).  A scope reaches the optimized HLO as the instruction's
``metadata={op_name="jit(_run)/while/body/.../prop/exc_exc/cond/
branch_1_fun/event/scatter-add"}``, and not the profiler's events: an
``XLA Ops`` event carries the instruction's HLO text without metadata.  So
the reduction maps each traced op to its scope through the HLO text of the
executable that ran (``CompiledModel.run_hlo``):

* the map: (module, instruction name) -> scope, from each instruction's
  ``op_name``.  A scope is the op_name's vocabulary segments alone (JAX's
  own ``while``, ``body``, ``cond``, ``branch_1_fun`` ... are dropped, as
  is the trailing primitive); where one layer's scope opens inside
  another's (the engine's pre-trace all-gather is ``exchange`` inside
  ``prop/<group>``), the innermost layer names it.  A fusion carries its
  root instruction's op_name, so a fusion whose instructions span two
  scopes is charged to its root's;
* each op's module is the event on the same device's ``XLA Modules`` line
  that encloses it (``jit__run(1538...)`` -> ``jit__run``);
* each op's self time (``chipbench.trace.self_times``) inside the
  benchmark's window goes to its scope, or to no scope: an instruction
  without a vocabulary segment (the scan's bookkeeping, copies XLA adds)
  or one of a module whose HLO text was not given;
* idle gaps are the window's stretches with no op on the device, each
  labelled by the program span (``repro.<name>``, the profiler annotation
  of ``repro.obs.trace.span``) that overlaps it most, the innermost on a
  tie, else ``none``.

Seconds are averaged over the devices, as ``chipbench.trace`` does.
"""

from __future__ import annotations

import re
from collections import defaultdict

from chipbench import trace as tr

PROGRAM_PREFIX = "repro."  # the program's profiler spans
LAYERS = ("prop", "neuron", "custom", "rng", "probe", "health", "exchange")
NAMED = ("prop", "neuron", "custom")      # followed by a name segment
PROP_PARTS = ("compact", "event", "fallback", "dense")
UNSCOPED = "none"

_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.MULTILINE)
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?\bmetadata=\{[^}]*?'
                    r'\bop_name="([^"]*)"', re.MULTILINE)
_FINGERPRINT = re.compile(r"\(\d+\)$")


def scope_of(op_name: str):
    """The vocabulary scope of one ``op_name``, or None."""
    segs = op_name.split("/")[:-1]          # the primitive is last
    scope, i = None, 0
    while i < len(segs):
        s = segs[i]
        if s in NAMED and i + 1 < len(segs):
            scope = [s, segs[i + 1]]
            i += 2
            continue
        if s in LAYERS and s not in NAMED:
            scope = [s]
        elif (scope is not None and scope[0] == "prop" and len(scope) == 2
              and s in PROP_PARTS):
            scope.append(s)
        i += 1
    return "/".join(scope) if scope else None


def hlo_scopes(text: str) -> dict:
    """{(module, instruction): scope or None} of HLO module text
    (``compile().as_text()``; several modules may follow each other)."""
    heads = [(m.start(), m.group(1)) for m in _MODULE.finditer(text)]
    out = {}
    for m in _INSTR.finditer(text):
        module = [name for at, name in heads if at <= m.start()]
        if module:
            out[(module[-1], m.group(1))] = scope_of(m.group(2))
    return out


def _module_name(event_name: str) -> str:
    """``jit__run(15388027131515875373)`` -> ``jit__run``."""
    return _FINGERPRINT.sub("", event_name)


def _enclosing(modules, s: float) -> str:
    for a, b, name in modules:
        if a <= s < b:
            return name
    return ""


def reduce_profile(pd, scopes: dict, span_prefix: str) -> dict:
    """Device seconds by scope, and idle gaps by program span, of one
    ProfileData inside the benchmark's window span (see the module doc).
    ``scopes`` is ``hlo_scopes(...)`` of the executables that ran."""
    host = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                host.append((ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in host if n == span_prefix + tr.WINDOW]
    if not windows:
        raise ValueError(f"no {span_prefix}{tr.WINDOW} span in the trace")
    w0, w1 = windows[0]
    spans = [(n[len(PROGRAM_PREFIX):], s, e) for n, s, e in host
             if n.startswith(PROGRAM_PREFIX)]

    devices = [p for p in pd.planes
               if p.name.startswith("/device:") and tr._op_lines(p)]
    if not devices:
        raise ValueError("no device op line in the trace")
    scope_s = defaultdict(float)
    busy_ns = 0.0
    gaps = []
    for plane in devices:
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                          _module_name(ev.name))
                         for line in plane.lines
                         if line.name == "XLA Modules" for ev in line.events)
        ivs = []
        for line in tr._op_lines(plane):
            evs = []
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    evs.append((s, e, ev.name))
            for s, e, own, name in tr.self_times(evs):
                ivs.append((s, e))
                instr = name.split(" = ", 1)[0].lstrip("%")
                key = (_enclosing(modules, s), instr)
                scope_s[scopes.get(key) or UNSCOPED] += own
        merged = tr._merge(ivs)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n = len(devices)
    labelled = sorted(((_label(s, e, spans), (e - s) / 1e9)
                       for s, e in gaps), key=lambda g: -g[1])
    unscoped = scope_s.pop(UNSCOPED, 0.0)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "unscoped_s": unscoped / 1e9 / n,
        "scope_s": {k: v / 1e9 / n for k, v in scope_s.items()},
        "scopes": [[k, v / 1e9 / n] for k, v in
                   sorted(scope_s.items(), key=lambda kv: -kv[1])[:tr.TOP]],
        "idle_gaps_program": [[k, v / n] for k, v in labelled[:tr.TOP]],
    }


def _label(s: float, e: float, spans) -> str:
    """The span overlapping [s, e) most; of equal overlaps the shortest
    (the innermost)."""
    best, most = UNSCOPED, (0.0, 0.0)
    for name, a, b in spans:
        ov = (min(b, e) - max(a, s), a - b)
        if ov[0] > 0 and ov > most:
            best, most = name, ov
    return best


def shares(r: dict):
    """The per-layer shares of a ``reduce_profile`` result, in percent;
    None where no op ran under a scope (a program without named scopes):

    * ``prop_scope_share``: device time under ``prop/*`` over busy time;
    * ``prop_fallback_share``: time under ``prop/*/fallback`` over the
      time under ``prop/*``;
    * ``neuron_update_share``: time under ``neuron/*`` over busy time;
    * ``unscoped_share``: busy time under no scope over busy time."""
    scoped = r["scope_s"]
    busy = r["busy_s"]
    if not scoped or busy <= 0:
        return None
    prop = sum(v for k, v in scoped.items() if k.startswith("prop/"))
    fallback = sum(v for k, v in scoped.items()
                   if k.startswith("prop/") and k.endswith("/fallback"))
    neuron = sum(v for k, v in scoped.items() if k.startswith("neuron/"))
    return {
        "prop_scope_share": 100.0 * prop / busy,
        "prop_fallback_share": 100.0 * fallback / prop if prop > 0 else 0.0,
        "neuron_update_share": 100.0 * neuron / busy,
        "unscoped_share": 100.0 * r["unscoped_s"] / busy,
    }


def reduce_dir(logdir: str, hlo_text: str, span_prefix: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(tr.find_xspace(logdir)),
                          hlo_scopes(hlo_text), span_prefix)
