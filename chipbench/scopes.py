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
* each op's self time inside the benchmark's window goes to its scope
  (``chipbench.trace.reduce_profile`` with the scope as its key: the op's
  module and instruction name), or to no scope: an instruction without a
  vocabulary segment (the scan's bookkeeping, copies XLA adds) or one of
  a module whose HLO text was not given;
* idle gaps are labelled by the program's spans (``idle_gaps_program``
  of that reduction).
"""

from __future__ import annotations

import re

from chipbench import trace as tr

LAYERS = ("prop", "neuron", "custom", "rng", "probe", "health", "exchange")
NAMED = ("prop", "neuron", "custom")      # followed by a name segment
PROP_PARTS = ("compact", "event", "fallback", "dense")
UNSCOPED = "none"

_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.MULTILINE)
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?\bmetadata=\{[^}]*?'
                    r'\bop_name="([^"]*)"', re.MULTILINE)


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


def reduce_profile(pd, scopes: dict, span_prefix: str) -> dict:
    """Device seconds by scope, and idle gaps by program span, of one
    ProfileData inside the benchmark's window span (see the module doc).
    ``scopes`` is ``hlo_scopes(...)`` of the executables that ran."""
    def scope(module: str, name: str) -> str:
        return scopes.get((module, tr.instruction(name))) or UNSCOPED

    r = tr.reduce_profile(pd, span_prefix, key=scope)
    scope_s = dict(r["ops_s"])
    unscoped = scope_s.pop(UNSCOPED, 0.0)
    return {
        "window_s": r["window_s"],
        "busy_s": r["busy_s"],
        "unscoped_s": unscoped,
        "scope_s": scope_s,
        "scopes": tr.top(scope_s.items()),
        "idle_gaps_program": r["idle_gaps_program"],
    }


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


def read(ctx: dict, name: str):
    """One share of a run's reduction by scope (``ctx["scopes"]``), or
    None where the run was not traced or ran no op under a scope."""
    r = ctx["scopes"]
    s = shares(r) if r is not None else None
    return s[name] if s is not None else None


def reduce_dir(logdir: str, hlo_text: str, span_prefix: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(tr.find_xspace(logdir)),
                          hlo_scopes(hlo_text), span_prefix)
