"""Wall-clock phase timing + optional jax.profiler capture hooks.

:class:`PhaseTimer` is the CLI-facing layer over :mod:`repro.obs.trace`:
phases are recorded both as trace spans (so they land in the exported
Chrome trace) and as a simple (name, seconds) table the CLIs print.

:func:`jax_profiler_trace` wraps ``jax.profiler.trace`` (XLA-level
timelines, TensorBoard-loadable).  A profiler that cannot start raises
:class:`ProfilerUnavailable`: a run that asked for a device trace must not
finish without one.
"""
from __future__ import annotations

import sys
import time
from contextlib import contextmanager
from typing import List, Optional, Tuple

from repro.obs import trace as _trace

__all__ = ["PhaseTimer", "ProfilerUnavailable", "jax_profiler_trace",
           "write_trace", "export_trace_cli"]


class ProfilerUnavailable(RuntimeError):
    """jax.profiler could not start a trace."""


class PhaseTimer:
    """Accumulates named wall-clock phases; each phase is also a span."""

    def __init__(self, collector: Optional[_trace.TraceCollector] = None):
        self._collector = collector or _trace.get_collector()
        self.phases: List[Tuple[str, float]] = []

    @contextmanager
    def phase(self, name: str, **args):
        t0 = time.perf_counter()
        with self._collector.span(name, **args):
            yield
        self.phases.append((name, time.perf_counter() - t0))

    def total(self) -> float:
        return sum(s for _, s in self.phases)

    def render(self) -> str:
        if not self.phases:
            return "(no phases recorded)"
        width = max(len(n) for n, _ in self.phases)
        lines = [f"  {n:<{width}}  {s * 1e3:10.2f} ms" for n, s in self.phases]
        lines.append(f"  {'total':<{width}}  {self.total() * 1e3:10.2f} ms")
        return "\n".join(lines)


@contextmanager
def jax_profiler_trace(logdir: str):
    """jax.profiler.trace(logdir); raises ProfilerUnavailable when the
    profiler cannot start."""
    import jax.profiler as _prof
    try:
        _prof.start_trace(logdir)
    except Exception as e:  # whatever the profiler backend raises
        raise ProfilerUnavailable(
            f"jax profiler could not start a trace in {logdir!r}: "
            f"{type(e).__name__}: {e}") from e
    try:
        yield
    finally:
        _prof.stop_trace()


def write_trace(path: str,
                collector: Optional[_trace.TraceCollector] = None) -> int:
    """Export the Chrome trace to ``path``; returns the event count.

    Raises OSError when the file cannot be written — callers (the CLIs)
    turn that into a non-zero exit instead of a teardown-swallowed error.
    """
    c = collector or _trace.get_collector()
    return c.export(path)


def export_trace_cli(path: str, tag: str,
                     collector: Optional[_trace.TraceCollector] = None
                     ) -> int:
    """Shared ``--trace FILE`` tail for the CLIs: export and report.

    Returns a process exit code — 0 on success (or empty ``path``), 1 with
    a clear stderr message when the trace file cannot be written.  The run
    itself already happened; only the export failed.
    """
    if not path:
        return 0
    try:
        n = write_trace(path, collector)
    except OSError as e:
        print(f"[{tag}] error: cannot write trace file {path!r}: {e}",
              file=sys.stderr)
        return 1
    print(f"[{tag}] wrote {n} trace events to {path} "
          "(open in chrome://tracing or Perfetto)")
    return 0
