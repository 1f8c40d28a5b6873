"""Tracing / profiling (SURVEY.md §5: the reference has none beyond coarse
psutil+wall-clock — this subsystem is the rebuild's upgrade, kept optional).

Three layers:

- :class:`StepClock` — the round's span tree on the host: the engine's
  phases (``control_plane``, ``round_program``, ``ledger``, ``eval``) and
  the spans inside them, each with a parent, a start, optional counts and a
  ``jax.profiler.TraceAnnotation`` that puts it on the profiler's clock
  beside the device operations; always on, mean/p50/p95 summaries.
- :func:`trace` — context manager around ``jax.profiler`` emitting a
  TensorBoard-loadable trace directory for the wrapped region.
- :func:`scope` — the device side of the tree: ``jax.named_scope`` names
  for the round programs' stages, which reach each operation's ``op_name``
  in the compiled program and so the profiler's device events.
"""

from __future__ import annotations

import contextlib
import time
from collections import Counter, defaultdict
from typing import Dict, List, Optional

from bcfl_tpu.telemetry import events as _telemetry


def _stats(xs: List[float]) -> Dict[str, float]:
    import numpy as np

    a = np.asarray(xs)
    return {
        "count": int(a.size),
        "total_s": float(a.sum()),
        "mean_s": float(a.mean()),
        "p50_s": float(np.percentile(a, 50)),
        "p95_s": float(np.percentile(a, 95)),
    }


class StepClock:
    """One span tree per round: ``with clock.phase("round_program"): ...``
    for the engine's phases, ``with clock.span("inputs") as counts: ...`` for
    what happens inside (and between) them.

    A span's name is its path under the span that was open when it opened
    (``round_program/inputs``); a phase keeps its bare name wherever it
    opens (``ledger`` opens inside ``round_program`` and is still
    ``ledger``) and records that parent beside it. ``span`` yields a dict
    for the span's integer counts (``h2d_bytes``, ``d2h_bytes``,
    ``compiled``), filled in by the caller before the span closes;
    ``count`` adds to the innermost open span's from the code that does the
    counted work (``key_programs``).

    Every completed span also feeds the run's event stream as a typed
    ``phase`` event (bcfl_tpu.telemetry, OBSERVABILITY.md) — a no-op unless
    the run installed an event writer — and sits in the profiler's trace as
    ``fed.<name>`` while one is being taken (a flag check otherwise), so the
    pre-telemetry cost model is unchanged.

    ``summary()`` is the flat legacy view: one entry per phase, whatever
    its nesting, with today's five fields. Spans appear only under their
    parent's entry (``summary()[parent]["children"][child]``, beside the
    parent's ``self_s``); a span opened under no phase reaches the stream
    and the profiler but not the summary."""

    def __init__(self):
        import jax

        self._annotation = jax.profiler.TraceAnnotation
        self._times: Dict[str, List[float]] = defaultdict(list)
        # parent name -> child's own name -> durations; (parent, child) ->
        # summed counts
        self._children: Dict[str, Dict[str, List[float]]] = defaultdict(
            lambda: defaultdict(list))
        self._counts: Dict[tuple, Counter] = defaultdict(Counter)
        # the open spans, innermost last: (name, counts)
        self._stack: List[tuple] = []
        # the round the open spans belong to, set by the round loop: an id
        # on every annotation, so a trace's spans can be told apart by round
        self.round: Optional[int] = None

    def phase(self, name: str):
        return self._open(name, name, None, {})

    def span(self, name: str, program: Optional[str] = None, **counts):
        parent = self._stack[-1][0] if self._stack else None
        path = name if parent is None else f"{parent}/{name}"
        return self._open(path, None, program, counts)

    def count(self, key: str, n: int = 1) -> None:
        """Add ``n`` to count ``key`` of the innermost open span (no span
        open: nothing is counted)."""
        if self._stack:
            counts = self._stack[-1][1]
            counts[key] = counts.get(key, 0) + n

    @contextlib.contextmanager
    def _open(self, name, phase, program, counts):
        parent = self._stack[-1][0] if self._stack else None
        ids = {} if self.round is None else {"round": int(self.round)}
        if program is not None:
            ids["program"] = program
        self._stack.append((name, counts))
        t0_ns = time.time_ns()
        t0 = time.perf_counter()
        try:
            with self._annotation("fed." + name, **ids):
                yield counts
        finally:
            dt = time.perf_counter() - t0
            self._stack.pop()
            if phase is not None:
                self._times[phase].append(dt)
            if parent is not None:
                own = name.rsplit("/", 1)[-1]
                self._children[parent][own].append(dt)
                self._counts[parent, own].update(counts)
            _telemetry.emit("phase", name=name, wall_s=dt, parent=parent,
                            t0_ns=t0_ns, **ids, **counts)

    def summary(self) -> Dict[str, Dict]:
        out = {}
        for name, xs in self._times.items():
            entry = out[name] = _stats(xs)
            if name in self._children:
                kids = entry["children"] = {
                    k: dict(_stats(v), **self._counts[name, k])
                    for k, v in self._children[name].items()}
                entry["self_s"] = entry["total_s"] - sum(
                    c["total_s"] for c in kids.values())
        return out


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """``jax.profiler`` trace of the wrapped region (no-op if ``log_dir`` is
    falsy). View with TensorBoard's profile plugin or Perfetto."""
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def scope(stage: str):
    """``jax.named_scope("fed.<stage>")``, as a context manager or a
    decorator: the one place the round programs' stage names are made
    (``forward``, ``loss``, ``optimizer``, ``aggregate``, ``fingerprint``,
    ``transport``, ``codec.encode``, ...; OBSERVABILITY.md lists them). A
    scope changes an operation's metadata and nothing else. ``jax.vmap``,
    ``scan`` and ``grad`` wrap the name (``vmap(jvp(fed.forward))``,
    ``transpose(jvp(fed.forward))``), so readers match it as a substring."""
    import jax

    return jax.named_scope("fed." + stage)
