"""Tracing / profiling (SURVEY.md §5: the reference has none beyond coarse
psutil+wall-clock — this subsystem is the rebuild's upgrade, kept optional).

Three layers:

- :class:`StepClock` — the round's span tree on the host: the engine's
  phases (``setup``, ``control_plane``, ``round_program``, ``ledger``,
  ``eval``) and the spans inside them, each with a parent, a start,
  optional counts and a ``jax.profiler.TraceAnnotation`` that puts it on
  the profiler's clock beside the device operations; always on,
  mean/p50/p95 summaries. JAX's own compile events (tracing, lowering, the
  backend's compile or the persistent cache's load) add to the innermost
  span open on the thread that compiles (:data:`COMPILE_COUNTS`).
- :func:`trace` — context manager around ``jax.profiler`` emitting a
  TensorBoard-loadable trace directory for the wrapped region.
- :func:`scope` — the device side of the tree: ``jax.named_scope`` names
  for the round programs' stages, which reach each operation's ``op_name``
  in the compiled program and so the profiler's device events.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import Counter, defaultdict
from typing import Dict, List, Optional

from bcfl_tpu.telemetry import events as _telemetry

#: ``jax.monitoring`` event -> the span count it adds to: durations in whole
#: microseconds, the cache's miss by one. ``compile_us`` wraps the persistent
#: cache's lookup, so it is XLA's compile on a miss and the key plus the load
#: on a hit; ``cache_misses`` counts the programs compiled and written to the
#: cache.
COMPILE_COUNTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_us",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_us",
    "/jax/core/compile/backend_compile_duration": "compile_us",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

# per thread: the counts of the innermost span open on it (``counts``) and
# how deep each timed compile stage is nested (``depth``: a jit traced inside
# another's trace reports its own duration, inside the outer one's)
_local = threading.local()
_registration = threading.Lock()
_listening = False


def _nesting():
    depth = getattr(_local, "depth", None)
    if depth is None:
        depth = _local.depth = {}
    return depth


def _on_stage_start(event, _value, **_kw):
    """A timed stage opens (``jax.monitoring`` records its start)."""
    if event in COMPILE_COUNTS:
        depth = _nesting()
        depth[event] = depth.get(event, 0) + 1


def _on_duration(event, duration_secs, **_kw):
    """A timed stage closed: counted where it is the outermost of its kind
    on this thread and a span is open here."""
    key = COMPILE_COUNTS.get(event)
    if key is None:
        return
    depth = _nesting()
    if event in depth:  # a stage that announced its start
        depth[event] = max(depth[event] - 1, 0)
        if depth[event]:
            return
    counts = getattr(_local, "counts", None)
    if counts is not None:
        counts[key] = counts.get(key, 0) + int(round(duration_secs * 1e6))


def _on_event(event, **_kw):
    key = COMPILE_COUNTS.get(event)
    counts = getattr(_local, "counts", None)
    if key is not None and counts is not None:
        counts[key] = counts.get(key, 0) + 1


def _listen() -> None:
    """Register the process's one set of compile listeners, once."""
    global _listening
    with _registration:
        if _listening:
            return
        import jax

        jax.monitoring.register_scalar_listener(_on_stage_start)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


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
    counted work (``key_programs``); JAX's compile events add theirs
    (:data:`COMPILE_COUNTS`) to the innermost span open on the compiling
    thread, whichever clock it belongs to, and to nothing where none is.

    Every completed span also feeds the run's event stream as a typed
    ``phase`` event (bcfl_tpu.telemetry, OBSERVABILITY.md) — a no-op unless
    the run installed an event writer — and sits in the profiler's trace as
    ``fed.<name>`` while one is being taken (a flag check otherwise), so the
    pre-telemetry cost model is unchanged. A clock made with ``hold=True``
    keeps those events instead, until :meth:`emit_held` sends them (the
    engine's construction, which runs before any stream exists).

    ``summary()`` is the flat legacy view: one entry per phase, whatever
    its nesting, with today's five fields. Spans appear only under their
    parent's entry (``summary()[parent]["children"][child]``, beside the
    parent's ``self_s``); a span opened under no phase reaches the stream
    and the profiler but not the summary."""

    def __init__(self, hold: bool = False):
        import jax

        _listen()
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
        # ``hold``: the completed spans' events, kept until ``emit_held``
        self._held: Optional[List[tuple]] = [] if hold else None

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
        outer = getattr(_local, "counts", None)
        _local.counts = counts
        t0_ns = time.time_ns()
        t0 = time.perf_counter()
        try:
            with self._annotation("fed." + name, **ids):
                yield counts
        finally:
            dt = time.perf_counter() - t0
            _local.counts = outer
            self._stack.pop()
            if phase is not None:
                self._times[phase].append(dt)
            if parent is not None:
                own = name.rsplit("/", 1)[-1]
                self._children[parent][own].append(dt)
                self._counts[parent, own].update(counts)
            event = (name, dt, parent, t0_ns, ids, counts)
            if self._held is None:
                self._emit(*event)
            else:
                self._held.append(event)

    @staticmethod
    def _emit(name, wall_s, parent, t0_ns, ids, counts):
        _telemetry.emit("phase", name=name, wall_s=wall_s, parent=parent,
                        t0_ns=t0_ns, **ids, **counts)

    def emit_held(self) -> None:
        """Send the events a ``hold=True`` clock kept, once, each with its
        own start and parent (a no-op unless a writer is installed; nothing
        is kept for a second call)."""
        held, self._held = self._held or [], []
        for event in held:
            self._emit(*event)

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
