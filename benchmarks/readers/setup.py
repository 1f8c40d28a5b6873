"""Set-up layer (``FedEngine.__init__`` and every program's compile): the
engine's own event stream, ``events_engine.jsonl`` in the run's telemetry
directory (``harness.setup_engine`` names it ``<out_dir>/telemetry``, beside
``harness.window``'s ``<out_dir>/trace``). The engine sends its
construction's spans once, at its first run's start: the ``setup`` phase and
its children ``setup/data``, ``setup/model_init``, ``setup/remat_saved``
and ``setup/eval_batches``. Every ``phase`` event carries the compile counts
of its own span (``trace_us``, ``lower_us``, ``compile_us``,
``cache_misses``; a parent's counts leave out its children's), so a sum
over the stream counts each compile once. The window
compiles nothing (``compiles_in_window``, limit 0): the sums are set-up's.

A program without the construction's spans (no ``setup`` event) or a run
without the stream has nothing to read: every reader returns None and the
result line leaves the metric out. Standard library only."""

import json
import os


def _phases(ctx):
    """The stream's ``phase`` events, or None without a ``setup`` event."""
    path = os.path.join(os.path.dirname(ctx["trace_dir"]), "telemetry",
                        "events_engine.jsonl")
    try:
        with open(path) as f:
            lines = f.read().splitlines()
    except OSError:
        return None
    events = []
    for line in lines:
        try:
            ev = json.loads(line)
        except ValueError:  # a torn last line
            continue
        if isinstance(ev, dict) and ev.get("ev") == "phase":
            events.append(ev)
    if not any(e.get("name") == "setup" for e in events):
        return None
    return events


def _wall_s(ctx, *names):
    events = _phases(ctx)
    if events is None:
        return None
    return sum(e["wall_s"] for e in events if e.get("name") in names)


def _count(ctx, *keys):
    events = _phases(ctx)
    if events is None:
        return None
    return sum(e.get(k, 0) for e in events for k in keys)


def engine_s(ctx):
    """The ``setup`` phase: ``FedEngine.__init__`` whole."""
    return _wall_s(ctx, "setup")


def data_s(ctx):
    """The engine's corpus, its tokenizing and its evaluation batches (the
    harness then hands its own batches over)."""
    return _wall_s(ctx, "setup/data", "setup/eval_batches")


def model_init_s(ctx):
    """The model's build and the engine's own draw of its base and adapters
    (the harness then hands its own weights over)."""
    return _wall_s(ctx, "setup/model_init")


def lower_s(ctx):
    """Tracing and lowering to MLIR, the Pallas kernels' Mosaic lowering
    among it, over every span of the stream."""
    n = _count(ctx, "trace_us", "lower_us")
    return None if n is None else n / 1e6


def compile_s(ctx):
    """XLA's compiles, or the persistent cache's key and load on a hit, over
    every span of the stream."""
    n = _count(ctx, "compile_us")
    return None if n is None else n / 1e6


def cache_misses(ctx):
    """Programs compiled and written to the persistent cache rather than
    loaded from it: 0 on a warm run."""
    return _count(ctx, "cache_misses")
