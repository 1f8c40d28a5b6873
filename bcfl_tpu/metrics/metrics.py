"""Run metrics — the reference's observability surface (SURVEY.md §3.5), with
its bugs fixed but its metric set preserved for comparability:

- wall-clock latency in minutes (``server_IID_IMDB.py:221-224`` prints
  "Latency : X mins"),
- CPU overhead percent via psutil (``:59-63, 226-229``),
- memory overhead in GB — the reference captures ``memory_info_after``
  BEFORE training and ``memory_info_before`` after, so it usually prints a
  negative number (C11); here before is before and after is after,
- model size in GB (reference: ``save_pretrained`` + ``os.path.getsize``,
  ``serverless_IID_IMDB.py:280-284``; here computed from the param tree
  directly — no disk round-trip needed),
- per-client local accuracy per round and global accuracy per round
  (``serverless_NonIID_IMDB.py:292, 304, 334``).
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional

import jax
import numpy as np


def model_size_gb(tree) -> float:
    # metadata-only on array leaves: np.asarray would pull every leaf to host
    # (a full-tree device transfer per call) and crashes on donated-away
    # buffers. Non-array leaves (plain ints/floats in a host-side state dict)
    # fall back to np.asarray — those are already on host, so the transfer
    # concern doesn't apply.
    def leaf_bytes(x):
        if hasattr(x, "size") and hasattr(x, "dtype"):
            return x.size * x.dtype.itemsize
        return np.asarray(x).nbytes

    return sum(leaf_bytes(x) for x in jax.tree.leaves(tree)) / 1e9


class ResourceMonitor:
    """before/after psutil capture, with before actually before.

    psutil interval semantics (the part the reference gets wrong twice):
    ``Process.cpu_percent(None)`` is a *windowed* measurement — each call
    reports the average CPU utilization since the PREVIOUS call, and the
    very first call has no previous window, so it always returns a
    meaningless ``0.0`` and merely arms the baseline. ``__init__``
    therefore makes a priming call whose result is *discarded* (the old
    code stored that 0.0 as ``cpu_before``, a number that could never mean
    anything); ``snapshot()``'s reading then covers exactly the
    init -> snapshot window. Calling :meth:`snapshot` more than once is
    supported, but each later reading covers only the window since the
    previous snapshot — not the whole run."""

    def __init__(self, run_dir: Optional[str] = None):
        import psutil

        self._proc = psutil.Process()
        self._psutil = psutil
        self._proc.cpu_percent(None)  # prime: first call is always 0.0
        self.rss_before = self._proc.memory_info().rss
        self.t_before = time.time()
        # when set, sampling also reports free bytes on the filesystem
        # holding the run directory — the resource fault lane's ENOSPC
        # ladder (RUNTIME.md) is exactly the failure this series predicts
        self._run_dir = run_dir

    def disk_free_bytes(self) -> Optional[int]:
        """Free bytes on the filesystem holding ``run_dir``, or None when
        no run_dir was given or the statvfs fails (observer never raises)."""
        if self._run_dir is None:
            return None
        try:
            import shutil

            return int(shutil.disk_usage(self._run_dir).free)
        except OSError:
            return None

    def snapshot(self) -> Dict[str, float]:
        return {
            # average CPU% over the window since __init__ (or the previous
            # snapshot) — see the interval semantics above
            "cpu_percent": self._proc.cpu_percent(None),
            "memory_gb": (self._proc.memory_info().rss - self.rss_before) / 1e9,
            "latency_min": (time.time() - self.t_before) / 60.0,
        }

    # -------------------------------------------------- periodic sampling
    # Before/after snapshots bound a run; a hundreds-of-rounds soak needs
    # the drift BETWEEN them. The sampling thread emits one catalogued
    # `resource` event per interval through the process telemetry seam
    # (absolute RSS, not the delta — the health series plots a level, and
    # windowed CPU% per psutil's interval semantics above), so the live
    # monitor's health.jsonl can track host memory/CPU across the soak.
    # A daemon thread with a waitable stop event: never blocks exit, and
    # the emit seam is a no-op when telemetry is off.

    def start_sampling(self, interval_s: float) -> bool:
        """Begin emitting `resource` telemetry events every ``interval_s``
        seconds (idempotent; returns False when already running or the
        interval is non-positive)."""
        import threading

        if interval_s <= 0 or getattr(self, "_sample_thread", None):
            return False
        from bcfl_tpu.telemetry import events as _telemetry

        self._sample_stop = threading.Event()

        def _loop():
            # a dedicated windowed-CPU baseline for the sampler: sharing
            # snapshot()'s window would make both readings meaningless
            while not self._sample_stop.wait(interval_s):
                try:
                    free = self.disk_free_bytes()
                    extra = ({} if free is None
                             else {"disk_free_bytes": free,
                                   "disk_free_gb": free / 1e9})
                    _telemetry.emit(
                        "resource",
                        rss_gb=self._proc.memory_info().rss / 1e9,
                        cpu_percent=self._proc.cpu_percent(None),
                        interval_s=interval_s, **extra)
                except Exception:  # noqa: BLE001 — observer never crashes the run
                    pass

        self._sample_thread = threading.Thread(
            target=_loop, daemon=True, name="bcfl-resource-sampler")
        self._sample_thread.start()
        return True

    def stop_sampling(self) -> None:
        """Stop the sampling thread (idempotent, joins briefly)."""
        t = getattr(self, "_sample_thread", None)
        if t is None:
            return
        self._sample_stop.set()
        t.join(timeout=5.0)
        self._sample_thread = None


@dataclasses.dataclass
class RoundRecord:
    round: int
    train_loss: float
    train_acc: float
    local_acc: List[float]  # per client
    global_acc: Optional[float] = None
    global_loss: Optional[float] = None
    mask: Optional[List[float]] = None
    anomalies: Optional[List[int]] = None
    # ledger-authentication outcome per client (1 = update verified against
    # the hash chain, 0 = rejected); None when the ledger is off
    auth: Optional[List[float]] = None
    # staleness-decayed merge weight per client for this aggregation event
    # (async mode only)
    async_alpha: Optional[List[float]] = None
    # True when every client was eliminated from this round's aggregate
    # (anomaly filter x fault-injected dropout x ledger auth): the engine
    # kept the previous global model instead of emitting a 0/0 mean
    degraded: bool = False
    # fault-injection observability (bcfl_tpu.faults): clients dropped by the
    # chaos plan this round / per-client injected straggler delay (seconds)
    dropped: Optional[List[int]] = None
    straggler_s: Optional[List[float]] = None
    # chaos partition (ROBUSTNESS.md §6): per-client connected-component id
    # this round (None = mesh whole); healed marks the first whole round
    # after a span, where the components reconciled through the configured
    # aggregator
    partition: Optional[List[int]] = None
    healed: bool = False
    # chaos churn: per-client alive mask (0 = permanently left / not yet
    # joined); None when no churn is scheduled
    churn_alive: Optional[List[float]] = None
    # peer lifecycle (bcfl_tpu.reputation): per-client state name and EWMA
    # trust AFTER this round's evidence was folded in; None = reputation off
    reputation_state: Optional[List[str]] = None
    reputation_trust: Optional[List[float]] = None
    # async staleness (global version - client version) at this aggregation
    # event, for each client (async mode only)
    staleness: Optional[List[int]] = None
    # cohort mode (SCALING.md): the round's sampled REGISTRY client ids, in
    # stacked-slot order. Every other per-client field on this record stays
    # in the SLOT domain — value lists (mask/auth/local_acc/reputation_*)
    # are slot-aligned and index lists (anomalies/dropped) hold slot
    # indices — so `cohort[slot]` is the one mapping back to registry
    # identity. None when registry sampling is off (slot == client id).
    cohort: Optional[List[int]] = None
    info_passing_sync_s: Optional[float] = None
    info_passing_async_s: Optional[float] = None
    # bytes-on-wire accounting (COMPRESSION.md): what this round's update
    # exchange shipped across all clients — raw full-precision size vs the
    # configured codec's payload (equal, ratio 1.0, at compress=none)
    bytes_raw: Optional[float] = None
    bytes_on_wire: Optional[float] = None
    compression_ratio: Optional[float] = None
    # LoRA adapter exchange: mean Shannon effective rank of the global
    # adapter tree after this round's aggregation — the rank-collapse guard
    # for heterogeneous-rank fleets (a healthy RBLA aggregate keeps energy
    # spread across rank dims; a collapsing one trends toward 1.0). None
    # when lora_rank == 0.
    effective_rank: Optional[float] = None
    # what the model counted this round (fed.client_step.model_counters; the
    # expert layer's moe_slots_held / moe_slots_absent / moe_rows_max, each
    # over the round's steps and clients by its kind); None for most models
    counters: Optional[Dict[str, float]] = None
    wall_s: float = 0.0
    # True when this round ran inside a fused multi-round dispatch: wall_s
    # is then the chunk total split EVENLY across its rounds (an
    # interpolation, not a per-round measurement — the real measured unit is
    # wall_chunk_s) and info-passing values are chunk-constant
    fused: bool = False
    wall_chunk_s: Optional[float] = None


@dataclasses.dataclass
class RunMetrics:
    rounds: List[RoundRecord] = dataclasses.field(default_factory=list)
    model_size_gb: float = 0.0
    resources: Dict[str, float] = dataclasses.field(default_factory=dict)
    ledger: Dict[str, float] = dataclasses.field(default_factory=dict)
    # per-phase step timings from metrics.tracing.StepClock
    phases: Dict[str, Dict[str, float]] = dataclasses.field(default_factory=dict)
    # communication accounting rollup: codec kind, per-round raw vs
    # bytes-on-wire, and the compression ratio (COMPRESSION.md)
    comms: Dict[str, float] = dataclasses.field(default_factory=dict)
    # peer-lifecycle rollup (bcfl_tpu.reputation.ReputationTracker.summary):
    # final state/trust per client, quarantine event + round counts
    reputation: Dict = dataclasses.field(default_factory=dict)

    @property
    def global_accuracies(self) -> List[float]:
        """The reference's ``global_accuracies`` list
        (``serverless_NonIID_IMDB.py:334``)."""
        return [r.global_acc for r in self.rounds if r.global_acc is not None]

    def to_json(self) -> str:
        return json.dumps({
            "rounds": [dataclasses.asdict(r) for r in self.rounds],
            "model_size_gb": self.model_size_gb,
            "resources": self.resources,
            "ledger": self.ledger,
            "phases": self.phases,
            "comms": self.comms,
            "reputation": self.reputation,
            "global_accuracies": self.global_accuracies,
        }, indent=2)

    def summary(self) -> str:
        accs = self.global_accuracies
        lines = [
            f"rounds: {len(self.rounds)}",
            f"model size: {self.model_size_gb:.4f} GB",
            f"final global accuracy: {accs[-1]:.4f}" if accs else "no global eval",
        ]
        for k, v in self.resources.items():
            lines.append(f"{k}: {v:.3f}")
        return "\n".join(lines)
