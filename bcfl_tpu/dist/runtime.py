"""PeerRuntime — one OS process of the real multi-host async runtime.

Each peer owns a fixed slice of the global client set and drives its own
local training loop on its own JAX backend; peers exchange updates over
:mod:`bcfl_tpu.dist.transport` and aggregate FedBuff-style at a **component
leader** (the lowest peer id reachable in the peer's connected component —
peer 0 when the network is whole). See RUNTIME.md for the protocol.

The essentials, and how they map onto the existing machinery:

- **Training + wire encode** go through the engine's update-exchange seam
  (:meth:`bcfl_tpu.fed.engine.FedEngine._exchange_updates`, ``commit=False``):
  the wire quantity is exactly what the local split-phase rounds exchange —
  the codec payload (encoded delta vs the peer's adopted base) under
  compression, the post-train stacked params otherwise — and the announced
  ledger digests are the same ``entry_digest`` binding the local flow
  chains.
- **Buffered async aggregation** mirrors ``FedEngine._async_round``'s math
  with MEASURED staleness: an update's staleness is the leader's version
  minus the sender's base version at the moment it is merged (arrival
  order, not a simulated clock), its merge weight is
  ``staleness_decay ** staleness`` (times example counts under
  ``weighted_agg``), and the global takes an ``async_server_lr`` step along
  the weighted-mean delta with the ``_async_merge_scale`` rescale.
- **Ledger forking is real**: the leader commits each merged update's
  ANNOUNCED digests to its chain and verifies what ARRIVED; during a
  transport partition each component's leader extends its own chain from
  the common prefix (two distinct heads exist), and the heal runs the
  segment-verified deterministic merge (:meth:`Ledger.merge_rows` /
  ``adopt_merge``) plus a participation-weighted model consensus through
  the engine's ``collapse`` program.
- **Crash/rejoin** rides the existing checkpoint store: every adopted or
  produced version is checkpointed (``save_checkpoint``); a restarted peer
  restores the newest valid state (``restore_latest``), HELLOs the leader,
  and re-enters with a verified chain replica.
- **Nothing can wedge**: a hard per-process deadline, an idle watchdog (no
  version progress), and a parent-death check each force a nonzero exit,
  and the spawning harness reaps stragglers.
- **Everything is traced** (OBSERVABILITY.md): each peer writes an
  append-only ``events_peer{p}.jsonl`` stream (bcfl_tpu.telemetry) —
  train-round spans, transport send/recv/detector/chaos events, FedBuff
  merges with full lineage (which ``(peer, msg_epoch, msg_id)`` updates at
  what measured staleness and weight composed each version), ledger
  commit/fork/heal, checkpoint and quorum events — which ``bcfl-tpu
  trace`` collates into one causally-ordered cross-peer timeline and
  checks the delivery-contract invariants against. The peer also rewrites
  its JSON report periodically (``DistConfig.report_every_rounds``) and on
  SIGTERM, so a killed or stalled peer leaves a current partial report
  instead of nothing.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import queue
import signal
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from bcfl_tpu import telemetry

logger = logging.getLogger(__name__)


class ResumeError(RuntimeError):
    """``--resume`` found no usable durable state and ``--bootstrap`` was
    not given. Distinct exit code so supervisors distinguish "my state is
    gone" (operator decision needed: accept peer repair or investigate)
    from every crash/stall/deadline failure mode — a peer must never
    silently re-enter the fleet with zero state (RUNTIME.md "State-sync
    protocol")."""

    EXIT_CODE = 8


class DurabilityError(RuntimeError):
    """A durable write (checkpoint commit / ledger high-water) kept
    failing after every rung of the resource-lane response ladder
    (emergency retention GC, then telemetry shed — ROBUSTNESS.md §11).
    Distinct exit code so supervisors distinguish "this host cannot make
    rounds durable" (disk full / fd table exhausted: an operator must
    free resources) from every crash/stall/deadline failure mode — a
    peer must never silently keep committing un-durable state."""

    EXIT_CODE = 9


@dataclasses.dataclass
class MergeRecord:
    version: int
    leader: int
    arrivals: List[Dict]  # per merged update: peer/msg_id/staleness/latency/auth
    rejected: List[Dict]  # updates excluded (stale lineage, auth failure)
    wall_s: float
    solo: bool  # produced while partitioned (a fork extension)
    degraded: bool = False  # merged on a reduced quorum (some peer DOWN)
    quorum: Optional[Dict] = None  # {"component", "alive", "down"} when degraded
    robust: Optional[Dict] = None  # robust-rule info (k, trim_t/krum_*) when armed
    robust_degraded: bool = False  # fewer arrivals than the declared precondition


def _tamper_tree(tree, frac: float):
    """Flip one byte of one leaf (both chosen by ``frac``) — the seeded
    in-flight corruption of a served STATE_SYNC transfer
    (``FaultPlan.sync_tamper``). Deterministic pure function of the input
    draw; the original tree is not mutated."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idx = min(int(frac * len(leaves)), len(leaves) - 1)
    arr = np.asarray(leaves[idx])
    raw = bytearray(arr.tobytes())
    if raw:
        pos = min(int(frac * len(raw)), len(raw) - 1)
        raw[pos] ^= 0xFF
    leaves = list(leaves)
    leaves[idx] = np.frombuffer(bytes(raw),
                                arr.dtype).reshape(arr.shape).copy()
    return jax.tree_util.tree_unflatten(treedef, leaves)


def measured_staleness(leader_version: int, base_version: int):
    """``(staleness, clamped)`` of one arrival: the leader's version minus
    the sender's base version, clamped to >= 0.

    The raw difference CAN be negative after a leader restart: the leader
    restores the newest durable checkpoint, whose version counter may sit
    BELOW the base version a concurrent sender already adopted from a
    later (lost-to-the-crash) broadcast. ``decay ** negative`` would
    INFLATE that update's merge weight (1/decay per lost version) — the
    opposite of what staleness decay is for — so the exponent clamps to 0
    (a from-the-future update is at worst "fresh") and the clamp is
    surfaced (``clamped=True`` -> a `warn` telemetry event + the arrival
    record) instead of silently normalizing the disagreement away."""
    raw = int(leader_version) - int(base_version)
    return max(raw, 0), raw < 0


def _peer_engine_cfg(cfg, local_clients: int):
    """The embedded per-peer engine config: the peer's own client slice on a
    plain local mesh. The dist layer owns async/partition/eval semantics, so
    the inner engine runs the vanilla sync-server build (its round LOOP is
    never used — only its data/program/ledger/exchange machinery).

    The aggregator is pinned to "mean": the robust rules on this runtime
    act over the buffered ARRIVAL set host-side (bcfl_tpu.dist.robust),
    while the inner engine's ``collapse`` program must stay the plain
    weighted mean that reduces one peer's client slice to its vote.
    Reputation is likewise pinned off: the dist layer runs its own
    per-PEER tracker (bcfl_tpu.reputation.dist); the engine's per-client
    lifecycle has no role inside a peer."""
    from bcfl_tpu.faults import FaultPlan
    from bcfl_tpu.reputation import ReputationConfig

    return cfg.replace(
        runtime="local", sync="sync", mode="server",
        num_clients=local_clients, eval_every=0,
        aggregator="mean", reputation=ReputationConfig(),
        faults=FaultPlan(),  # partition/straggler lanes act at the transport
        checkpoint_dir=None, checkpoint_every=0,
        rounds_per_dispatch=1, donate=False)


class PeerRuntime:
    def __init__(self, cfg, peer_id: int, ports: List[int], run_dir: str,
                 resume: bool = False, bootstrap: bool = False):
        import jax

        from bcfl_tpu.dist.transport import (
            LimpChaos,
            PartitionGate,
            PeerTransport,
            WireChaos,
        )
        from bcfl_tpu.fed.engine import FedEngine

        self.cfg = cfg
        self.peer_id = int(peer_id)
        self.peers = cfg.dist.peers
        self.run_dir = run_dir
        os.makedirs(run_dir, exist_ok=True)
        # per-process event stream (OBSERVABILITY.md): ON by default for
        # the dist runtime — the chaos proofs and their invariant gates
        # are queries over these streams. telemetry_dir="off" disables
        # (the overhead-measurement setting); a path overrides the run
        # dir. Installed before the transport exists so its serve threads
        # always see the writer.
        self.events_path = None
        stream_dir = telemetry.resolve_stream_dir(cfg.telemetry_dir,
                                                  run_dir)
        if stream_dir is not None:
            self.events_path = os.path.join(
                stream_dir, f"events_peer{self.peer_id}.jsonl")
            telemetry.install(telemetry.EventWriter(
                self.events_path, peer=self.peer_id, run=cfg.name,
                sample=cfg.telemetry_sample))
        # resource lane, events seam: the EventWriter's flush-time fault
        # hook consults the seeded per-flush draw (the writer's own errno
        # handler sheds sampled telemetry in response — the stream never
        # takes down the run, so this seam never reaches the exit rung)
        self._events_flush_n = 0
        self._events_fault_busy = False
        if cfg.faults.resource_enabled:
            w = telemetry.get_writer()
            if w is not None:
                w.write_fault = self._events_write_fault
        k = cfg.num_clients // self.peers
        self.local_clients = k
        self.global_ids = np.arange(self.peer_id * k, (self.peer_id + 1) * k)

        self.eng = FedEngine(_peer_engine_cfg(cfg, k))
        self._jax = jax
        # which device this peer ran on, for its report. Under the harness's
        # one-chip-per-process pin every peer sees ITS chip as device id 0;
        # the chip's identity on the host is the pinned index
        dev = self.eng.mesh.mesh.devices.flat[0]
        self._device = {
            "platform": dev.platform, "device_kind": dev.device_kind,
            "id": int(dev.id), "count": len(jax.devices()),
            "visible_chip": os.environ.get("TPU_VISIBLE_CHIPS"),
        }
        if self.eng._comp is not None:
            self.eng._ef = self.eng.progs.ef_init(self.eng.trainable0)

        self.trainable = self.eng.trainable0
        self.version = 0
        self.local_round = 0
        self.chain = self.eng.ledger  # the peer's chain replica (or None)
        # version -> (model tree or None, chain-head hex at creation): what
        # an uncompressed update's delta is computed against at the leader,
        # lineage-checked so a fork-based update can never merge into the
        # wrong component's history (compressed runs keep only the head —
        # see _note_version)
        self.history: Dict[int, tuple] = {
            0: (self.trainable if self.eng._comp is None else None,
                self._head())}
        self.history_limit = 16

        self.merges: List[MergeRecord] = []
        self.adopted: List[int] = []
        self._last_broadcast_len = 0  # suffix base of the next chain broadcast
        self._last_hello = 0.0
        self.fork: Optional[Dict] = None
        self.reconcile: Optional[Dict] = None
        self._below_quorum = False
        self._below_quorum_events = 0  # episodes, not loop polls
        self._buffer: List[tuple] = []  # guarded-by: _buffer_lock — (header, trees, recv_time)
        # shed count: writes under the buffer lock; the report's read is
        # a GIL-atomic snapshot (hence the (writes) qualifier)
        self._buffer_shed = 0  # guarded-by: _buffer_lock (writes)
        # double-buffered intake (cfg.dist.pipeline, RUNTIME.md §4): an
        # intake thread drains the transport inbox continuously — UPDATE
        # arrivals land in self._buffer under this lock (the active
        # arrival buffer), everything else routes to the control queue
        # the main loop drains. _maybe_merge SWAPS the arrival buffer out
        # under the lock and merges the swapped-out one while intake
        # keeps filling the fresh standby — merge/verify overlaps intake
        # instead of serializing behind it.
        self._buffer_lock = threading.Lock()
        self._ctrl: "queue.Queue" = queue.Queue()
        self._intake_thread: Optional[threading.Thread] = None
        # quarantine_drops is bumped from the intake thread (_intake_update)
        # AND the main merge thread (_prepare_update): a plain += there is
        # a racy read-add-store, same class transport._bump guards against
        self._qdrop_lock = threading.Lock()
        # when the CURRENT merge window opened (first entry into an empty
        # buffer): the buffer_timeout_s clock. Deliberately not the oldest
        # surviving entry's timestamp — the intake cap sheds oldest-first,
        # so under flood that timestamp keeps advancing and a timeout
        # measured from it can never fire (a dead peer holding
        # distinct < want would park merges forever)
        self._buffer_since = 0.0  # guarded-by: _buffer_lock
        self._partitioned = False
        self._fork_comps = None
        self._pending_reconcile = False
        self._last_reconcile_try = 0.0
        self._stop = False
        self._resumed = False
        # --- durable-state repair (RUNTIME.md "State-sync protocol") ---
        # set by _restore when the scrub finds nothing usable (--bootstrap)
        # or the monotone-incarnation guard detects a rollback; while set,
        # the peer neither trains nor announces — it requests STATE_SYNC
        # from live peers until a verified transfer is adopted
        self.bootstrap = bool(bootstrap)
        self._needs_bootstrap = False
        self._bootstrap_reason: Optional[str] = None
        self._repaired: Optional[Dict] = None
        self._last_sync_req = 0.0
        self._sync_target_i = 0
        self._sync_serves: Dict[int, int] = {}  # requester -> serves so far

        # per-PEER reputation (reputation/dist.py): wire evidence ->
        # quarantine, transitions committed to the chain, state
        # checkpointed bit-for-bit. Every peer runs one; the leader's is
        # the one that gates merges.
        self.rep = None
        if cfg.reputation.enabled:
            from bcfl_tpu.reputation.dist import DistReputationTracker

            self.rep = DistReputationTracker(cfg.reputation, self.peers,
                                             self.peer_id)
        self._det_seen = 0  # detector transitions already fed as evidence
        # byzantine lane (dist/byzantine.py): constructed only when the
        # plan arms it — the injection seam in _train_once is otherwise
        # absent, not merely inert
        self.byz = None
        if cfg.faults.byz_enabled:
            from bcfl_tpu.dist.byzantine import ByzantineAdversary

            self.byz = ByzantineAdversary(
                cfg.faults, self.peer_id,
                clock_fn=lambda: self.local_round)
        # the robust rules' declared arrival-count precondition (validated
        # against cfg.dist.buffer at config time); a merge below it still
        # aggregates with clamped trim but is recorded robust_degraded
        self._robust_min = 0
        if cfg.aggregator != "mean":
            from bcfl_tpu.dist.robust import (
                MIN_ORDER_VOTES,
                krum_min_buffer,
            )

            self._robust_min = (
                krum_min_buffer(cfg.dist.buffer or 1, cfg.aggregator_trim)
                if cfg.aggregator == "krum" else MIN_ORDER_VOTES)

        plan = cfg.faults if cfg.faults.partitions else None
        # the span clock is the peer's LOCAL ROUND: it advances autonomously
        # with the peer's own training loop, so every peer traverses the
        # partition span even while cross-partition messages are dropped (a
        # version-keyed clock can deadlock: versions only advance via the
        # very messages the partition blocks)
        self.gate = PartitionGate(plan, self.peers,
                                  version_fn=lambda: self.local_round)
        # the wire chaos lane shares the gate's autonomous span clock (the
        # peer's local round); an all-defaults plan injects nothing
        chaos = (WireChaos(cfg.faults, clock_fn=lambda: self.local_round)
                 if cfg.faults.wire_enabled else None)
        # the limp lane shares the same autonomous span clock: its
        # direction-keyed link throttles are consumed inside the
        # transport's attempt loop (a paced send, never a silent stall)
        limp = (LimpChaos(cfg.faults, clock_fn=lambda: self.local_round)
                if cfg.faults.limp_enabled else None)
        host = cfg.dist.host
        # transport incarnation epoch: a file-backed restart counter, NOT
        # wall clock — a backward clock step between a crash and its
        # restart must not make receivers treat the new incarnation's
        # messages as a dead one's stragglers
        epoch_path = os.path.join(run_dir, f"epoch_peer{self.peer_id}")
        try:
            with open(epoch_path) as f:
                epoch = int(f.read().strip()) + 1
        except (OSError, ValueError):
            epoch = 1
        with open(epoch_path, "w") as f:
            f.write(str(epoch))
        self.transport = PeerTransport(
            self.peer_id, [(host, p) for p in ports], gate=self.gate,
            io_timeout_s=min(60.0, cfg.dist.peer_deadline_s),
            chaos=chaos, limp=limp, policy=cfg.dist, epoch=epoch)

        self.ckpt_dir = os.path.join(run_dir, f"ckpt_peer{self.peer_id}")
        # monotone-incarnation high-water marker: like the transport epoch
        # file, a tiny supervisor-domain record OUTSIDE the checkpoint dir
        # — the newest (version, chain_len) this peer ever made durable.
        # A restore landing BELOW it means the durable state was rolled
        # back (or fell back past damage) and must resync forward before
        # announcing anything (see _restore).
        self._hw_path = os.path.join(run_dir, f"highwater_peer{self.peer_id}")
        if resume:
            self._restore()

        # --- watchdogs: a hung peer FAILS, it never wedges the run ---
        self._t0 = time.time()
        self._last_version_change = time.time()
        self._ppid = os.getppid()
        self._deadline_timer = threading.Timer(
            cfg.dist.peer_deadline_s, self._deadline_fire)
        self._deadline_timer.daemon = True
        self._deadline_timer.start()
        # partial-report cadence (report_every_rounds): what the report
        # loop compares against to decide a periodic rewrite is due.
        # Reentrant lock: the deadline Timer thread, the main loop's
        # periodic flush, and the SIGTERM handler (which interrupts the
        # main thread mid-frame) all write the same report file.
        self._report_lock = threading.RLock()
        # cadence markers: written by whichever thread rewrites the
        # report; the main loop's due-check reads are snapshots
        self._report_round = -1    # guarded-by: _report_lock (writes)
        self._report_version = -1  # guarded-by: _report_lock (writes)
        self._report_terminal = False  # guarded-by: _report_lock
        self._chain_ok_cache: Optional[bool] = None  # guarded-by: _report_lock
        # SIGTERM leaves a current report + flushed event stream behind
        # (SIGKILL cannot be caught — there the periodic rewrites are the
        # whole story). Registered in the peer's main thread.
        try:
            signal.signal(signal.SIGTERM, self._sigterm)
        except ValueError:
            pass  # not the main thread (embedded/test use): skip

    # ------------------------------------------------------------- watchdogs

    def _deadline_fire(self):
        logger.error("peer %d: hard deadline %.0fs expired; exiting",
                     self.peer_id, self.cfg.dist.peer_deadline_s)
        self._write_report(status="deadline")
        os._exit(3)

    def _sigterm(self, signum, frame):
        logger.error("peer %d: SIGTERM; writing final partial report",
                     self.peer_id)
        try:
            self._write_report(status="sigterm")
        finally:
            # unconditional: a reentrancy hiccup in the report/telemetry
            # write must not swallow the termination itself
            os._exit(7)

    def _maybe_flush_report(self):
        """Periodic partial-report rewrite: every ``report_every_rounds``
        local rounds and on every version change — a SIGKILLed peer's
        newest report is at most one cadence stale, instead of absent.
        ``report_every_rounds=0`` is the documented off-switch for ALL
        mid-run rewrites (startup/terminal writes remain)."""
        every = self.cfg.dist.report_every_rounds
        due = every > 0 and (
            self.version != self._report_version
            or self.local_round - self._report_round >= every)
        if due:
            self._write_report(status="running")

    def _check_watchdogs(self):
        if os.getppid() != self._ppid:
            logger.error("peer %d: supervisor died; exiting", self.peer_id)
            self._write_report(status="orphaned")
            os._exit(5)
        if (time.time() - self._last_version_change
                > self.cfg.dist.idle_timeout_s):
            logger.error("peer %d: no version progress for %.0fs; exiting",
                         self.peer_id, self.cfg.dist.idle_timeout_s)
            self._write_report(status="stalled")
            os._exit(4)

    # ------------------------------------------------------------------ utils

    def _head(self) -> Optional[str]:
        return self.chain.head.hex() if self.chain is not None else None

    def _component(self):
        return self.gate.component_of(self.peer_id)

    def _leader(self) -> int:
        return min(self._component())

    def _note_version(self):
        self._last_version_change = time.time()
        # the model part of a history entry is only ever read by the
        # UNCOMPRESSED delta path (_prepare_update); compressed runs keep
        # just the lineage head — never 16 pinned copies of the params
        model = self.trainable if self.eng._comp is None else None
        self.history[self.version] = (model, self._head())
        for v in sorted(self.history):
            if len(self.history) <= self.history_limit:
                break
            del self.history[v]

    # --- dispatch-mode extension hooks (gossip.py overrides these) ---

    def _checkpoint_extra(self) -> Dict:
        """Extra keys a dispatch subclass folds into the checkpoint state."""
        return {}

    def _restore_extra(self, state: Dict) -> None:
        """Dispatch-subclass twin of :meth:`_checkpoint_extra` on restore.
        Called from ``_restore`` (inside ``__init__`` when resume=True), so
        subclasses must pre-set any attributes it touches BEFORE super()."""

    def _report_extra(self) -> Dict:
        """Extra keys a dispatch subclass folds into the peer report."""
        return {}

    def _sync_serve_extra(self, header_out: Dict) -> None:
        """Extra header keys a dispatch subclass ships with a STATE_SYNC
        serve (gossip adds its version vector)."""

    def _adopt_extra(self, header: Dict, trees: Dict) -> None:
        """Dispatch-subclass hook after a verified STATE_SYNC adoption
        (gossip refreshes its host state copy and version vector)."""

    def _cast(self, tree):
        import jax.numpy as jnp

        pd = jnp.dtype(self.cfg.param_dtype)
        return self._jax.tree.map(
            lambda x: jnp.asarray(x, pd)
            if jnp.issubdtype(np.asarray(x).dtype, np.floating)
            else jnp.asarray(x), tree)

    def _to_device(self, tree_np):
        import jax.numpy as jnp

        return self.eng.mesh.shard_clients(
            self._jax.tree.map(jnp.asarray, tree_np))

    # ----------------------------------------------------------- train + send

    def _train_once(self):
        """One local round: every local client fine-tunes from the peer's
        current base; the wire payload comes out of the engine's shared
        update-exchange seam."""
        import jax

        from bcfl_tpu.core import client_round_keys
        from bcfl_tpu.data import client_batches

        cfg = self.cfg
        rnd = self.local_round
        t0 = time.time()
        tree, n_ex = client_batches(
            self.eng.cache, self.eng.partitioner, self.global_ids, rnd,
            cfg.batch_size, max_batches=cfg.max_local_batches)
        batches = self._to_device(tree)
        keys = client_round_keys(
            jax.random.fold_in(self.eng.root_key, 4), self.global_ids, rnd)
        rngs = self.eng.mesh.shard_clients(jax.random.key_data(keys))
        base = self.eng.progs.broadcast(self.trainable)
        post, _stats = self.eng.progs.local_updates(
            base, self.eng.frozen, batches, rngs)
        ex = self.eng._exchange_updates(
            rnd, post, base, rngs, None, mode="async", commit=False)
        digests = None
        if ex.fp is not None:
            digests = [
                self.eng._entry_digest(ex.wire_kind, ex.fp[c]).hex()
                for c in range(self.local_clients)]
        header = {
            "type": "update", "base_version": int(self.version),
            "round": int(rnd), "wire_kind": ex.wire_kind,
            "lineage": self.history[self.version][1],
            "n_ex": [int(x) for x in np.asarray(n_ex)],
            "digests": digests, "sent_at": time.time(),
        }
        wire_tree = jax.tree.map(np.asarray, jax.device_get(ex.sent))
        self.local_round += 1
        telemetry.emit("round", round=rnd, wall_s=time.time() - t0,
                       base_version=int(self.version))

        # chaos straggler lane, driven for REAL at the transport: the
        # injected delay is an actual pre-send sleep, so it shows up in the
        # measured staleness/latency distribution instead of a simulated one
        delays = cfg.faults.straggler_delays(rnd, self.peers)
        if delays is not None and delays[self.peer_id] > 0:
            time.sleep(float(delays[self.peer_id]))
        # limp lane (gray failures, ROBUSTNESS.md §11): the CPU-starved/
        # swapping case — a REAL stall at the train seam, so the phi
        # detector and the w_slow response are graded against measured
        # slowness. Never sampled: the soak gates count stalls exactly.
        limp_act = cfg.faults.limp_action(rnd, self.peer_id)
        if limp_act is not None and limp_act["stall_s"] > 0:
            telemetry.emit("limp.inject", kind="stall", round=int(rnd),
                           stall_s=float(limp_act["stall_s"]))
            time.sleep(float(limp_act["stall_s"]))

        leader = self._leader()
        if self.byz is not None:
            # the byzantine lane's ONE injection seam: above the wire,
            # below the honest training — the frame the transport ships is
            # well-formed, the content lies (dist/byzantine.py). The
            # poisoning behaviors re-announce digests over the mutated
            # payload so ledger auth PASSES (the robust merge catches
            # them); forgery/equivocation keep the honest announcement so
            # the leader's refingerprint fails (the ledger catches them).
            header, wire_tree, act = self.byz.corrupt_update(
                header, wire_tree, dst=leader)
            if act is not None and act["reannounce"] and header.get(
                    "digests") is not None:
                header = dict(header, digests=self._announce_digests(
                    header["wire_kind"], wire_tree))
        if leader == self.peer_id:
            # the leader's own update gets a real (from, msg_id) identity
            # too, so EVERY merged update is dedup-accountable
            self._buffer_push((dict(header, **{
                "from": self.peer_id,
                "msg_id": self.transport.alloc_msg_id(self.peer_id),
                "msg_epoch": self.transport.epoch}),
                {"payload": wire_tree}, time.time()))
        elif self.cfg.dist.pipeline:
            # pipelined: hand the frame to the per-destination sender
            # worker and immediately start the next local round — the
            # retry/backoff/detector protocol runs in the worker while
            # this peer trains (comms/compute overlap, RUNTIME.md §4).
            # The bounded handoff blocks when the link is slower than
            # training (back-pressure), so frames can't pile up.
            self.transport.send_async(leader, header,
                                      {"payload": wire_tree})
        else:
            # serial (pipeline=False): the transport's retrying seam owns
            # failure handling inline; an undelivered update simply
            # rebases on the next global broadcast
            self.transport.send(leader, header, {"payload": wire_tree})

    def _announce_digests(self, wire_kind: str, tree_np) -> List[str]:
        """Per-client entry digests of a wire payload, recomputed through
        the same device fingerprint program the honest announcement uses —
        what the poisoning behaviors re-announce so their mutated payload
        authenticates."""
        fp = np.asarray(self.eng.progs.fingerprint(self._to_device(tree_np)))
        return [self.eng._entry_digest(wire_kind, fp[c]).hex()
                for c in range(self.local_clients)]

    # ------------------------------------------------------- leader: merging

    def _buffer_push(self, entry: tuple):
        """Leader-side FedBuff intake, BOUNDED: while merges are parked
        (below quorum) the leader still trains and followers still send,
        and each entry holds a model-sized wire tree — an uncapped list
        would grow to OOM before the idle watchdog fires. Shed the OLDEST
        (its stale lineage would be the first rejected at the eventual
        merge anyway). Called from the main loop AND (pipeline on) the
        intake thread — all buffer state moves under the buffer lock."""
        cap = max(4, 2 * self.peers, 2 * (self.cfg.dist.buffer or 1))
        with self._buffer_lock:
            if not self._buffer:
                self._buffer_since = entry[2]  # a new merge window opens
            self._buffer.append(entry)
            while len(self._buffer) > cap:
                self._buffer.pop(0)
                self._buffer_shed += 1

    def _maybe_merge(self):
        import math

        from bcfl_tpu.dist.transport import DOWN

        cfg = self.cfg
        comp = self._component()
        # quorum degradation (RUNTIME.md "Delivery contract"): peers the
        # failure detector holds DOWN don't count toward the buffer target
        # — the leader proceeds on the reachable quorum instead of paying
        # buffer_timeout_s per merge for updates that can never arrive.
        # Below quorum_frac of the component it refuses to advance the
        # global at all (the idle watchdog bounds that wait).
        states = self.transport.detector.states()
        down = [p for p in comp
                if p != self.peer_id and states.get(p) == DOWN]
        # QUARANTINED peers count like DOWN ones toward the merge target:
        # their arrivals are refused post-ack, so waiting buffer_timeout_s
        # for updates that can never buffer would hand the adversary a
        # denial-of-service for free. They still count against the quorum
        # DENOMINATOR — quarantining more than (1 - quorum_frac) of the
        # component parks the leader, by design (a distrusted majority is
        # not a quorum).
        quarantined = ([p for p in self.rep.quarantined_peers()
                        if p in comp and p != self.peer_id]
                       if self.rep is not None else [])
        alive = [p for p in comp if p not in down and p not in quarantined]
        if len(alive) < max(1, math.ceil(cfg.dist.quorum_frac * len(comp))):
            # count EPISODES (entries into the below-quorum state), not
            # main-loop polls — the surfaced number must not depend on
            # how fast the host spins the loop
            if not self._below_quorum:
                self._below_quorum = True
                self._below_quorum_events += 1
                telemetry.emit("quorum.below", component=len(comp),
                               alive=len(alive), down=list(down))
            # with merges (and so broadcasts) parked, nothing else on the
            # leader sends — so nothing would ever probe the DOWN peers
            # and the below-quorum state would be ABSORBING even after
            # the network heals. Ping them directly: send() rate-limits
            # to one probe per probe_interval_s, a success flips the peer
            # REACHABLE, and the next poll restores quorum.
            for p in down:
                self.transport.send(p, {"type": "ping"})
            return
        self._below_quorum = False
        # the buffer target counts DISTINCT senders, not buffered entries:
        # a fast peer (or a flooding adversary) can park several of its own
        # updates before a slow peer lands one, and a robust rule graded
        # on "f of k votes are bad" is only meaningful when the vote
        # population is PEERS — k entries from one sender are one voice
        # (and one vote: _apply_robust_merge groups by sender). The
        # buffer_timeout still bounds the wait for stragglers.
        # Target check and swap are ONE critical section: the intake
        # thread keeps pushing concurrently, and the swap hands merge a
        # consistent snapshot while arrivals land in the fresh standby
        # buffer (the double-buffer seam).
        want = min(cfg.dist.buffer or 1, len(alive))
        with self._buffer_lock:
            if not self._buffer:
                return
            distinct = len({int(h.get("from", -1))
                            for h, _, _ in self._buffer})
            if (distinct < want and time.time() - self._buffer_since
                    < cfg.dist.buffer_timeout_s):
                return
            buf, self._buffer = self._buffer, []
        t0 = time.time()
        arrivals, rejected, weighted = [], [], []
        for header, trees, recv_t in buf:
            out = self._prepare_update(header, trees, recv_t)
            (arrivals if out.get("ok") else rejected).append(out["rec"])
            if out.get("ok"):
                weighted.append(out)
        robust_info = None
        if weighted:
            if cfg.aggregator != "mean":
                robust_info = self._apply_robust_merge(weighted)
            else:
                self._apply_merge(weighted)
        self.version += 1
        rec = MergeRecord(
            version=self.version, leader=self.peer_id, arrivals=arrivals,
            rejected=rejected, wall_s=time.time() - t0,
            solo=self.gate.components() is not None,
            degraded=bool(down),
            quorum=({"component": len(comp), "alive": len(alive),
                     "down": down, "quarantined": quarantined}
                    if (down or quarantined) else None),
            robust=robust_info,
            # the precondition is stated over distinct peer VOTES (the
            # rule's population), not buffered entries
            robust_degraded=bool(
                robust_info is not None
                and robust_info.get("k", 0) < self._robust_min))
        self.merges.append(rec)
        # health-series extras (OBSERVABILITY.md §6): the leader's current
        # per-peer trust vector and, when LoRA is on, the merged global
        # adapter's effective rank (the rank-collapse guard statistic) —
        # the live monitor folds both into health.jsonl per round
        trust_map = ({str(p): round(float(self.rep.tracker.trust[p]), 6)
                      for p in range(self.peers)}
                     if self.rep is not None else None)
        eff_rank = None
        if self.eng._eff_rank is not None:
            try:
                eff_rank = float(self.eng._eff_rank(self.trainable))
            except Exception:  # noqa: BLE001 — a health stat is never merge-fatal
                pass
        # the FedBuff lineage event (OBSERVABILITY.md): which (peer,
        # msg_epoch, msg_id) updates, at what measured staleness and
        # merge weight, composed this model version — plus the chain
        # state it committed, for the monotone-heads invariant
        telemetry.emit(
            "merge", version=rec.version, leader=rec.leader,
            arrivals=rec.arrivals, rejected=rec.rejected, solo=rec.solo,
            degraded=rec.degraded, component=list(comp),
            quorum=rec.quorum, wall_s=rec.wall_s,
            robust=rec.robust, robust_degraded=rec.robust_degraded,
            trust=trust_map, effective_rank=eff_rank,
            **({"chain_len": len(self.chain),
                "head8": self.chain.head.hex()[:16], "rewrite": False}
               if self.chain is not None else {}))
        # gray-failure observation shares the merge clock whether or not
        # reputation is armed: phi samples land in the stream either way
        self._observe_gray_health()
        if self.rep is not None:
            # the merge IS the observation clock: fold the pending wire
            # evidence (auth/outlier/staleness/replay + drained detector
            # transitions) into the per-peer state machine, AFTER this
            # merge's event (a quarantine this merge triggers must gate
            # the NEXT merge, not retroactively taint this one), and
            # commit any transitions to the chain BEFORE the broadcast so
            # the suffix every follower adopts carries them.
            self._drain_detector_evidence()
            arrived = ([a["peer"] for a in arrivals]
                       + [r["peer"] for r in rejected])
            transitions = self.rep.observe_merge(arrived)
            if transitions and self.chain is not None:
                self.rep.commit_transitions(self.chain, self.version,
                                            transitions)
                telemetry.emit("ledger", op="rep_transition",
                               n=len(transitions),
                               chain_len=len(self.chain), rewrite=False,
                               head8=self.chain.head.hex()[:16])
        # history snapshot AFTER any reputation rows hit the chain: the
        # broadcast ships the suffix INCLUDING those rows, so a follower's
        # recorded head for this version is the post-rep-rows head — the
        # leader's lineage record must match it, or every honest update
        # based on this version would bounce as "fork lineage mismatch"
        # (and feed the replay evidence lane!) after any transition
        self._note_version()
        self._maybe_checkpoint()
        self._broadcast_global(healed=False)

    def _drain_detector_evidence(self) -> None:
        """Feed NEW failure-detector transitions to the reputation
        tracker: a peer the circuit breaker drove to DOWN since the last
        merge is unreliability evidence (the weakest lane — peer death is
        not malice, but a flapping peer should not keep full merge
        weight)."""
        det = self.transport.detector
        new = det.transitions_total - self._det_seen
        if new <= 0:
            return
        self._det_seen = det.transitions_total
        from bcfl_tpu.dist.transport import DOWN as _DOWN

        recent = list(det.transitions)[-min(new, len(det.transitions)):]
        for t in recent:
            if t.get("to") == _DOWN:
                self.rep.note_detector_down(t["peer"])

    def _observe_gray_health(self) -> None:
        """Gray-failure observation, clocked by the merge (leadered) or
        the peer-local merge (gossip): sample the phi detector's per-peer
        suspicion into the stream and feed MEASURED slowness to the
        reputation tracker's w_slow lane. Severity is the WORST of three
        measurements, clamped to [0, 1]: phi normalized by the down
        threshold (liveness suspicion — silence, failed sends); the
        measured-throughput shortfall below ``min_bandwidth_bps`` (the
        config's own "slowest link we budget for": a link the estimator
        measures BELOW it is limping even when every adaptively-budgeted
        send still lands); and the measured-RTT excess beyond
        ``deadline_floor_s`` (a round trip consuming more than the
        fastest wall we would ever enforce — the stall/SIGSTOP signature:
        acks come back seconds late while throughput and phi both look
        healthy at the merge instant). All three are zero for a healthy
        peer, which is what lets the down-weight RECOVER when the limp
        clears.
        Structurally a down-weight only: ``note_slowness`` never touches
        the quarantine evidence path (the ``slowness_is_not_malice``
        invariant holds by construction, then gets checked anyway)."""
        det = self.transport.detector
        snap_fn = getattr(det, "phi_snapshot", None)
        if snap_fn is None:
            return  # detector="fixed": no continuous suspicion to sample
        phi_down = float(self.cfg.dist.phi_down)
        for key, info in snap_fn().items():
            p = int(key)
            if p == self.peer_id:
                continue
            telemetry.emit_sampled(
                "detector.phi", (int(self.version), p), target=p,
                phi=info["phi"], state=det.state_of(p),
                window_s=info.get("window_s"), rtt_s=info.get("rtt_s"),
                bps=info.get("bps"))
            if self.rep is not None:
                sev_phi = (min(float(info["phi"]) / phi_down, 1.0)
                           if phi_down > 0 else 0.0)
                bps = info.get("bps")
                min_bps = float(self.cfg.dist.min_bandwidth_bps)
                sev_bw = (max(0.0, 1.0 - float(bps) / min_bps)
                          if bps and min_bps > 0 else 0.0)
                rtt = info.get("rtt_s")
                floor = float(self.cfg.dist.deadline_floor_s)
                sev_rtt = (max(0.0, float(rtt) / floor - 1.0)
                           if rtt and floor > 0 else 0.0)
                self.rep.note_slowness(
                    p, min(1.0, max(sev_phi, sev_bw, sev_rtt)))

    def _apply_robust_merge(self, updates: List[Dict]) -> Dict:
        """Robust twin of :meth:`_apply_merge`: each buffered update is
        collapsed to its client-slice delta (the weighted mean through the
        same ``collapse`` program as the mean path), the deltas are
        grouped into one vote PER SENDING PEER (``combine_votes`` — the
        "f of k" breakdown arithmetic is over peers, so one sender's
        message rate must never inflate its vote count), the votes are
        aggregated host-side with the configured robust rule
        (bcfl_tpu.dist.robust), and the global takes the same
        ``async_server_lr`` × ``_async_merge_scale``-rescaled step along
        the robust estimate — staleness shrinks the applied STEP, the
        rule ignores it as a vote weight (the local robust contract,
        ROBUSTNESS.md §2). Outlier flags land on every flagged peer's
        arrival records and feed the reputation tracker."""
        import jax
        import jax.numpy as jnp

        from bcfl_tpu.dist.robust import combine_votes, robust_merge
        from bcfl_tpu.fed.engine import _tree_axpy

        zero = jax.tree.map(jnp.zeros_like, self.trainable)
        deltas_np, weights, base_total = [], [], 0.0
        for u in updates:
            w_dev = self.eng.mesh.shard_clients(jnp.asarray(u["alpha"]))
            vote = self.eng.progs.collapse(u["deltas"], w_dev, zero)
            deltas_np.append(jax.tree.map(np.asarray,
                                          jax.device_get(vote)))
            weights.append(float(np.asarray(u["alpha"]).sum()))
            base_total += u["base_w"]
        by_peer: Dict[int, List[int]] = {}
        for i, u in enumerate(updates):
            by_peer.setdefault(int(u["rec"]["peer"]), []).append(i)
        peer_order = sorted(by_peer)
        votes = [combine_votes([deltas_np[i] for i in by_peer[p]],
                               [weights[i] for i in by_peer[p]])
                 for p in peer_order]
        vote_w = [sum(weights[i] for i in by_peer[p]) for p in peer_order]
        agg, flags, info = robust_merge(
            votes, vote_w, self.cfg.aggregator, self.cfg.aggregator_trim)
        info["votes_by_peer"] = {str(p): len(by_peer[p])
                                 for p in peer_order}
        if "krum_selected" in info:
            # robust_merge speaks in vote positions; the lineage record
            # must name the PEER whose vote became the global (sender
            # sets are rarely contiguous from 0 — a position would
            # misattribute)
            info["krum_selected_peer"] = peer_order[info["krum_selected"]]
        dists = info.get("distances")
        for j, p in enumerate(peer_order):
            if not flags[j]:
                continue
            for i in by_peer[p]:
                updates[i]["rec"]["outlier"] = True
            # like every other evidence lane, never against self: under
            # non-iid slices the leader's own honest vote can sit far
            # from the aggregate, and a leader quarantining ITSELF while
            # remaining the component leader would wedge the run (the
            # flag still lands on the record for observability)
            if self.rep is not None and p != self.peer_id:
                self.rep.note_outlier(
                    p, distance=(dists[j] if dists else None))
        if agg is None:
            return info  # every vote eliminated: params kept (degraded)
        total = sum(weights)
        scale = total / max(base_total, 1e-9)
        agg_dev = self.eng.mesh.replicate(self._cast(agg))
        self.trainable = _tree_axpy(self.trainable, agg_dev,
                                    self.cfg.async_server_lr * scale)
        return info

    def _prepare_update(self, header: Dict, trees: Dict, recv_t: float):
        """Commit + verify + decode one buffered update. Returns a record
        and, when accepted, the per-client merge weights and delta rows."""
        cfg = self.cfg
        src = int(header["from"])
        base_v = int(header["base_version"])
        staleness, clamped = measured_staleness(self.version, base_v)
        rec = {"peer": src, "msg_id": header.get("msg_id"),
               "msg_epoch": header.get("msg_epoch"),
               "round": int(header["round"]),
               "base_version": base_v, "staleness": staleness,
               "latency_s": max(recv_t - float(header["sent_at"]), 0.0)}
        if clamped:
            # leader restarted onto an older version counter than this
            # sender's base (see measured_staleness): the decay exponent
            # is clamped — surfaced, never silently normalized
            rec["staleness_clamped"] = True
            telemetry.emit("warn", what="negative_staleness", peer_from=src,
                           leader_version=int(self.version),
                           base_version=base_v)
        # post-ack quarantine gate, second seam (the first is _handle):
        # an update BUFFERED before the quarantine transition must not
        # merge after it — this check runs at merge time, which is what
        # the no_quarantined_merge invariant holds the stream to
        if (self.rep is not None and src != self.peer_id
                and self.rep.is_quarantined(src)):
            with self._qdrop_lock:
                self.rep.quarantine_drops += 1
            rec["rejected"] = "peer quarantined (post-ack gate)"
            return {"ok": False, "rec": rec}
        # lineage check (BOTH wire formats) BEFORE anything touches the
        # chain: an update based on another fork's history must go through
        # the reconcile protocol, never a silent merge — and a protocol-
        # rejected update must leave NO chain entries (the chain attests
        # updates that entered aggregation, where auth failures are the
        # recorded evidence). The sender names the chain head of its base
        # version; it must match this leader's history for that version.
        hist = self.history.get(base_v)
        if hist is not None and hist[1] != header.get("lineage"):
            rec["rejected"] = "fork lineage mismatch"
            if self.rep is not None and src != self.peer_id:
                # the replay behavior's signature: a stale base's lineage
                # resent against rewritten/advanced history
                self.rep.note_replay(src, "fork lineage mismatch")
            return {"ok": False, "rec": rec}
        if self.eng._comp is None and hist is None:
            # uncompressed wire ships post-train params: the delta NEEDS
            # the base model, so an evicted base version is fatal here
            rec["rejected"] = "unknown base version"
            if self.rep is not None and src != self.peer_id:
                self.rep.note_replay(src, "unknown base version")
            return {"ok": False, "rec": rec}
        dev = self._to_device(trees["payload"])
        ids = [src * self.local_clients + c
               for c in range(self.local_clients)]
        auth = np.ones((self.local_clients,), np.float32)
        if self.chain is not None and header.get("digests"):
            # commit what the sender ANNOUNCED, then authenticate what
            # ARRIVED — the same commit -> transport -> verify order as the
            # local split-phase flow, but across a real wire
            kind = header["wire_kind"]
            for c, d in zip(ids, header["digests"]):
                self.chain.append_digest(int(header["round"]), int(c),
                                         bytes.fromhex(d),
                                         self.eng._client_payload_bytes)
            telemetry.emit("ledger", op="commit", round=int(header["round"]),
                           n=self.local_clients, chain_len=len(self.chain),
                           rewrite=False,
                           head8=self.chain.head.hex()[:16])
            fp = np.asarray(self.eng.progs.fingerprint(dev))
            for c in range(self.local_clients):
                recomputed = self.eng._entry_digest(kind, fp[c]).hex()
                if recomputed != header["digests"][c]:
                    auth[c] = 0.0
            rec["auth"] = auth.tolist()
            if (self.rep is not None and src != self.peer_id
                    and (auth == 0.0).any()):
                # the hard evidence lane: announced one fingerprint,
                # shipped another (digest forgery / equivocation / wire
                # damage past the CRC — repetition tells them apart).
                # Never against self (like every other lane): a leader
                # configured as the adversary must not quarantine ITSELF
                # while remaining leader — its forged self-update is
                # already auth-masked out of the merge above
                self.rep.note_auth_failure(
                    src, float((auth == 0.0).mean()))
        if self.eng._comp is None:
            # uncompressed wire ships post-train params: reconstruct the
            # delta against the (lineage-verified, above) base model
            from bcfl_tpu.fed.engine import _tree_sub

            deltas = _tree_sub(dev, self.eng.progs.broadcast(hist[0]))
        else:
            # compressed wire ships the encoded delta itself — FedBuff can
            # apply it without the base; a base evicted from the bounded
            # history merely can't be lineage-verified (recorded)
            if hist is None:
                rec["lineage_unverified"] = True
            deltas = self.eng.progs.decode_delta(
                dev, self.eng.progs.broadcast(self.trainable))
        if self.rep is not None and src != self.peer_id:
            # measured-staleness evidence: a chronically stale peer (real
            # slowness or deliberate replay) decays toward SUSPECT
            self.rep.note_staleness(src, staleness)
        n_ex = np.asarray(header["n_ex"], np.float32)
        alpha = auth * (cfg.staleness_decay ** staleness)
        base_w = n_ex if cfg.weighted_agg else np.ones_like(n_ex)
        alpha = alpha * base_w
        if self.rep is not None:
            # trust gates merge weight: the EWMA score scales this
            # update's whole vote (probation peers additionally carry the
            # probation_weight fold) — the dist analogue of the engine's
            # reputation-gate mask fold
            trust_mult = self.rep.gate(src)
            rec["trust"] = round(float(trust_mult), 6)
            alpha = alpha * np.float32(trust_mult)
        if float(alpha.sum()) <= 0.0:
            rec["rejected"] = "all clients eliminated (auth/trust)"
            return {"ok": False, "rec": rec}
        # the update's total merge weight (staleness decay x examples x
        # auth, summed over the peer's client slice): part of the merge
        # lineage — every composed model version is reconstructible from
        # the stream
        rec["weight"] = float(alpha.sum())
        return {"ok": True, "rec": rec, "deltas": deltas, "alpha": alpha,
                "base_w": float(base_w.sum())}

    def _apply_merge(self, updates: List[Dict]):
        """FedBuff step along the staleness-weighted mean delta — the
        measured-clock twin of ``FedEngine._async_round``'s merge."""
        import jax
        import jax.numpy as jnp

        from bcfl_tpu.fed.engine import _tree_axpy, _tree_wsum

        zero = jax.tree.map(jnp.zeros_like, self.trainable)
        merged_parts, weights, base_total = [], [], 0.0
        for u in updates:
            w_dev = self.eng.mesh.shard_clients(jnp.asarray(u["alpha"]))
            merged_parts.append(
                self.eng.progs.collapse(u["deltas"], w_dev, zero))
            weights.append(float(np.asarray(u["alpha"]).sum()))
            base_total += u["base_w"]
        total = sum(weights)
        merged = _tree_wsum(
            jnp.asarray([w / total for w in weights], jnp.float32),
            merged_parts)
        # decay shrinks the applied STEP, not just relative votes — the
        # _async_merge_scale rescale (PARALLELISM.md "Async semantics")
        scale = total / max(base_total, 1e-9)
        self.trainable = _tree_axpy(self.trainable, merged,
                                    self.cfg.async_server_lr * scale)

    def _broadcast_global(self, healed: bool, full: bool = False):
        import jax

        header = {
            "type": "global", "version": int(self.version),
            "healed": bool(healed),
        }
        if self.chain is not None:
            # normal merges broadcast only the chain SUFFIX since the last
            # broadcast (O(new entries), not O(chain)); heals broadcast the
            # full chain — the merge rewrote history past the fork point,
            # so no replica's suffix base is valid. A follower whose length
            # or head doesn't match the suffix base resyncs via HELLO.
            start = 0 if (healed or full) else self._last_broadcast_len
            header["chain_start"] = int(start)
            header["chain_prev_head"] = self.chain.head_at(start).hex()
            header["chain"] = self.chain.segment(start)
            self._last_broadcast_len = len(self.chain)
        else:
            header["chain"] = None
        telemetry.emit("broadcast", version=int(self.version),
                       healed=bool(healed), full=bool(healed or full))
        model = jax.tree.map(np.asarray, jax.device_get(self.trainable))
        for p in self._component():
            if p == self.peer_id:
                continue
            # retrying seam; a peer that misses the broadcast resyncs via
            # HELLO, and a dead one trips the detector toward DOWN. With
            # the pipeline on, broadcasts ride the same per-destination
            # sender workers as updates (FIFO per destination, so version
            # N always hits the wire before N+1) and the leader starts
            # its next round while the model streams out.
            if self.cfg.dist.pipeline:
                self.transport.send_async(p, header, {"model": model})
            else:
                self.transport.send(p, header, {"model": model})

    # --------------------------------------------------- partition lifecycle

    def _update_partition_state(self):
        comps = self.gate.components()
        if comps is not None and not self._partitioned:
            self._partitioned = True
            self._fork_comps = comps
            self.fork = {
                "at_version": int(self.version),
                "fork_base": (int(len(self.chain))
                              if self.chain is not None else None),
                "head_at_fork": self._head(),
                "component": list(self.gate.component_of(self.peer_id)),
            }
            telemetry.emit("fork.begin", at_version=int(self.version),
                           component=self.fork["component"],
                           head8=(self._head() or "")[:16],
                           fork_base=self.fork["fork_base"])
            logger.info("peer %d: partition began at version %d "
                        "(component %s)", self.peer_id, self.version,
                        self.fork["component"])
        elif comps is None and self._partitioned:
            self._partitioned = False
            self.fork["head_before_heal"] = self._head()
            self.fork["chain_len_before_heal"] = (
                int(len(self.chain)) if self.chain is not None else None)
            old_comp = next(c for c in self._fork_comps
                            if self.peer_id in c)
            if min(old_comp) == self.peer_id and self.peer_id != 0:
                # I led a fork component: initiate the reconcile handshake
                self._pending_reconcile = True
            telemetry.emit("fork.heal", at_version=int(self.version),
                           head8=(self._head() or "")[:16])
            logger.info("peer %d: partition healed at version %d (head %s)",
                        self.peer_id, self.version,
                        (self._head() or "")[:16])

    def _solo_weight(self) -> float:
        """Participation mass this peer's fork accumulated: merged arrivals
        across its solo merges — the reconcile consensus weight."""
        return float(sum(len(m.arrivals) for m in self.merges if m.solo)
                     or 1.0)

    def _try_reconcile(self):
        """Offer the fork to the global leader. Retried (throttled) until a
        post-heal GLOBAL supersedes it: a send can 'succeed' at the socket
        yet be dropped by the leader's own still-partitioned clock, so only
        an adopted global clears the pending flag."""
        import jax

        if not self.gate.allowed(self.peer_id, 0):
            return
        if time.time() - self._last_reconcile_try < 2.0:
            return
        self._last_reconcile_try = time.time()
        header = {
            "type": "reconcile", "version": int(self.version),
            "rows": self.chain.segment(0) if self.chain is not None else None,
            "weight": self._solo_weight(),
        }
        model = jax.tree.map(np.asarray, jax.device_get(self.trainable))
        # retrying seam; undelivered offers re-fire on the throttle until a
        # healed global supersedes them
        self.transport.send(0, header, {"model": model})

    def _handle_reconcile(self, header: Dict, trees: Dict):
        """Global leader's side of the heal: verify the fork segment, adopt
        the deterministic chain merge, reconcile the component models
        through the collapse consensus, and broadcast the healed global."""
        import jax.numpy as jnp

        from bcfl_tpu.fed.engine import _tree_wsum
        from bcfl_tpu.ledger import Ledger

        src = int(header["from"])
        t0 = time.time()
        rec = {"from_peer": src, "their_version": int(header["version"]),
               "my_version": int(self.version)}
        their_model = self._cast(trees["model"])
        their_weight = float(header.get("weight") or 1.0)
        my_weight = self._solo_weight()
        if self.chain is not None and header.get("rows") is not None:
            rows = header["rows"]
            their_heads = [bytes.fromhex(r["head"]) for r in rows]
            fork = self.chain.fork_point(their_heads)
            rec["fork_point"] = fork
            rec["my_head"] = self._head()
            rec["their_head"] = rows[-1]["head"] if rows else None
            rec["forked"] = (rec["my_head"] != rec["their_head"])
            bad = Ledger.verify_segment(
                self.chain.head_at(fork), rows[fork:],
                self.cfg.ledger.use_native)
            if bad != -1:
                # a tampered fork segment: never adopted — the requester is
                # told the CURRENT (unmerged) global instead
                rec["segment_rejected_at"] = int(bad)
                self.reconcile = rec
                logger.warning("peer %d: rejected tampered reconcile "
                               "segment from %d (link %d)",
                               self.peer_id, src, bad)
                self._broadcast_global(healed=False)
                return
            merged = Ledger.merge_rows(self.chain.segment(fork), rows[fork:])
            self.chain.adopt_merge(fork, merged)
            rec["merged_entries"] = len(merged)
            rec["merged_head"] = self._head()
            rec["chain_ok"] = (self.chain.verify_chain() == -1)
            # a declared history rewrite: the monotone-heads invariant
            # treats this (and only this kind of) length change as legal
            telemetry.emit("ledger", op="adopt_merge",
                           chain_len=len(self.chain), rewrite=True,
                           head8=(self._head() or "")[:16],
                           fork_point=fork)
        # model consensus across the healed components: the participation-
        # weighted mean of the two fork models (with aggregator pinned to
        # "mean" on this runtime, this IS what the collapse consensus
        # program computes — the direct form skips a one-off stacked-
        # program compile per heal)
        total = my_weight + their_weight
        self.trainable = _tree_wsum(
            jnp.asarray([my_weight / total, their_weight / total],
                        jnp.float32),
            [self.trainable, their_model])
        self.version = max(self.version, int(header["version"])) + 1
        self._note_version()
        rec["healed_version"] = int(self.version)
        rec["wall_s"] = time.time() - t0
        self.reconcile = rec
        telemetry.emit("reconcile", **rec)
        self._maybe_checkpoint()
        self._broadcast_global(healed=True)
        logger.info("peer %d: reconciled fork from peer %d -> version %d "
                    "(chain head %s)", self.peer_id, src, self.version,
                    (self._head() or "")[:16])

    # ------------------------------------------------------- follower: adopt

    def _request_resync(self, leader: int):
        """Ask the leader for a full-state GLOBAL (throttled): the suffix a
        broadcast carried didn't extend this replica — missed broadcasts,
        or a fork rewrite this peer hasn't seen yet."""
        if time.time() - self._last_hello < 2.0:
            return
        self._last_hello = time.time()
        self.transport.send(leader, {"type": "hello",
                                     "version": int(self.version)})

    def _handle_global(self, header: Dict, trees: Dict):
        from bcfl_tpu.ledger import Ledger

        version = int(header["version"])
        if self._needs_bootstrap:
            # repair in flight: globals are not commitment-refingerprinted,
            # so a bootstrapping peer adopts ONLY through the verified
            # STATE_SYNC path (repair_authenticated invariant)
            return
        if version <= self.version:
            return
        if self._pending_reconcile and not header.get("healed"):
            # a fork is pending: adopting an ordinary (pre-heal) global
            # would REPLACE this peer's fork chain — destroying the very
            # evidence the reconcile must deliver — and clearing the offer
            # here could cancel a reconcile the leader never received (its
            # receiver gate drops sends while ITS clock is still in the
            # span), deadlocking the leader's finalize guard. Defer: keep
            # retrying the offer; the leader cannot finalize before
            # handling it, and its HEALED broadcast supersedes everything.
            return
        if self.chain is not None and header.get("chain") is not None:
            rows = header["chain"]
            start = int(header.get("chain_start", 0))
            if start == 0:
                # full sync (heal / hello reply): rebuild and verify the
                # whole replica from genesis
                replica = Ledger(self.cfg.ledger.use_native)
                if replica.append_rows(rows) != -1:
                    logger.error("peer %d: global v%d carried an "
                                 "unverifiable chain; not adopting",
                                 self.peer_id, version)
                    return
                self.chain = replica
                self.eng.ledger = replica
                # full replica rebuild: a declared rewrite (heal / hello
                # resync may shorten a fork replica's chain legitimately)
                telemetry.emit("ledger", op="resync",
                               chain_len=len(self.chain), rewrite=True,
                               head8=self.chain.head.hex()[:16])
                if self.rep is not None:
                    # inherit the leader's committed reputation verdicts
                    # from the adopted chain — a REJOINING peer re-enters
                    # knowing who is quarantined instead of starting blind
                    self.rep.absorb_rows(rows)
            elif (start == len(self.chain)
                  and self.chain.head.hex() == header.get("chain_prev_head")):
                # contiguous suffix: verify incrementally as it lands
                if self.chain.append_rows(rows) != -1:
                    logger.error("peer %d: global v%d suffix failed link "
                                 "verification; resyncing", self.peer_id,
                                 version)
                    self._request_resync(int(header["from"]))
                    return
                telemetry.emit("ledger", op="append",
                               chain_len=len(self.chain), rewrite=False,
                               head8=self.chain.head.hex()[:16])
                if self.rep is not None:
                    # the suffix carries the leader's reputation rows too:
                    # every follower tracks its leader's verdicts from the
                    # broadcasts it already receives
                    self.rep.absorb_rows(rows)
            else:
                # gap or diverged base (missed broadcasts, fork rewrite):
                # never adopt a model whose chain this replica can't
                # verify — request the full state instead
                self._request_resync(int(header["from"]))
                return
        self.trainable = self.eng.mesh.replicate(self._cast(trees["model"]))
        self.version = version
        self.adopted.append(version)
        self._note_version()
        telemetry.emit("adopt", version=version,
                       healed=bool(header.get("healed")),
                       leader=int(header.get("from", -1)))
        if header.get("healed"):
            # ONLY the healed global clears a pending offer: it is the one
            # broadcast that provably incorporated this peer's fork
            self._pending_reconcile = False
        self._maybe_checkpoint()

    def _handle_hello(self, header: Dict):
        """A (re)joining peer announces itself; the leader replies with the
        full current state so the rejoiner re-enters verified."""
        if self._leader() != self.peer_id:
            return
        import jax

        src = int(header["from"])
        reply = {
            "type": "global", "version": int(self.version), "healed": False,
            "chain_start": 0,
        }
        if self.chain is not None:
            from bcfl_tpu.ledger import GENESIS

            reply["chain_prev_head"] = GENESIS.hex()
            reply["chain"] = self.chain.segment(0)
        else:
            reply["chain"] = None
        model = jax.tree.map(np.asarray, jax.device_get(self.trainable))
        # retrying seam; an undelivered reply re-fires on the rejoiner's
        # next throttled HELLO
        self.transport.send(src, reply, {"model": model})

    # ------------------------------------- state-sync repair (RUNTIME.md)

    def _sync_targets(self) -> List[int]:
        """Peers a bootstrap request cycles through: the leader first (it
        holds the authoritative state in leadered dispatch), then every
        other peer — any live peer can serve, so a damaged LEADER repairs
        from its followers. Gossip overrides this with a seeded neighbor
        sample."""
        leader = min(p for p in range(self.peers) if p != self.peer_id)
        rest = [p for p in range(self.peers)
                if p not in (self.peer_id, leader)]
        return [leader] + rest

    def _maybe_request_sync(self):
        """Throttled STATE_SYNC request loop: while ``_needs_bootstrap``,
        ask one live peer (cycling) for its full verified state. Runs from
        the main loop — the peer neither trains nor announces until a
        transfer is adopted."""
        if not self._needs_bootstrap:
            return
        if time.time() - self._last_sync_req < 2.0:
            return
        self._last_sync_req = time.time()
        targets = self._sync_targets()
        if not targets:
            return
        dst = targets[self._sync_target_i % len(targets)]
        self._sync_target_i += 1
        telemetry.emit("state.sync.request",
                       reason=self._bootstrap_reason or "empty",
                       to=int(dst), have_version=int(self.version),
                       have_len=(len(self.chain)
                                 if self.chain is not None else 0))
        self.transport.send(dst, {
            "type": "state_sync_req",
            "reason": self._bootstrap_reason or "empty",
            "version": int(self.version),
            "have_len": int(len(self.chain)) if self.chain is not None else 0,
        })

    def _handle_state_sync_req(self, header: Dict):
        """Serve a damaged/empty peer the full current state, anchored to
        the chain: a reserved commitment row (``Ledger.commit_state``)
        binding ``params_digest(state)`` at the current version is
        appended (once per distinct digest) BEFORE the transfer, so the
        receiver can verify the chain segment link-by-link and then
        refingerprint the tree against committed history — the transfer
        is trustless even though the server is just a peer."""
        import jax

        if self._needs_bootstrap:
            return  # damaged myself: the requester's cycle finds another
        from bcfl_tpu.ledger.ledger import Ledger, params_digest

        src = int(header["from"])
        model = jax.tree.map(np.asarray, jax.device_get(self.trainable))
        header_out = {"type": "state_sync", "version": int(self.version)}
        if self.chain is not None:
            digest = params_digest(model, self.cfg.ledger.use_native)
            rows = self.chain.segment(0)
            if Ledger.find_state_commitment(
                    rows, self.version, self.peer_id) != digest:
                self.chain.commit_state(self.version, self.peer_id, digest)
                telemetry.emit("ledger", op="commit_state",
                               chain_len=len(self.chain), rewrite=False,
                               head8=self.chain.head.hex()[:16])
            header_out["chain"] = self.chain.segment(0)
        else:
            header_out["chain"] = None
        self._sync_serve_extra(header_out)
        serial = self._sync_serves.get(src, 0)
        self._sync_serves[src] = serial + 1
        tam = self.cfg.faults.sync_tamper_action(self.peer_id, src, serial)
        if tam is not None:
            # seeded in-flight tamper (AFTER the digest was committed):
            # the refusal this provokes at the receiver is the proof the
            # refingerprint gate is load-bearing
            model = _tamper_tree(model, tam["frac"])
        telemetry.emit("state.sync.serve", to=src,
                       version=int(self.version),
                       chain_len=(len(self.chain)
                                  if self.chain is not None else 0),
                       tampered=tam is not None, serial=serial)
        self.transport.send(src, header_out, {"model": model})

    def _handle_state_sync(self, header: Dict, trees: Dict):
        """Adopt a served state — but only after BOTH verification gates
        pass: (1) the chain segment verifies link-by-link from genesis AND
        extends this peer's surviving prefix (a tampered row or a forked
        history fails here, via the existing verify_segment/fork_point
        API); (2) the received tree refingerprints to the state commitment
        row the chain carries for exactly this (version, server). Refusals
        re-enter the request cycle; nothing is adopted on faith.

        A serve landing AFTER a completed repair (the requester cycled
        targets and another peer answered first) is still pushed through
        the same gates so the evidence is durable — a tampered late
        transfer must surface as a state.sync.refuse, not vanish into
        the duplicate drop — but is never adopted, and a refused late
        serve does not re-enter the request cycle."""
        from bcfl_tpu.ledger.ledger import GENESIS, Ledger, params_digest

        adopting = self._needs_bootstrap
        server = int(header["from"])
        version = int(header["version"])
        rows = header.get("chain")
        refuse = None
        digest = recomputed = None
        if self.chain is not None:
            if not rows:
                refuse = "no_chain"
            elif Ledger.verify_segment(
                    GENESIS, rows, self.cfg.ledger.use_native) != -1:
                refuse = "bad_links"
            else:
                heads = [bytes.fromhex(r["head"]) for r in rows]
                if self.chain.fork_point(heads) < len(self.chain):
                    # the served history contradicts what this peer still
                    # durably holds — a fork (or a rolled-back server);
                    # never adopt a chain that rewrites a surviving prefix
                    refuse = "forked_prefix"
                else:
                    digest = Ledger.find_state_commitment(rows, version,
                                                          server)
                    if digest is None:
                        refuse = "no_commitment"
                    else:
                        recomputed = params_digest(
                            trees["model"], self.cfg.ledger.use_native)
                        if recomputed != digest:
                            refuse = "digest_mismatch"
        telemetry.emit("state.sync.verify", ok=refuse is None,
                       src=server, version=version,
                       digest8=(recomputed.hex()[:16]
                                if recomputed is not None else None),
                       reason=refuse)
        if refuse is not None:
            logger.warning("peer %d: refusing state_sync from %d (%s)",
                           self.peer_id, server, refuse)
            telemetry.emit("state.sync.refuse", reason=refuse, src=server,
                           version=version)
            if adopting:
                # re-request immediately from the next target in the cycle
                self._last_sync_req = 0.0
            return
        if not adopting:
            return  # clean late serve: audited above, nothing to adopt
        if self.chain is not None:
            replica = Ledger(self.cfg.ledger.use_native)
            replica.append_rows(rows)  # verified above; rebuild the heads
            self.chain = replica
            self.eng.ledger = replica
            telemetry.emit("ledger", op="resync", chain_len=len(self.chain),
                           rewrite=True, head8=self.chain.head.hex()[:16])
            if self.rep is not None:
                self.rep.absorb_rows(rows)
        self.trainable = self.eng.mesh.replicate(self._cast(trees["model"]))
        self.version = version
        self.adopted.append(version)
        self._note_version()
        self._adopt_extra(header, trees)
        self._needs_bootstrap = False
        reason = self._bootstrap_reason
        self._bootstrap_reason = None
        self._repaired = {"from": server, "version": version,
                          "reason": reason}
        telemetry.emit("state.sync.adopt", version=version, src=server,
                       digest8=(digest.hex()[:16]
                                if digest is not None else None),
                       chain_len=(len(self.chain)
                                  if self.chain is not None else 0),
                       reason=reason)
        logger.info("peer %d: repaired from peer %d at version %d (%s)",
                    self.peer_id, server, version, reason)
        self._maybe_checkpoint()

    # --------------------------------------------------- checkpoint / resume

    def _durable_write(self, seam: str, counter: int, fn):
        """One durable write through the resource-lane response ladder
        (ROBUSTNESS.md §11). The seeded draw decides whether this write's
        first ``depth`` attempts fail (ENOSPC/EMFILE raised cleanly,
        nothing landed — the commit protocol is all-or-nothing, so a
        retry is safe); each failure walks one rung — emergency retention
        GC, then telemetry shed — before retrying. A write still failing
        after every remedy raises :class:`DurabilityError`: the peer
        exits with the distinct durability code instead of silently
        committing un-durable state. A REAL (non-injected) ENOSPC/EMFILE
        out of ``fn`` walks the same ladder."""
        plan = self.cfg.faults
        act = (plan.resource_action(seam, counter, self.peer_id)
               if plan.resource_enabled else None)
        remedies = 0
        while True:
            try:
                if act is not None and remedies < act["depth"]:
                    err = 28 if act["cls"] == "enospc" else 24
                    telemetry.emit("resource.inject", seam=seam,
                                   cls=act["cls"], counter=int(counter),
                                   depth=int(act["depth"]),
                                   attempt=remedies, errno=err)
                    raise OSError(err, os.strerror(err))
                return fn()
            except OSError as e:
                if e.errno not in (28, 24):
                    raise
                if remedies == 0:
                    self._emergency_gc(seam)
                elif remedies == 1:
                    self._shed_telemetry(seam)
                else:
                    raise DurabilityError(
                        f"peer {self.peer_id}: durable write at the "
                        f"{seam!r} seam (counter {counter}) still failing "
                        f"(errno {e.errno}) after emergency GC and "
                        f"telemetry shed") from e
                remedies += 1

    def _emergency_gc(self, seam: str) -> None:
        """First ladder rung: free space NOW by dropping every retained
        checkpoint round except the newest — retention depth is a
        convenience, durability of the CURRENT round is the contract.
        The newest committed round always survives (the peer stays
        restorable even if the retry still fails)."""
        from bcfl_tpu.checkpoint.checkpoint import (
            _fsync_dir,
            _list_rounds,
            _remove_round,
        )

        rounds = _list_rounds(self.ckpt_dir)
        victims = rounds[:-1]
        for r in victims:
            _remove_round(self.ckpt_dir, r, keep_meta=False)
        if victims:
            _fsync_dir(self.ckpt_dir)
        telemetry.emit("gc.emergency", seam=seam, removed=len(victims),
                       kept=len(rounds) - len(victims))

    def _shed_telemetry(self, seam: str) -> None:
        """Second ladder rung: stop buffering SAMPLED telemetry (counted,
        never written) so durable bytes get whatever headroom remains.
        Never-sampled events keep flowing (the invariants read those) and
        ledger/checkpoint bytes are never shed — only the high-rate
        observability tail is."""
        w = telemetry.get_writer()
        if w is not None and w.begin_shed(seam):
            telemetry.emit("write.shed", seam=seam, mode="on")

    def _events_write_fault(self, nbytes: int) -> None:
        """Resource lane at the EventWriter flush seam: consult the
        seeded per-flush draw and fail the stream write cleanly. The
        writer's own errno handler sheds sampled telemetry in response —
        this seam never escalates to the exit rung (telemetry must never
        take down the run it observes). The counter is the seam's own
        flush sequence; the busy flag keeps the inject event's OWN flush
        from recursing into a second draw."""
        if self._events_fault_busy:
            return
        n = self._events_flush_n
        self._events_flush_n += 1
        act = self.cfg.faults.resource_action("events", n, self.peer_id)
        if act is None:
            return
        err = 28 if act["cls"] == "enospc" else 24
        self._events_fault_busy = True
        try:
            telemetry.emit("resource.inject", seam="events",
                           cls=act["cls"], counter=n,
                           depth=int(act["depth"]), errno=err,
                           nbytes=int(nbytes))
            raise OSError(err, os.strerror(err))
        finally:
            self._events_fault_busy = False

    def _maybe_checkpoint(self):
        cfg = self.cfg
        every = cfg.dist.checkpoint_every_versions
        if not every or self.version % every:
            return
        import jax

        from bcfl_tpu.checkpoint import save_checkpoint
        from bcfl_tpu.compression import codecs as cc

        state = {
            "trainable": jax.device_get(self.trainable),
            "version": np.int64(self.version),
            "local_round": np.int64(self.local_round),
            "seed": np.int64(cfg.seed),
            "compress_format": np.frombuffer(
                cc.wire_format(self.eng._comp).encode(), np.uint8).copy(),
            "ef_residual": (jax.device_get(self.eng._ef)
                            if self.eng._ef is not None else None),
        }
        if self.rep is not None:
            # the per-peer tracker rides the checkpoint bit-for-bit (the
            # same rep_* keys as the engine's per-client lifecycle): a
            # resumed leader re-enters with every trust score and
            # quarantine timer exactly where the crash left them
            state.update(self.rep.checkpoint_state())
        state.update(self._checkpoint_extra())
        # both durable seams run the resource-lane response ladder: the
        # checkpoint commit (payload + meta sidecar carrying the chain
        # bytes) and the ledger's durable commitment point (the
        # high-water marker the rollback guard reads)
        self._durable_write(
            "checkpoint", self.version,
            lambda: save_checkpoint(
                self.ckpt_dir, self.version, state,
                self.chain.to_json() if self.chain is not None else None,
                keep_last=cfg.dist.checkpoint_keep_last))
        self._durable_write(
            "ledger",
            len(self.chain) if self.chain is not None else self.version,
            self._write_highwater)
        # storage fault lane (ROBUSTNESS.md §10): damage the committed
        # durable state per the seeded (peer, version) draw — injected
        # AFTER the commit, the media-failure model
        action = cfg.faults.storage_action(self.version, self.peer_id)
        if action is not None:
            from bcfl_tpu.checkpoint import apply_storage_fault

            record = apply_storage_fault(self.ckpt_dir, action)
            if record is not None:
                telemetry.emit("chaos", lane="storage", action=record["cls"],
                               version=int(self.version), **{
                                   k: v for k, v in record.items()
                                   if k != "cls"})

    # ------------------------------------------- durable high-water marker

    def _read_highwater(self) -> Optional[Dict]:
        try:
            with open(self._hw_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _write_highwater(self):
        hw = self._read_highwater()
        cur = {"version": int(self.version),
               "chain_len": len(self.chain) if self.chain is not None else 0}
        if hw is not None and hw.get("version", -1) >= cur["version"]:
            return
        tmp = self._hw_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cur, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._hw_path)

    def _restore(self):
        from bcfl_tpu.checkpoint import restore_latest, scrub
        from bcfl_tpu.compression import codecs as cc
        from bcfl_tpu.ledger import Ledger

        report = scrub(self.ckpt_dir)
        restored = restore_latest(self.ckpt_dir)
        if restored is None:
            if not self.bootstrap:
                # loud by default: a --resume peer whose durable state is
                # gone or wholly damaged must not silently rejoin with
                # zero state — that is an operator decision (--bootstrap)
                raise ResumeError(
                    f"peer {self.peer_id}: --resume found no usable "
                    f"checkpoint in {self.ckpt_dir} "
                    f"(scrub: {'empty' if report['empty'] else 'damaged'}, "
                    f"damaged={list(report['damaged'])}, "
                    f"torn={list(report['torn'])}); pass --bootstrap to "
                    f"opt into ledger-authenticated peer repair")
            self._needs_bootstrap = True
            self._bootstrap_reason = ("empty" if report["empty"]
                                      else "damaged")
            logger.warning("peer %d: no usable checkpoint (%s); will "
                           "bootstrap from a live peer", self.peer_id,
                           self._bootstrap_reason)
            return
        _, state, ledger_json = restored
        ck_seed = state.get("seed")
        if ck_seed is not None and int(ck_seed) != self.cfg.seed:
            raise ValueError(
                f"peer checkpoint seed {int(ck_seed)} != config seed "
                f"{self.cfg.seed}: resuming would change every stream")
        ck_comp = state.get("compress_format")
        if ck_comp is not None:
            ck_comp = bytes(np.asarray(ck_comp, np.uint8)).decode()
            here = cc.wire_format(self.eng._comp)
            if ck_comp != here:
                raise ValueError(
                    f"peer checkpoint wire format {ck_comp!r} != this "
                    f"run's {here!r}")
        self.trainable = self.eng.mesh.replicate(self._cast(
            state["trainable"]))
        self.version = int(state["version"])
        self.local_round = int(state["local_round"])
        if state.get("ef_residual") is not None and self.eng._comp is not None:
            self.eng._ef = self._to_device(state["ef_residual"])
        if ledger_json and self.chain is not None:
            self.chain = Ledger.from_json(ledger_json,
                                          self.cfg.ledger.use_native)
            self.eng.ledger = self.chain
        if self.rep is not None and state.get("rep_trust") is not None:
            self.rep.restore(state)
            # the bit-identical-restore evidence: the EXACT restored
            # arrays, recorded before anything evolves them, for the
            # resume proof to compare against the checkpoint file
            self._restored_rep = self.rep.report()
            for p in self.rep.quarantined_peers():
                # re-declare restored quarantines into THIS incarnation's
                # stream: the no_quarantined_merge invariant is
                # pid-scoped, so without this a resumed leader's
                # post-restart merges would be judged against an empty
                # quarantine set. quarantine_evidence exempts the
                # from="restored" marker — a FOLLOWER restores verdicts
                # it absorbed from the leader's broadcast chain rows and
                # has no evidence events of its own to point at
                telemetry.emit(
                    "rep.transition", client=int(p), scope="peer",
                    **{"from": "restored", "to": "quarantined",
                       "trust": float(self.rep.tracker.trust[p])})
        self._restored_from_version = int(state["version"])
        self.history = {
            self.version: (self.trainable if self.eng._comp is None
                           else None, self._head())}
        self._restore_extra(state)
        self._resumed = True
        logger.info("peer %d: restored checkpoint at version %d "
                    "(round %d)", self.peer_id, self.version,
                    self.local_round)
        hw = self._read_highwater()
        if hw is not None and self.version < int(hw.get("version", -1)):
            # monotone-incarnation guard: this incarnation restored a state
            # OLDER than one a previous incarnation durably announced —
            # either the checkpoint dir was rolled back to a stale snapshot
            # or damage forced the restore past the newest round. Either
            # way the peer must resync FORWARD (verified STATE_SYNC) before
            # training or announcing: re-entering at the stale version
            # would re-announce old versions as new.
            self._needs_bootstrap = True
            self._bootstrap_reason = "rollback"
            logger.warning(
                "peer %d: restored version %d is below the durable "
                "high-water %d (rollback or damage fallback); resyncing "
                "forward before rejoining", self.peer_id, self.version,
                int(hw["version"]))

    # ------------------------------------------------------------- main loop

    def _intake_update(self, header: Dict, trees: Dict):
        """The UPDATE intake seam, shared by the serial path (_handle, main
        loop) and the pipelined intake thread: post-ack quarantine gate,
        then into the leader's locked arrival buffer."""
        src = int(header.get("from", -1))
        if (self.rep is not None and src != self.peer_id
                and self.rep.is_quarantined(src)):
            # quarantine refusal is POST-ACK, like a partition-gate
            # drop: the frame was delivered intact and the sender's
            # failure detector must not read distrust as peer death
            # (peer death != malice, and vice versa)
            with self._qdrop_lock:
                self.rep.quarantine_drops += 1
            return
        if self._leader() == self.peer_id:
            self._buffer_push((header, trees, time.time()))
        # an update addressed to a stale leader is dropped: the sender
        # will rebase on the next global broadcast

    def _intake_loop(self):
        """Pipelined intake (cfg.dist.pipeline): drain the transport inbox
        continuously — UPDATE frames go straight into the double-buffered
        arrival buffer (so the listener/inbox never backs up behind a
        merge), everything else routes to the control queue the main loop
        drains. Protocol handlers stay single-threaded in the main loop;
        only the buffer push crosses threads, under its lock."""
        while not self._stop:
            msg = self.transport.recv(timeout_s=0.05)
            if msg is None:
                continue
            header, trees = msg
            if header.get("type") == "update":
                self._intake_update(header, trees)
            else:
                self._ctrl.put(msg)

    def _next_ctrl(self, timeout_s: float):
        """Next message for the MAIN loop: the control queue when the
        intake thread owns the inbox, the inbox itself otherwise."""
        if self._intake_thread is not None:
            try:
                return self._ctrl.get(timeout=timeout_s)
            except queue.Empty:
                return None
        return self.transport.recv(timeout_s=timeout_s)

    def _handle(self, header: Dict, trees: Dict):
        kind = header.get("type")
        if kind == "update":
            # serial path only — with the pipeline on, updates were
            # already consumed by the intake thread and never reach here
            self._intake_update(header, trees)
        elif kind == "ping":
            pass  # liveness probe: delivery (the ack) was the answer
        elif kind == "global":
            self._handle_global(header, trees)
        elif kind == "reconcile":
            if self.peer_id == 0:
                self._handle_reconcile(header, trees)
        elif kind == "hello":
            self._handle_hello(header)
        elif kind == "state_sync_req":
            self._handle_state_sync_req(header)
        elif kind == "state_sync":
            self._handle_state_sync(header, trees)
        elif kind == "shutdown":
            self._stop = True
        else:
            logger.warning("peer %d: unknown message type %r",
                           self.peer_id, kind)

    def _finalize(self):
        loss = acc = None
        try:
            loss, acc = self.eng._global_eval(self.trainable)
        except Exception as e:  # an eval failure must not eat the report
            logger.warning("peer %d: final eval failed (%s)", self.peer_id, e)
        self._final_eval = {"loss": loss, "acc": acc}
        # drain the sender pipeline BEFORE the stop message: the final
        # global broadcast rides the per-destination workers, and a sync
        # shutdown racing past a queued broadcast would stop a follower
        # one version short of the state it was owed
        self.transport.flush_sends(
            timeout_s=self.cfg.dist.send_deadline_s)
        for p in range(self.peers):
            if p == self.peer_id:
                continue
            # retrying seam; a DOWN peer's circuit skips this instantly
            self.transport.send(p, {"type": "shutdown",
                                    "version": int(self.version)})
        self._stop = True

    def run(self) -> int:
        logger.info("peer %d/%d up: clients %s, version %d%s",
                    self.peer_id, self.peers, list(self.global_ids),
                    self.version, " (resumed)" if self._resumed else "")
        telemetry.emit("run.start", role="peer", peers=self.peers,
                       resumed=self._resumed, version=int(self.version),
                       epoch=self.transport.epoch,
                       pipeline=bool(self.cfg.dist.pipeline))
        self.transport.start()
        # periodic host-resource sampling (cfg.dist.resource_sample_s):
        # feeds the live monitor's health series. Only when this process
        # has an event stream — the sampler emits through the same seam.
        self._resmon = None
        if (self.cfg.dist.resource_sample_s > 0
                and self.events_path is not None):
            try:
                from bcfl_tpu.metrics.metrics import ResourceMonitor

                self._resmon = ResourceMonitor(run_dir=self.run_dir)
                self._resmon.start_sampling(self.cfg.dist.resource_sample_s)
            except Exception as e:  # noqa: BLE001 — psutil absence never kills a peer
                logger.warning("resource sampling unavailable: %s", e)
        if self.cfg.dist.pipeline:
            self._intake_thread = threading.Thread(
                target=self._intake_loop, daemon=True,
                name=f"bcfl-dist-intake-{self.peer_id}")
            self._intake_thread.start()
        # an immediate partial report: from this instant on, even a peer
        # SIGKILLed seconds into the run leaves evidence behind
        self._write_report(status="running")
        if self._resumed and self.peer_id != 0 and not self._needs_bootstrap:
            self.transport.send(0, {"type": "hello",
                                    "version": int(self.version)})
        try:
            while not self._stop:
                self._check_watchdogs()
                self._maybe_flush_report()
                msg = self._next_ctrl(timeout_s=0.05)
                while msg is not None:
                    self._handle(*msg)
                    msg = self._next_ctrl(timeout_s=0.0)
                if self._stop:
                    break
                if self._needs_bootstrap:
                    # damaged/empty/rolled-back durable state: repair FIRST.
                    # No training, merging, or announcing until a verified
                    # STATE_SYNC transfer is adopted — the idle watchdog
                    # still bounds a repair that never completes.
                    self._maybe_request_sync()
                    time.sleep(0.05)
                    continue
                self._update_partition_state()
                if self._pending_reconcile:
                    self._try_reconcile()
                if self._leader() == self.peer_id:
                    self._maybe_merge()
                if (self.peer_id == 0 and self.version >= self.cfg.num_rounds
                        and self.gate.components() is None
                        and (self.fork is None
                             or self.reconcile is not None)):
                    # target version count reached, mesh whole, and any fork
                    # this run produced has been reconciled: evaluate, tell
                    # everyone, stop. Never finalize mid-partition (a gate-
                    # blocked shutdown would strand the other components) or
                    # before the heal (the fork evidence would be lost).
                    self._finalize()
                if self._stop:
                    break
                if (self.version < self.cfg.num_rounds
                        or self.gate.components() is not None
                        or (self.peer_id == 0 and self.fork is not None
                            and self.reconcile is None)):
                    # keep training past the version target while a span is
                    # active or a fork is unresolved: the span clock IS the
                    # local round, so stopping here would freeze the peer
                    # inside the partition forever
                    self._train_once()
                else:
                    time.sleep(0.05)  # drained; waiting for shutdown/merges
        except DurabilityError as e:
            # the resource-lane exit rung: the host cannot make rounds
            # durable even after GC + shed — exit with the distinct code,
            # never silently commit un-durable state
            logger.error("%s", e)
            self._write_report(status="undurable")
            return DurabilityError.EXIT_CODE
        finally:
            # a short drain so a follower's last enqueued update isn't cut
            # off mid-stream by close (post-shutdown frames are moot, but
            # a half-written frame would show up as a receiver wire_drop)
            self.transport.flush_sends(timeout_s=2.0)
            self.transport.close()
            self._deadline_timer.cancel()
            if self._resmon is not None:
                self._resmon.stop_sampling()
        self._write_report(status="ok")
        return 0

    # ---------------------------------------------------------------- report

    def _write_report(self, status: str):
        """Atomic (tmp + rename) report write. ``status="running"`` is the
        periodic partial flush — the report a SIGKILLed peer leaves
        behind; any other status is terminal and also closes out the
        event stream (run.end + flush), so a cleanly-ended stream is a
        complete record.

        Serialized under a reentrant lock (watchdog Timer thread, main
        loop, SIGTERM handler share the tmp file), and terminal statuses
        win: once one is written, a periodic "running" rewrite can never
        overwrite it."""
        with self._report_lock:
            if self._report_terminal:
                return
            if status != "running":
                self._report_terminal = True
            self._write_report_locked(status)

    def _chain_ok(self, status: str) -> Optional[bool]:  # guarded-by: _report_lock
        if self.chain is None:
            return None
        if status != "running" or self._chain_ok_cache is None:
            self._chain_ok_cache = self.chain.verify_chain() == -1
        return self._chain_ok_cache

    def _write_report_locked(self, status: str):  # guarded-by: _report_lock
        self._report_round = self.local_round
        self._report_version = self.version
        staleness = [a["staleness"] for m in self.merges for a in m.arrivals]
        latencies = [a["latency_s"] for m in self.merges for a in m.arrivals]
        tstats = self.transport.stats()
        report = {
            "peer": self.peer_id,
            "peers": self.peers,
            "status": status,
            "pid": os.getpid(),
            "device": self._device,
            "resumed": self._resumed,
            "final_version": int(self.version),
            "local_rounds": int(self.local_round),
            "merges": [dataclasses.asdict(m) for m in self.merges],
            "solo_merges": sum(1 for m in self.merges if m.solo),
            "degraded_merges": sum(1 for m in self.merges if m.degraded),
            "below_quorum_events": self._below_quorum_events,
            "buffer_shed": self._buffer_shed,
            "adopted_versions": self.adopted,
            "staleness_values": staleness,
            "arrival_latency_s": latencies,
            "transport": tstats,
            "send_failures": tstats["send_failures"],
            "dropped_by_gate": tstats["dropped_by_gate"],
            "fork": self.fork,
            "reconcile": self.reconcile,
            # byzantine-tolerance surfaces (ROBUSTNESS.md §8): the
            # per-peer tracker's state + the adversary's injection
            # counters (exactly zero with the lane off — the baseline
            # legs gate on these keys)
            "reputation": (self.rep.report()
                           if self.rep is not None else None),
            "restored_reputation": getattr(self, "_restored_rep", None),
            "restored_from_version": getattr(
                self, "_restored_from_version", None),
            # durable-state repair evidence (RUNTIME.md "State-sync
            # protocol"): why this peer bootstrapped and from whom —
            # what the storage soak's convergence gates read
            "bootstrap_reason": self._bootstrap_reason,
            "repaired": self._repaired,
            "byzantine": (self.byz.stats() if self.byz is not None
                          else {"armed": False, "injected": {},
                                "total": 0}),
            "chain_len": len(self.chain) if self.chain is not None else None,
            "chain_head": self._head(),
            # verify_chain re-hashes the WHOLE ledger — O(chain) per call,
            # quadratic if run on every periodic flush. Full verify on
            # terminal writes only; periodic reports carry the last
            # verified verdict (refreshed at startup and at exit)
            "chain_ok": self._chain_ok(status),
            "final_eval": getattr(self, "_final_eval", None),
            "events": self.events_path,
            "wall_s": time.time() - self._t0,
        }
        report.update(self._report_extra())
        path = os.path.join(self.run_dir, f"report_peer{self.peer_id}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2)
        os.replace(tmp, path)
        telemetry.emit("report.flush", status=status)
        if status != "running":
            # terminal: run.end marks the stream cleanly closed (the
            # acked_not_lost invariant only judges receivers bearing this
            # mark), and the flush makes it durable even on the os._exit
            # watchdog paths, which skip atexit hooks
            telemetry.emit("run.end", status=status,
                           version=int(self.version),
                           local_rounds=int(self.local_round))
        telemetry.flush()


def peer_main(argv=None) -> int:
    """Entry point of one peer process (``python -m bcfl_tpu.dist``)."""
    import argparse

    ap = argparse.ArgumentParser(prog="bcfl_tpu.dist")
    ap.add_argument("--config", required=True,
                    help="path to the supervisor-written FedConfig JSON")
    ap.add_argument("--peer-id", type=int, required=True)
    ap.add_argument("--ports", required=True,
                    help="comma-separated listen ports, one per peer")
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--bootstrap", action="store_true",
                    help="with --resume: if no usable checkpoint survives, "
                         "repair from a live peer over verified STATE_SYNC "
                         "instead of failing loudly")
    ap.add_argument("--platform", default=None)
    args = ap.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format=f"[peer {args.peer_id}] %(levelname)s %(message)s")
    from bcfl_tpu.core.hostenv import compile_cache

    compile_cache()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from bcfl_tpu.dist.launch import cfg_from_json

    with open(args.config) as f:
        cfg = cfg_from_json(f.read())
    ports = [int(p) for p in args.ports.split(",")]
    if cfg.dist.dispatch == "gossip":
        # leaderless epidemic dispatch (RUNTIME.md "Gossip dispatch"):
        # same transport, same engine, no privileged process
        from bcfl_tpu.dist.gossip import GossipPeerRuntime as Runtime
    else:
        Runtime = PeerRuntime
    try:
        rt = Runtime(cfg, args.peer_id, ports, args.run_dir,
                     resume=args.resume, bootstrap=args.bootstrap)
    except ResumeError as e:
        # distinct exit code: "durable state unusable and repair not
        # authorized" is an operator decision, not a crash
        logger.error("%s", e)
        return ResumeError.EXIT_CODE
    try:
        return rt.run()
    except DurabilityError as e:
        # backstop for a durable write failing outside the main loop —
        # the same distinct "cannot make rounds durable" code
        logger.error("%s", e)
        return DurabilityError.EXIT_CODE
