"""Single configuration surface for the whole framework.

The reference has no config system: its configuration space is 11 near-copy
scripts whose deltas are module-level constants (``CHECKPOINT``,
``NUM_CLIENTS``, ``NUM_ROUNDS``, ``DEVICE``, dataset + column names, partition
arithmetic) — see SURVEY.md §2.1 for the per-file matrix. Here that space is
one frozen dataclass; the 11 scripts become presets in
:mod:`bcfl_tpu.entrypoints.presets`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from bcfl_tpu.compression import CompressionConfig
from bcfl_tpu.faults import FaultPlan
from bcfl_tpu.reputation import ReputationConfig


@dataclasses.dataclass(frozen=True)
class PartitionConfig:
    """How each client selects its local train/test subset.

    ``iid``: every client draws ``iid_samples`` random examples
    (reference: ``random.sample(range(len(ds)), 100)``,
    ``src/Serverlesscase/serverless_IID_IMDB.py:60-65``), optionally a fresh
    resample each round (``resample_each_round``, reference behaviour at
    ``serverless_IID_IMDB.py:258``).

    ``contiguous`` (Non-IID): client ``k`` takes the index slice
    ``[stride*k, stride*k + train_span)`` for train and either the trailing
    slice ``[stride*k + train_span, stride*(k+1))`` (``test_mode='trailing'``,
    reference ``serverless_NonIID_IMDB.py:59-60`` — the 300k/240 schedule) or a
    fixed shared slice ``[0, test_span)`` (``test_mode='fixed'``, reference
    ``Serverless_NonIID_Medical_transcriptions.py:55-56`` — the 500i/400
    schedule).
    """

    kind: str = "iid"  # "iid" | "contiguous"
    iid_samples: int = 100
    iid_test_samples: Optional[int] = None  # default: same as iid_samples
    resample_each_round: bool = False
    stride: int = 300
    train_span: int = 240
    test_span: int = 60
    test_mode: str = "trailing"  # "trailing" | "fixed"

    def __post_init__(self):
        if self.kind not in ("iid", "contiguous"):
            raise ValueError(f"unknown partition kind: {self.kind!r}")
        if self.test_mode not in ("trailing", "fixed"):
            raise ValueError(f"unknown test_mode: {self.test_mode!r}")
        if self.kind == "contiguous":
            if self.train_span > self.stride:
                raise ValueError(
                    f"train_span {self.train_span} > stride {self.stride}: "
                    "client slices would overlap"
                )
            if self.test_mode == "trailing" and self.train_span + self.test_span > self.stride:
                raise ValueError(
                    f"train_span+test_span {self.train_span + self.test_span} > "
                    f"stride {self.stride}: trailing test slice would overlap the "
                    "next client's train slice"
                )


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """P2P network model + anomaly gating (reference: notebook-only, cells 0-12
    of ``All_graphs_IMDB_dataset.ipynb``; here it is wired into training)."""

    anomaly_filter: Optional[str] = None  # None|"pagerank"|"dbscan"|"zscore"|"community"
    # bandwidth matrix source: "reference" = the notebook's fixed 10-node graph,
    # "random" = sampled in [bw_low, bw_high] mbps like the notebook's values.
    bandwidth: str = "reference"
    bw_low: float = 88.0
    bw_high: float = 496.0
    # gossip mixing coefficient for ring gossip (serverless mode)
    gossip_alpha: float = 0.5
    gossip_steps: int = 1  # ring-gossip rounds per federated round


@dataclasses.dataclass(frozen=True)
class LedgerConfig:
    """Hash-chained weight ledger (the real implementation of the reference's
    'BC-FL' — described only in ``README.md:10`` and MT notebook cells 26-28)."""

    enabled: bool = False
    use_native: bool = True  # C++ SHA-256 core if built, hashlib otherwise
    # ledger-entry payload size (bytes) for communication accounting: the
    # reference models the blockchain payload as 0.043 GB vs the 0.4036 GB
    # full model (MT notebook cell 27 vs 23)
    entry_payload_bytes: int = 46_170_898  # 0.043 GiB-class default


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Multi-host async P2P runtime knobs (``FedConfig.runtime='dist'``,
    RUNTIME.md). Each peer is a real OS process owning a slice of the
    clients; update exchange is length-prefixed TCP over loopback/DCN
    carrying the configured codec's wire format plus ledger fingerprint
    digests; aggregation is FedBuff-style buffered async with MEASURED
    (arrival-order) staleness. All timeouts are hard deadlines — a hung
    peer fails the run instead of wedging it (the harness reaps it)."""

    peers: int = 2
    host: str = "127.0.0.1"
    # first listen port; peer p listens on base_port + p. 0 = the spawner
    # picks free ports and passes them down (scripts/dist_async.py, CLI)
    base_port: int = 0
    # merge target at a component leader, in DISTINCT sending peers (each
    # update carries its sender's whole client slice; several updates from
    # one sender count once toward the target and collapse into one vote
    # under a robust aggregator — the "f of k" arithmetic is over peers).
    # 0 = 1: merge on every arrival — the pure-async setting, and the one
    # that makes the measured staleness distribution non-degenerate. Must
    # be <= peers.
    buffer: int = 0
    # leader-side cap on waiting for the buffer to fill: merge whatever
    # arrived once this many seconds pass since the first buffered update
    # (a departed peer must not stall every future merge)
    buffer_timeout_s: float = 20.0
    # peer-process watchdog: no observable progress (no message, no local
    # round) for this long -> the peer exits nonzero instead of wedging
    idle_timeout_s: float = 120.0
    # hard wall deadline for one peer process; the in-process watchdog
    # enforces it even if the supervisor died
    peer_deadline_s: float = 600.0
    # checkpoint every N adopted/produced global versions (0 = off); the
    # crash/rejoin path restores from the newest one
    checkpoint_every_versions: int = 1
    # checkpoint retention: keep only the newest K committed rounds on
    # disk (0 = keep everything). Removal of older rounds is ordered
    # strictly AFTER the new round's commit+fsync, so a crash mid-GC can
    # only ever leave EXTRA rounds, never fewer than K usable ones.
    checkpoint_keep_last: int = 0
    # --- self-healing transport policy (RUNTIME.md "Delivery contract") ---
    # every logical send retries failed attempts with exponential backoff
    # (base * 2^k, capped at retry_max_s, deterministically jittered) up to
    # send_retries RE-tries, all under the per-destination send_deadline_s
    # wall budget — at-least-once delivery, made safe by the receiver's
    # per-sender (from, msg_id) dedup window
    send_retries: int = 4
    retry_base_s: float = 0.05
    retry_max_s: float = 2.0
    send_deadline_s: float = 20.0
    # circuit-breaker failure detector: consecutive send-attempt failures
    # move a peer REACHABLE -> SUSPECT (suspect_after) -> DOWN
    # (down_after); any success snaps it back to REACHABLE. While DOWN the
    # circuit is open — sends are skipped except one probe per
    # probe_interval_s, so a recovered peer is re-detected without paying
    # a connect timeout on every message
    suspect_after: int = 2
    down_after: int = 6
    probe_interval_s: float = 2.0
    # --- failure-detection mode (RUNTIME.md "Timing contract") ---
    # "phi" (default) = adaptive phi-accrual-style estimator: per-peer
    # inbound-interval EWMA + variance feed a CONTINUOUS suspicion level
    # phi (monotone in silence, snapped back by any liveness evidence);
    # suspect/down become thresholds on phi and send deadlines adapt per
    # destination from measured RTT/throughput (floor/ceiling clamped
    # below). "fixed" = the consecutive-counter detector above with the
    # static send_deadline_s — bit-compatible with pre-gray-failure
    # replays (the knob the existing dist_chaos legs pin).
    detector: str = "phi"
    # phi thresholds: suspicion grows by 1 per consecutive failed send
    # attempt plus the peer's silence beyond its adaptive expected window
    # (so the defaults grade like suspect_after=2 / down_after=6 under
    # pure failures, while pure silence also accrues — the gray-failure
    # signal the fixed counter is blind to)
    phi_suspect: float = 2.0
    phi_down: float = 6.0
    # clamp on the adaptive expected-silence window (EWMA mean + 3 sigma
    # of inbound intervals): the floor keeps a chatty link from making
    # sub-second silences suspicious, the ceiling bounds how long an
    # unheard-from peer can stay unsuspected
    phi_window_floor_s: float = 5.0
    phi_window_ceil_s: float = 120.0
    # clamp on the adaptive per-destination send deadline (measured RTT
    # headroom + frame_bytes / measured throughput). floor bounds how
    # aggressive a fast link's deadline may get; ceiling bounds how long
    # a limping link can hold a send. detector="fixed" ignores both and
    # uses send_deadline_s verbatim.
    deadline_floor_s: float = 2.0
    deadline_ceil_s: float = 120.0
    # assumed link throughput (bytes/s) before any measurement exists:
    # the size-proportional term of the adaptive deadline divides by this
    # until real throughput samples arrive, so a first-contact 32 MB
    # frame gets a budget that scales with its size instead of starving
    # under a latency-tuned constant (the PR 8 large-frame starvation
    # note)
    min_bandwidth_bps: float = 1_048_576.0
    # gossip hedging: when a sampled neighbor's phi crosses this
    # threshold at dispatch time, the peer re-draws a seeded replacement
    # neighbor (detector="phi" only; the draw is replayable — see
    # bcfl_tpu.dist.gossip.HEDGE_LANE)
    gossip_hedge_phi: float = 2.0
    # receiver-side per-sender dedup window (message ids); ids at or below
    # (newest seen - window) are treated as duplicates and dropped
    dedup_window: int = 1024
    # bounded inbox: a flooding (or chaos-duplicated) peer cannot grow a
    # leader's queue without bound — overflow REFUSES the newest frame
    # (no ack, dedup id un-recorded, counted in transport stats
    # `inbox_overflow`), so the sender's retry can still deliver it once
    # the inbox drains — at-least-once survives a full inbox
    inbox_max: int = 1024
    # partial-report cadence: peers rewrite their report_peer*.json every
    # N local rounds (and on every adopted/produced version, at startup,
    # and on SIGTERM) with status="running" — a SIGKILLed or stalled peer
    # leaves a current partial report instead of nothing. 0 disables the
    # periodic rewrites (startup/terminal writes remain).
    report_every_rounds: int = 5
    # quorum degradation: the FedBuff leader's buffer target counts only
    # component peers the detector does NOT hold DOWN (merges recorded as
    # degraded while any are), and below this reachable fraction of the
    # component the leader refuses to advance the global at all (the idle
    # watchdog bounds that wait)
    quorum_frac: float = 0.5
    # --- comms/compute overlap (RUNTIME.md §4, PERF.md) ---
    # pipeline=True (default) overlaps communication with computation:
    # update sends and global broadcasts go through per-destination sender
    # WORKERS (the round loop enqueues and immediately starts the next
    # local round; retries/backoff/detector feeding run in the worker),
    # and the leader drains arrivals on an INTAKE thread into a
    # double-buffered FedBuff buffer (merge/verify consumes a swapped-out
    # buffer while intake keeps filling the standby one). False = the
    # PR 7-10 serial loop, bit-compatible — the wire_perf.py A/B baseline.
    pipeline: bool = True
    # bounded per-destination handoff queue depth for the sender workers:
    # when a destination is slower than the round loop, enqueue BLOCKS
    # after this many frames (back-pressure) instead of buffering
    # model-sized trees without bound
    pipeline_depth: int = 2
    # periodic host-resource sampling (metrics.ResourceMonitor sampling
    # mode): every this-many seconds each peer emits a catalogued
    # `resource` telemetry event (RSS, windowed CPU%) so the live
    # monitor's health series can track drift across a long soak.
    # 0.0 (default) = off; ignored when telemetry is off.
    resource_sample_s: float = 0.0
    # --- dispatch mode (RUNTIME.md "Gossip dispatch") ---
    # "leader" = the FedBuff path above: min reachable id owns the merge,
    # the robust votes, and the reputation clock for its component.
    # "gossip" = leaderless epidemic exchange (bcfl_tpu.dist.gossip): every
    # peer samples seeded neighbors per local round, pushes its full state,
    # and merges arrivals with a commutative version-vector rule — no
    # privileged process, elastic membership (bcfl_tpu.dist.membership).
    dispatch: str = "leader"
    # gossip neighbors contacted per local round (epidemic fan-out, or the
    # ring successor count under gossip_topology="ring")
    gossip_fanout: int = 2
    # neighbor-sampling topology: "epidemic" draws gossip_fanout live peers
    # from a PRNG keyed (seed, round, peer) — replayable; "ring" takes the
    # next gossip_fanout successors around the sorted live view
    gossip_topology: str = "epidemic"
    # HELLO beacon cadence (seconds): each peer periodically hellos one
    # sampled neighbor and any peer answers with a state+chain sync — the
    # steady-state resync that makes join/leave mid-run continuous
    gossip_hello_interval_s: float = 5.0

    def __post_init__(self):
        if self.peers < 2:
            raise ValueError(
                f"runtime='dist' needs >= 2 peers, got {self.peers}")
        if self.buffer < 0 or self.buffer > self.peers:
            raise ValueError(
                f"dist buffer {self.buffer} must be in [0, peers="
                f"{self.peers}] (it counts buffered PEER updates)")
        for name in ("buffer_timeout_s", "idle_timeout_s",
                     "peer_deadline_s", "retry_base_s", "retry_max_s",
                     "send_deadline_s", "probe_interval_s"):
            if getattr(self, name) <= 0:
                raise ValueError(f"{name} must be > 0")
        if self.send_retries < 0:
            raise ValueError(
                f"send_retries must be >= 0, got {self.send_retries}")
        if self.suspect_after < 1:
            raise ValueError(
                f"suspect_after must be >= 1, got {self.suspect_after}")
        if self.down_after < self.suspect_after:
            raise ValueError(
                f"down_after {self.down_after} must be >= suspect_after "
                f"{self.suspect_after} (a peer is SUSPECT before DOWN)")
        if self.detector not in ("phi", "fixed"):
            raise ValueError(
                f"dist detector must be 'phi' or 'fixed', got "
                f"{self.detector!r}")
        for name in ("phi_suspect", "phi_window_floor_s",
                     "deadline_floor_s", "min_bandwidth_bps",
                     "gossip_hedge_phi"):
            if getattr(self, name) <= 0:
                raise ValueError(f"{name} must be > 0")
        if self.phi_down < self.phi_suspect:
            raise ValueError(
                f"phi_down {self.phi_down} must be >= phi_suspect "
                f"{self.phi_suspect} (a peer is SUSPECT before DOWN)")
        if self.phi_window_ceil_s < self.phi_window_floor_s:
            raise ValueError(
                f"phi_window_ceil_s {self.phi_window_ceil_s} must be >= "
                f"phi_window_floor_s {self.phi_window_floor_s}")
        if self.deadline_ceil_s < self.deadline_floor_s:
            raise ValueError(
                f"deadline_ceil_s {self.deadline_ceil_s} must be >= "
                f"deadline_floor_s {self.deadline_floor_s}")
        for name in ("dedup_window", "inbox_max"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1")
        if self.report_every_rounds < 0:
            raise ValueError(
                f"report_every_rounds must be >= 0, got "
                f"{self.report_every_rounds}")
        if self.checkpoint_keep_last < 0:
            raise ValueError(
                f"checkpoint_keep_last must be >= 0 (0 keeps all), got "
                f"{self.checkpoint_keep_last}")
        if not 0.0 < self.quorum_frac <= 1.0:
            raise ValueError(
                f"quorum_frac must be in (0, 1], got {self.quorum_frac}")
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {self.pipeline_depth}")
        if self.resource_sample_s < 0:
            raise ValueError(
                f"resource_sample_s must be >= 0, got "
                f"{self.resource_sample_s}")
        if self.dispatch not in ("leader", "gossip"):
            raise ValueError(
                f"dist dispatch must be 'leader' or 'gossip', got "
                f"{self.dispatch!r}")
        if self.gossip_topology not in ("epidemic", "ring"):
            raise ValueError(
                f"gossip_topology must be 'epidemic' or 'ring', got "
                f"{self.gossip_topology!r}")
        if self.gossip_fanout < 1:
            raise ValueError(
                f"gossip_fanout must be >= 1, got {self.gossip_fanout}")
        if self.dispatch == "gossip" and self.gossip_fanout >= self.peers:
            raise ValueError(
                f"gossip_fanout {self.gossip_fanout} must be < peers "
                f"{self.peers} (a peer cannot gossip to more neighbors "
                "than exist besides itself)")
        if self.gossip_hello_interval_s <= 0:
            raise ValueError(
                f"gossip_hello_interval_s must be > 0, got "
                f"{self.gossip_hello_interval_s}")


# --- runtime capability table (RUNTIME.md §2) --------------------------------
# Every (feature x runtime) combination is either SUPPORTED or rejected by
# this one declared table — the single capability check the acceptance
def parse_lora_ranks(spec: str) -> Tuple[int, ...]:
    """Parse a ``lora_ranks`` spec ("2,4,8") into a tuple of positive ints.
    The spec is cycled over the stacked client axis: client ``i`` trains at
    ``spec[i % len(spec)]``. Raises with the offending token on bad input."""
    try:
        ranks = tuple(int(tok) for tok in spec.split(","))
    except ValueError:
        raise ValueError(
            f"lora_ranks must be comma-separated positive ints "
            f"(e.g. '2,4,8'), got {spec!r}")
    if not ranks or any(r <= 0 for r in ranks):
        raise ValueError(
            f"lora_ranks entries must all be > 0, got {spec!r}")
    return ranks


# contract names. Each row is ``(feature, active, {runtime: verdict})``:
# ``active(cfg)`` says whether the feature is requested, a ``True`` verdict
# means the runtime supports it, and a string verdict is the rejection
# reason raised at config time. ``capability_table(cfg)`` renders the whole
# matrix for docs/tests; FedConfig.__post_init__ walks it once.
RUNTIME_CAPS: Tuple = (
    ("serverless gossip mode",
     lambda c: c.mode == "serverless",
     {"local": True,
      "dist": "the dist runtime's serverless analogue is the leaderless "
              "dispatch, not the local ring-gossip diffusion — use "
              "mode='server' with dist.dispatch='gossip'"}),
    ("simulated-clock sync rounds",
     lambda c: c.sync == "sync",
     {"local": True,
      "dist": "runtime='dist' IS the real-clock async runtime (RUNTIME.md); "
              "set sync='async' — there is no synchronous barrier to run"}),
    ("simulated-clock async (sync='async')",
     lambda c: c.sync == "async",
     {"local": True, "dist": True}),
    ("faithful host-sequential mode",
     lambda c: c.faithful,
     {"local": True,
      "dist": "faithful mode mutates ONE shared model host-sequentially; "
              "peers on different hosts cannot share that model"}),
    ("cohort registry sampling",
     lambda c: c.registry_size > 0,
     {"local": True,
      "dist": "registry sampling re-deals the client set per round; a "
              "peer's client slice is its persistent identity (data, "
              "ledger keys, checkpoints)"}),
    ("tensor parallelism (tp > 1)",
     lambda c: c.tp > 1,
     {"local": True,
      "dist": "each peer builds a single-host client mesh; inner tp "
              "sharding across peers is not implemented"}),
    ("sequence parallelism (sp > 1)",
     lambda c: c.sp > 1,
     {"local": True,
      "dist": "each peer builds a single-host client mesh; inner sp "
              "sharding across peers is not implemented"}),
    ("pod-spanning mesh",
     lambda c: c.pod,
     {"local": True,
      "dist": "runtime='dist' is its own multi-process deployment (one "
              "process per peer group); pod=True is the single-program "
              "jax.distributed path — pick one"}),
    ("buffer donation",
     lambda c: c.donate,
     {"local": True,
      "dist": "peers re-enter their round programs for the whole run; "
              "donated-away input buffers would fail on the second round"}),
    ("fused multi-round dispatch",
     lambda c: c.rounds_per_dispatch > 1,
     {"local": True,
      "dist": "every peer round ends at the transport (send/receive is "
              "host work by construction); there is nothing to fuse "
              "across"}),
    ("anomaly filter",
     lambda c: c.topology.anomaly_filter is not None,
     {"local": True,
      "dist": "anomaly filters gate on the SIMULATED latency graph; the "
              "dist runtime measures real transport and has no global "
              "per-round view to filter"}),
    ("reputation lifecycle",
     lambda c: c.reputation.enabled,
     {"local": True, "dist": True}),  # dist: per-PEER tracker fed by wire
    # evidence (ledger refingerprint mismatches, robust-merge outlier
    # flags, staleness/replay, detector transitions); quarantine refusals
    # are post-ack gate drops and transitions commit to the ledger
    # (bcfl_tpu.reputation.dist, RUNTIME.md §5)
    ("robust aggregators",
     lambda c: c.aggregator != "mean",
     {"local": True, "dist": True}),  # dist: the robust rules run host-
    # side over the buffered ARRIVAL set (bcfl_tpu.dist.robust) —
    # supported WITH declared preconditions on the merge buffer, enforced
    # below at config time (trimmed_mean/median need buffer >= 3; krum
    # needs buffer >= 2f+3 for f = ceil(trim * buffer))
    ("communication compression",
     lambda c: c.compression.enabled,
     {"local": True, "dist": True}),
    ("hash-chained ledger",
     lambda c: c.ledger.enabled,
     {"local": True, "dist": True}),
    ("chaos: transport partition",
     lambda c: c.faults.partitions,
     {"local": True, "dist": True}),  # dist: enforced at the socket layer,
    # groups name PEERS; each connected component forks the ledger chain
    ("chaos: stragglers",
     lambda c: c.faults.straggler_prob > 0,
     {"local": True, "dist": True}),  # dist: a REAL pre-send sleep — the
    # injected delay shows up in the measured staleness distribution
    ("chaos: client dropout",
     lambda c: c.faults.dropout_prob > 0,
     {"local": True,
      "dist": "per-round dropout is a mask over a global stacked round; "
              "dist peers have no global round to mask — not implemented"}),
    ("chaos: transport corruption / flaky bursts",
     lambda c: c.faults.corrupts,
     {"local": True,
      "dist": "per-client corruption scales act on the engine's stacked "
              "in-graph transport stage, which dist rounds never run; use "
              "the wire lane instead (wire_corrupt_prob flips real frame "
              "bytes in flight; the frame CRC and the ledger verify path "
              "catch them)"}),
    ("chaos: wire faults (drop/dup/reorder/delay/corrupt)",
     lambda c: c.faults.wire_enabled,
     {"local": "the local engine has no socket boundary to inject at — "
               "the wire lane acts on real TCP frames in the dist "
               "transport (PeerTransport); use corrupt_prob for the "
               "simulated-transport analogue",
      "dist": True}),
    ("chaos: byzantine peers",
     lambda c: c.faults.byz_enabled,
     {"local": "byzantine behaviors forge the dist update exchange's wire "
               "headers and payloads (stale lineage, digest forgeries, "
               "per-destination equivocation); the local engine exchanges "
               "none of those — use corrupt_prob/flaky_* for the "
               "simulated in-graph analogue",
      "dist": True}),  # injected above the wire (dist/byzantine.py),
    # composable with the wire lane; ROBUSTNESS.md §8 names what evidence
    # catches each behavior
    ("chaos: churn",
     lambda c: c.faults.churns,
     {"local": True,
      "dist": "peer-level churn is the crash/rejoin path (kill and "
              "restart a peer process; scripts/dist_async.py --kill-peer "
              "drives it), not a mask schedule"}),
    ("chaos: host crash",
     lambda c: c.faults.crash_at_round is not None,
     {"local": True,
      "dist": "kill the peer PROCESS instead (scripts/dist_async.py "
              "--kill-peer): a real crash is the thing itself, not a "
              "simulated one"}),
    ("chaos: storage faults",
     lambda c: c.faults.storage_enabled,
     {"local": "the storage lane damages a peer's durable checkpoint/"
               "ledger state at the post-commit seam and exercises the "
               "scrub + STATE_SYNC repair path; the local engine has no "
               "per-peer durable state or peers to repair from — dist "
               "only",
      "dist": True}),  # injected in _maybe_checkpoint after commit+fsync
    # (faults/plan.py lane 8); detection is the startup scrub +
    # restore-time classification, recovery is the ledger-authenticated
    # STATE_SYNC transfer (ROBUSTNESS.md §10)
    ("chaos: limp faults (gray failures)",
     lambda c: c.faults.limp_enabled,
     {"local": "the limp lane stalls a PEER's train seam and throttles "
               "its real TCP links, graded by the adaptive failure "
               "detector and w_slow down-weighting — the local engine "
               "has neither a wire nor a detector; use straggler_prob "
               "for the simulated-clock analogue",
      "dist": True}),  # stall at the train seam, direction-keyed
    # throttle in the transport, SIGSTOP pauses via the harness
    # (faults/plan.py lane 9; ROBUSTNESS.md §11)
    ("chaos: resource faults (ENOSPC/EMFILE)",
     lambda c: c.faults.resource_enabled,
     {"local": "the resource lane fails a peer's durable writes "
               "(checkpoint commit, ledger append, event flush) and "
               "grades the emergency-GC → telemetry-shed → exit ladder; "
               "the local engine has no per-peer durable-write seams — "
               "dist only",
      "dist": True}),  # drawn per (seam, counter, peer) at the write
    # seams (faults/plan.py lane 10; ROBUSTNESS.md §11)
    # --- gossip-dispatch composition rows (RUNTIME.md "Gossip dispatch"):
    # active only when the dist runtime is asked for dispatch='gossip', so
    # they never fire for local runs or the leadered dist path ---
    ("communication compression under gossip dispatch",
     lambda c: c.compression.enabled and c.dist.dispatch == "gossip",
     {"local": True,
      "dist": "the codec wire encodes DELTAS against a shared adopted "
              "base version; gossip peers merge concurrently with no "
              "common base to delta against — use compress='none'"}),
    ("krum under gossip dispatch",
     lambda c: c.aggregator == "krum" and c.dist.dispatch == "gossip",
     {"local": True,
      "dist": "krum selects ONE vote from a population; over a gossip "
              "peer's tiny neighbor arrival set the selection guarantee "
              "is vacuous and the merge would just adopt one neighbor "
              "verbatim — use trimmed_mean or median"}),
    ("chaos: transport partition under gossip dispatch",
     lambda c: c.faults.partitions and c.dist.dispatch == "gossip",
     {"local": True, "dist": True}),  # dist: supported LEADERLESSLY
    # (RUNTIME.md §9, ROBUSTNESS.md §6): during the span each component
    # keeps converging on its own clocks — neighbor draws stay inside
    # the gate component, the merge seam rejects frames buffered across
    # the cut (the gossip scope of no_cross_partition_merge), and a
    # component below the robust vote floor degrades to the commutative
    # mean with a catalogued gossip.vote_floor event. The heal has no
    # arbiter: HELLO probes re-establish contact (the dormant-peer probe
    # lane prevents split-brain-forever), version-vector merges absorb
    # the other side's frontier, and per-peer chains reconcile pairwise
    # through fork_point/verify_segment/merge_rows/adopt_merge.
    # Preconditions: partition_groups name PEERS and the span is keyed
    # on each peer's OWN autonomous round clock (validated below);
    # proven by the chaos_smoke gossip-partition leg and
    # scripts/dist_soak.py --partition
    ("per-round central eval",
     lambda c: c.eval_every != 0,
     {"local": True,
      "dist": "per-round central eval would serialize the async runtime "
              "behind the leader; set eval_every=0 — the leader "
              "evaluates the final global once at shutdown"}),
    ("LoRA adapter exchange",
     lambda c: c.lora_rank > 0 or bool(c.lora_ranks),
     {"local": True, "dist": True}),  # dist: with lora_rank > 0 the
    # trainable tree IS the adapter tree, so update/broadcast frames,
    # leader refingerprint, robust merge votes, byzantine evidence, and
    # HELLO/checkpoint resync all carry KB-scale adapter payloads — the
    # full-model frame never crosses the wire (RUNTIME.md, COMPRESSION.md
    # "Adapter exchange"; gated by scripts/lora_comm.py)
    ("heterogeneous LoRA ranks",
     lambda c: bool(c.lora_ranks) and len(set(parse_lora_ranks(c.lora_ranks))) > 1,
     {"local": True,
      "dist": "each dist peer compiles round programs over its own client "
              "slice; the rank-aware padded aggregation (RBLA) is defined "
              "over the single-process stacked client axis — use a uniform "
              "lora_rank"}),
)


def capability_table(cfg: "FedConfig") -> Tuple[Tuple[str, bool, object], ...]:
    """The resolved (feature, active, verdict) rows for ``cfg``'s runtime —
    ``verdict`` is True (supported) or the rejection reason string."""
    return tuple(
        (feature, bool(active(cfg)), verdicts[cfg.runtime])
        for feature, active, verdicts in RUNTIME_CAPS)


@dataclasses.dataclass(frozen=True)
class FedConfig:
    # --- experiment identity ---
    name: str = "fed"
    seed: int = 42  # reference seeds dataset shuffle with 42 (server_IID_IMDB.py:68)
    # typed-key PRNG implementation: None = jax's default (threefry).
    # "rbg" opts into the TPU hardware generator — dropout RNG is +38% of
    # step time under threefry (PERF.md). Both are deterministic given the
    # seed, but they are DIFFERENT streams: changing this mid-experiment is
    # like changing the seed (checkpoints record it; resume verifies).
    prng_impl: Optional[str] = None

    # --- data ---
    dataset: str = "synthetic"  # key into bcfl_tpu.data.datasets registry
    text_col: str = "text"
    label_col: str = "labels"
    num_labels: int = 2
    seq_len: int = 128
    batch_size: int = 32  # reference: batch_size=32 (server_IID_IMDB.py:96-99)
    vocab_size: int = 8192  # hash-tokenizer vocab (HF tokenizers override this)
    tokenizer: str = "hash"  # "hash" | HF tokenizer name

    # --- task ---
    # "classification" = the reference's task (sequence classification);
    # "causal_lm" = federated next-token fine-tuning on the client corpora
    # (llama family only — the capability the BASELINE.json Llama-LoRA
    # config exists for; labels columns are ignored, ids are the targets)
    task: str = "classification"

    # --- model ---
    model: str = "tiny-bert"  # key into bcfl_tpu.models registry
    hf_checkpoint: Optional[str] = None  # e.g. "albert-base-v2" to import weights
    lora_rank: int = 0  # 0 = full fine-tune (reference behaviour); >0 = LoRA
    # per-client LoRA rank spec for HETEROGENEOUS fleets (RBLA, arXiv
    # 2408.08699): comma-separated ints cycled over the stacked client axis
    # — "2,4,8" means client i trains at rank spec[i % 3]. Mutually
    # exclusive with lora_rank; __post_init__ canonicalizes lora_rank to
    # max(spec) so every existing `lora_rank > 0` switch (adapter-tree
    # trainable, tp gating, dist adapter wire) sees the cohort ceiling.
    # Clients are materialized zero-padded at that max rank; the padding
    # mask is static in this spec, so heterogeneous fleets add zero
    # per-round retraces. "" = uniform (lora_rank applies to everyone).
    lora_ranks: str = ""
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # None = the model family's default (llama: flash on from seq 512;
    # encoders: dense). True forces the O(S)-memory blockwise/Pallas
    # attention path — the long-context switch, reachable from the CLI
    use_flash: Optional[bool] = None
    # per-layer activation rematerialization. Encoders and llama keep each
    # layer's input and recompute the layer in the backward pass:
    # O(num_layers) less activation HBM for ~1/3 more FLOPs, so more
    # full-fine-tune clients stack per chip. The latent_moe family keeps a
    # named set of each layer's values as well (models/latent_moe.py::
    # REMAT_SAVED: no product and no kernel of the forward pass runs again):
    # 53 KB a position a layer at the published widths in bfloat16, times
    # batch_size x seq_len x the clients a device stacks x layers (3.5 GB
    # for 8,192 positions and 8 layers; ``FedEngine.remat_saved`` has the
    # figure for a job). A job must fit that beside its base; there is no
    # switch back to keeping the input alone.
    remat: bool = False
    # donate each round's input param/opt buffers to the round program:
    # XLA aliases them into the outputs, halving per-round peak HBM (the
    # difference between 10 x BERT-base full fine-tune fitting a 16 GB chip
    # or not). The engine chains carries, so semantics are unchanged; the
    # one restriction is that engine.run() is single-shot (round 1 consumes
    # the initial tree) — a second run() raises instead of recomputing.
    # Scope: the sync server/gossip round programs (per-round and fused,
    # incl. the fused ledger *_fp path). The async/faithful paths and the
    # per-round split-phase ledger flow run undonated programs — there the
    # flag is a warning-emitting no-op.
    donate: bool = False

    # --- scale-out (SURVEY.md §2.5: the two axes the reference lacks) ---
    # tensor-parallel shards per client: tp > 1 builds a 2-D (clients, tp)
    # mesh, shards the FROZEN base megatron-style (requires lora_rank > 0 —
    # adapters stay per-client), and runs the same GSPMD round programs
    tp: int = 1
    # sequence-parallel shards per client: sp > 1 builds a 2-D
    # (clients, seq) mesh and swaps the model's attention for exact ring
    # attention over the seq axis (bcfl_tpu.parallel.sp) — each client's
    # ACTIVATIONS shard over the sequence, params stay replicated in the
    # group. Long-document federated fine-tuning; both model families
    # (encoders ride the non-causal ring).
    sp: int = 1
    # build the mesh over every host in the pod (jax.distributed must be
    # initialized first — core.mesh.distributed_init); devices are ordered
    # hosts-major so collectives ride ICI and cross DCN once
    pod: bool = False

    # --- federated topology ---
    # "local" = the whole federation runs in THIS process (simulated clock
    # for sync="async"; every pre-existing behaviour, bit-for-bit).
    # "dist"  = real multi-process async P2P runtime (bcfl_tpu.dist,
    # RUNTIME.md): each peer is an OS process owning a client slice, update
    # exchange rides length-prefixed TCP (the codec wire format + ledger
    # fingerprint digests), aggregation is FedBuff-buffered with MEASURED
    # staleness, and a transport partition genuinely forks the ledger
    # chain per connected component. Feature composition is governed by
    # RUNTIME_CAPS below — one declared capability check at config time.
    runtime: str = "local"
    mode: str = "server"  # "server" (centralized FedAvg) | "serverless" (P2P gossip)
    # "sync" | "async". With runtime="local", async is SIMULATED asynchrony
    # under a deterministic network clock: one buffered (FedBuff-style)
    # aggregation event per engine round, arrival order from the latency
    # graph + chaos straggler delays, staleness decay on merged deltas. It
    # is NOT wall-clock concurrency — see PARALLELISM.md "Async semantics"
    # for the real-clock vs simulated-clock contract side by side. For
    # actual wall-clock concurrency (measured staleness, real transport)
    # use runtime="dist", which REQUIRES sync="async".
    sync: str = "sync"
    num_clients: int = 4
    # --- cohort-batched client scale-out (SCALING.md "Cohort mode") ---
    # registry_size > 0 turns on client sampling: the run simulates a
    # registry of this many clients (data-partition identity, PRNG streams,
    # fault schedules, reputation and error-feedback state are all keyed by
    # registry id — host arrays sized by the registry), while each round a
    # seeded sampler draws only `sample_clients` of them onto the stacked
    # mesh axis. Device/HBM cost is bounded by the cohort, not the registry;
    # per-round wall scales with the sampled cohort (sublinear in registry
    # size). 0 = off (every client is a mesh slot every round — the
    # pre-cohort behaviour, unchanged).
    registry_size: int = 0
    # per-round sampled cohort size (the stacked client axis width when
    # sampling); 0 = fall back to num_clients. Must be <= registry_size.
    sample_clients: int = 0
    # clients stacked per device (the vmapped axis per mesh shard): > 0 pins
    # the mesh to exactly sample_clients/cohort_size devices instead of the
    # largest-divisor default. Must divide the sampled cohort size.
    cohort_size: int = 0
    num_rounds: int = 2
    local_epochs: int = 1  # reference: 1 epoch per round (server_IID_IMDB.py:172)
    max_local_batches: Optional[int] = None  # cap scan length (static shape)
    # fuse up to this many federated rounds into ONE XLA dispatch when the
    # host isn't needed between them (sync server FedAvg or sync parallel
    # serverless gossip — not faithful mode; no ledger, no anomaly filter) —
    # amortizes the per-dispatch host round trip. Chunks never cross an eval or checkpoint boundary,
    # so observable cadence is unchanged.
    rounds_per_dispatch: int = 1
    # True  = example-weighted FedAvg (Flower's aggregate, server mode)
    # False = unweighted mean (reference serverless ":296" semantics)
    weighted_agg: bool = True
    # Byzantine-robust aggregation rule, compiled INTO the round programs
    # (ROBUSTNESS.md). "mean" is the reference behaviour; the robust rules
    # are per-coordinate order statistics / update selection over the
    # PARTICIPATING clients (mask/auth-aware) and deliberately ignore
    # example weighting (weighted_agg) — order statistics have no sound
    # notion of fractional votes:
    #   trimmed_mean — drop the ceil(aggregator_trim * k) highest and lowest
    #                  values per coordinate, mean the rest,
    #   median       — coordinate-wise median of participating updates,
    #   krum         — select the single update closest to its k-f-2 nearest
    #                  neighbours (f = ceil(aggregator_trim * k)).
    # In sync="async" mode the participation-only rule also flattens the
    # PER-CLIENT staleness decay inside the merge (a stale arrival votes at
    # full strength); the global step-size rescale (_async_merge_scale)
    # still shrinks the applied delta, so staleness dampens the step, not
    # the vote.
    aggregator: str = "mean"
    # assumed Byzantine fraction for trimmed_mean/krum, in [0, 0.5)
    aggregator_trim: float = 0.2
    # faithful=True reproduces the reference serverless quirk where clients
    # sequentially mutate ONE shared model within a round
    # (serverless_NonIID_IMDB.py:288 — see SURVEY.md §3.2)
    faithful: bool = False

    # --- optimizer (reference: fresh AdamW lr=5e-5 each round, server_IID_IMDB.py:109) ---
    learning_rate: float = 5e-5
    optimizer: str = "adamw"
    max_grad_norm: float = 0.0  # 0 = off (reference has no clipping)

    # --- async scheduling ---
    async_buffer: int = 0  # aggregate when this many clients arrived (0 = num_clients)
    staleness_decay: float = 0.5  # weight = decay ** staleness
    # server step size along the staleness-weighted mean client delta
    # (FedBuff-style buffered aggregation)
    async_server_lr: float = 1.0

    # --- sub-configs ---
    partition: PartitionConfig = dataclasses.field(default_factory=PartitionConfig)
    topology: TopologyConfig = dataclasses.field(default_factory=TopologyConfig)
    ledger: LedgerConfig = dataclasses.field(default_factory=LedgerConfig)
    # fault-injection schedule (bcfl_tpu.faults, ROBUSTNESS.md); the default
    # plan injects nothing
    faults: FaultPlan = dataclasses.field(default_factory=FaultPlan)
    # peer-lifecycle reputation (bcfl_tpu.reputation, ROBUSTNESS.md §6):
    # EWMA trust over per-round evidence (ledger-auth failures, anomaly
    # flags, corruption hits, staleness) drives HEALTHY -> SUSPECT ->
    # QUARANTINED -> PROBATION -> HEALTHY; quarantined peers are excluded
    # from aggregation for a configurable window and readmitted at reduced
    # vote weight. Host-side state, checkpointed; disabled by default.
    reputation: ReputationConfig = dataclasses.field(
        default_factory=ReputationConfig)
    # communication compression for the update exchange (COMPRESSION.md):
    # kind ∈ none/int8/topk/int8+topk — quantized and/or sparsified client
    # deltas with error-feedback residuals, compiled INTO the round
    # programs. 'none' (default) is bit-identical to the uncompressed
    # programs. The faithful host-sequential mode has no
    # transport stage to compress (rejected below). kernel_impl ∈
    # auto/xla/pallas selects the codec kernels (PERF.md "Custom
    # kernels"); every impl's payload is byte-identical, so it never
    # affects wire bytes, digests, or resume.
    compression: CompressionConfig = dataclasses.field(
        default_factory=CompressionConfig)

    # multi-process P2P runtime knobs (runtime="dist" only; RUNTIME.md)
    dist: DistConfig = dataclasses.field(default_factory=DistConfig)

    # --- checkpoint / metrics ---
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0  # rounds; 0 = off
    # evaluate every Nth round (the final round always evaluates);
    # 0 = never evaluate, INCLUDING the final round (pure-throughput runs)
    eval_every: int = 1
    # cap the central-eval set to this many batches (None = the full test
    # split, the reference's evaluate_global_model behaviour); small hosts
    # use a cap so per-round eval doesn't dominate wall-clock
    max_eval_batches: Optional[int] = None
    # jax.profiler trace output dir (TensorBoard/Perfetto); None = off.
    # The reference's only profiling is psutil+wall-clock (SURVEY.md §5).
    profile_dir: Optional[str] = None

    # --- event telemetry (OBSERVABILITY.md) ---
    # crash-safe per-process JSONL event streams (bcfl_tpu.telemetry):
    # round/phase spans, transport send/retry/ack/dedup, failure-detector
    # transitions, chaos injections, FedBuff merge lineage, ledger
    # commit/fork/heal, checkpoint and reputation events — collated into
    # one causally-ordered timeline by `bcfl-tpu trace`.
    #   None  = the dist runtime streams into its run dir (telemetry is
    #           how chaos runs are gated, so it defaults ON there); the
    #           local engine emits nothing,
    #   "off" = disabled everywhere (the overhead-measurement setting),
    #   path  = stream into this directory on both runtimes.
    telemetry_dir: Optional[str] = None
    # deterministic sampling rate in [0, 1] for HIGH-RATE transport events
    # (per-attempt outcomes, chaos draws). Invariant-grade events (final
    # send outcomes, receive dispositions, merge lineage) are never
    # sampled — the invariant checks stay exact at any setting.
    telemetry_sample: float = 1.0

    def __post_init__(self):
        if self.lora_ranks:
            spec = parse_lora_ranks(self.lora_ranks)  # validates the spec
            if self.lora_rank > 0:
                raise ValueError(
                    "set lora_ranks OR lora_rank, not both: lora_ranks is "
                    "the per-client spec and canonicalizes lora_rank to "
                    "max(spec)")
            # canonicalize BEFORE the capability walk so every existing
            # `lora_rank > 0` switch sees the cohort max rank
            object.__setattr__(self, "lora_rank", max(spec))
        if self.runtime not in ("local", "dist"):
            raise ValueError(f"unknown runtime: {self.runtime!r}")
        if self.mode not in ("server", "serverless"):
            raise ValueError(f"unknown mode: {self.mode!r}")
        if self.sync not in ("sync", "async"):
            raise ValueError(f"unknown sync: {self.sync!r}")
        # the ONE capability check (RUNTIME_CAPS above): every requested
        # feature is either supported on this runtime or rejected here with
        # the table's declared reason — no per-path surprise rejections later
        for feature, active, verdict in capability_table(self):
            if active and verdict is not True:
                raise ValueError(
                    f"{feature} is not supported on runtime="
                    f"{self.runtime!r}: {verdict}")
        # what the model's family cannot run (models.refusals): refused here
        # with the reason, never silently replicated or skipped
        from bcfl_tpu.models import refusals

        refused = refusals(self.model, task=self.task, lora_rank=self.lora_rank,
                           tp=self.tp, sp=self.sp,
                           client_lora_ranks=self.client_lora_ranks)
        if refused:
            raise ValueError(refused)
        if self.runtime == "dist":
            if self.num_clients % self.dist.peers:
                raise ValueError(
                    f"num_clients {self.num_clients} must split evenly "
                    f"over {self.dist.peers} peers (each peer owns a "
                    "fixed client slice)")
            if self.faults.partitions and self.faults.partition_groups:
                bad = [i for g in self.faults.partition_groups for i in g
                       if i >= self.dist.peers]
                if bad:
                    raise ValueError(
                        f"dist partition_groups name PEERS; ids {bad} are "
                        f">= peers={self.dist.peers}")
            if (self.faults.partitions
                    and self.faults.partition_count > self.dist.peers):
                raise ValueError(
                    f"dist partition_count {self.faults.partition_count} "
                    f"> peers {self.dist.peers}")
            if self.faults.byz_enabled:
                bad = [p for p in self.faults.byz_peers
                       if p >= self.dist.peers]
                if bad:
                    raise ValueError(
                        f"byz_peers name PEERS; ids {bad} are >= peers="
                        f"{self.dist.peers}")
                if len(self.faults.byz_peers) >= self.dist.peers:
                    raise ValueError(
                        "byz_peers lists EVERY peer: an all-adversarial "
                        "federation has no honest majority for any rule "
                        "to defend — leave at least one peer honest")
            if self.faults.storage_enabled:
                if self.faults.storage_peers:
                    bad = [p for p in self.faults.storage_peers
                           if p >= self.dist.peers]
                    if bad:
                        raise ValueError(
                            f"storage_peers name PEERS; ids {bad} are >= "
                            f"peers={self.dist.peers}")
                for srv, req in (self.faults.sync_tamper or ()):
                    if srv >= self.dist.peers or req >= self.dist.peers:
                        raise ValueError(
                            f"sync_tamper pair ({srv}, {req}) names PEERS; "
                            f"ids must be < peers={self.dist.peers}")
                if not self.dist.checkpoint_every_versions:
                    raise ValueError(
                        "the storage fault lane injects at the checkpoint "
                        "commit seam; checkpoint_every_versions=0 never "
                        "writes one, so the lane would silently never "
                        "fire — enable checkpointing or drop the lane")
                if not self.ledger.enabled:
                    raise ValueError(
                        "the storage lane's repair path authenticates "
                        "STATE_SYNC transfers against the hash chain "
                        "(commitment rows + verify_segment); without "
                        "ledger.enabled there is no root of trust to "
                        "verify a transfer against — enable the ledger "
                        "or drop the lane")
            if self.faults.limp_enabled and self.faults.limp_peers:
                bad = [p for p in self.faults.limp_peers
                       if p >= self.dist.peers]
                if bad:
                    raise ValueError(
                        f"limp_peers name PEERS; ids {bad} are >= peers="
                        f"{self.dist.peers}")
            if self.faults.resource_enabled and self.faults.resource_peers:
                bad = [p for p in self.faults.resource_peers
                       if p >= self.dist.peers]
                if bad:
                    raise ValueError(
                        f"resource_peers name PEERS; ids {bad} are >= "
                        f"peers={self.dist.peers}")
            if self.aggregator != "mean":
                # robust aggregators are supported on dist WITH declared
                # preconditions on the merge buffer (RUNTIME.md §5): the
                # arrival set is the estimator's population, so the
                # buffer target must be large enough for the rule's
                # breakdown point to mean anything. Quorum degradation
                # can still shrink a given merge below these minima at
                # runtime — such merges aggregate with clamped trim and
                # are recorded `robust_degraded`.
                # the precondition math lives in bcfl_tpu.dist.robust
                # (MIN_ORDER_VOTES / krum_min_buffer) — the same source
                # the runtime's robust_degraded threshold reads, so
                # config-time acceptance and runtime grading can't drift
                from bcfl_tpu.dist.robust import (
                    MIN_ORDER_VOTES,
                    krum_min_buffer,
                )

                if self.dist.dispatch == "gossip":
                    # gossip has no leader buffer: the rule's population
                    # is a peer's local round arrival set — at most its
                    # sampled neighbors plus its own state. krum is
                    # already rejected by the caps table above.
                    if self.dist.gossip_fanout + 1 < MIN_ORDER_VOTES:
                        raise ValueError(
                            f"aggregator={self.aggregator!r} under "
                            f"dispatch='gossip' needs gossip_fanout >= "
                            f"{MIN_ORDER_VOTES - 1} (got "
                            f"{self.dist.gossip_fanout}): the rule's "
                            "population is a peer's neighbor arrival set "
                            "plus itself, and an order statistic over < "
                            f"{MIN_ORDER_VOTES} votes excludes nothing")
                eff = self.dist.buffer or 1
                if (self.aggregator in ("trimmed_mean", "median")
                        and self.dist.dispatch != "gossip"):
                    if eff < MIN_ORDER_VOTES:
                        raise ValueError(
                            f"aggregator={self.aggregator!r} on "
                            f"runtime='dist' needs dist.buffer >= "
                            f"{MIN_ORDER_VOTES} (got {eff}): the rule's "
                            "population is the buffered arrival set, and "
                            f"an order statistic over < {MIN_ORDER_VOTES} "
                            "votes excludes nothing")
                if self.aggregator == "krum":
                    need = krum_min_buffer(eff, self.aggregator_trim)
                    if eff < need:
                        raise ValueError(
                            f"aggregator='krum' on runtime='dist' needs "
                            f"dist.buffer >= 2f+3 = {need} for f = "
                            f"ceil(aggregator_trim * buffer) "
                            f"(got buffer {eff}): below that the "
                            "classical selection guarantee is vacuous")
        if self.num_clients < 1 or self.num_rounds < 1:
            raise ValueError("num_clients and num_rounds must be >= 1")
        if self.eval_every < 0:
            # 0 = never evaluate (pure-throughput runs); negative cadences
            # would silently produce modulo surprises
            raise ValueError(f"eval_every must be >= 0, got {self.eval_every}")
        if not 0.0 <= self.telemetry_sample <= 1.0:
            raise ValueError(
                f"telemetry_sample must be in [0, 1], got "
                f"{self.telemetry_sample}")
        if self.task not in ("classification", "causal_lm"):
            raise ValueError(f"unknown task: {self.task!r}")
        if self.prng_impl not in (None, "threefry", "rbg", "unsafe_rbg"):
            raise ValueError(
                "prng_impl must be None/threefry/rbg/unsafe_rbg, "
                f"got {self.prng_impl!r}")
        for field in ("param_dtype", "compute_dtype"):
            if getattr(self, field) not in ("float32", "bfloat16", "float16"):
                raise ValueError(
                    f"{field} must be float32/bfloat16/float16, "
                    f"got {getattr(self, field)!r}")
        if self.tp < 1 or self.sp < 1:
            raise ValueError(f"tp/sp must be >= 1, got {self.tp}/{self.sp}")
        if self.tp > 1 and self.sp > 1:
            raise ValueError("pick ONE inner mesh axis per run: tp or sp")
        if self.sp > 1 and self.seq_len % self.sp:
            raise ValueError(
                f"seq_len {self.seq_len} must be divisible by sp={self.sp} "
                "(ring attention shards the sequence into sp equal blocks)")
        if self.aggregator not in ("mean", "trimmed_mean", "median", "krum"):
            raise ValueError(
                "aggregator must be mean/trimmed_mean/median/krum, "
                f"got {self.aggregator!r}")
        if not 0.0 <= self.aggregator_trim < 0.5:
            # >= 0.5 would trim every client (2t >= k) / assume a Byzantine
            # majority, which no aggregation rule can survive
            raise ValueError(
                f"aggregator_trim must be in [0, 0.5), got "
                f"{self.aggregator_trim}")
        if self.faults.corrupts and self.faithful:
            raise ValueError(
                "FaultPlan corruption (incl. flaky bursts) models transport "
                "of the parallel paths' stacked updates; faithful "
                "(host-sequential) mode has no transport stage — use the "
                "tamper_hook shim there")
        if self.faults.partitions and self.runtime == "local":
            # the partition lane routes partitioned rounds through the
            # stacked split-phase flow with per-component aggregation
            # (ROBUSTNESS.md §6); paths with no per-component form are
            # rejected here rather than silently aggregating across a
            # partition that is supposed to exist. runtime='dist' is exempt
            # from this block: there the partition is enforced at the
            # SOCKET layer over peers (RUNTIME.md) and composes with the
            # real-clock async exchange by construction.
            if self.sync == "async":
                raise ValueError(
                    "chaos partition is not implemented for sync='async': "
                    "the buffered FedBuff merge has one global arrival "
                    "queue, and per-component queues would be a different "
                    "algorithm, not a fault model")
            if self.faithful:
                raise ValueError(
                    "chaos partition is not implemented for faithful "
                    "(host-sequential) mode — clients share ONE model, so "
                    "there is nothing to partition")
            if self.mode == "serverless" and self.topology.gossip_steps > 0:
                raise ValueError(
                    "chaos partition with ring-gossip diffusion "
                    "(gossip_steps > 0) would need a per-component ring — "
                    "a mesh reshape the fault model forbids; use "
                    "gossip_steps=0 (exact mean) for partitioned "
                    "serverless runs")
        if self.aggregator != "mean" and self.faithful:
            # the faithful path averages snapshots host-side with a plain
            # weighted sum; silently running that under a robust-aggregator
            # label would fake Byzantine protection
            raise ValueError(
                f"aggregator={self.aggregator!r} is not implemented for "
                "faithful (host-sequential) mode — it always aggregates "
                "with the reference's plain mean")
        if self.compression.enabled and self.faithful:
            # the faithful path host-sequentially mutates ONE shared model;
            # there is no per-client update exchange, so 'compressing the
            # wire' would be a label with no wire under it
            raise ValueError(
                f"compress={self.compression.kind!r} is not implemented for "
                "faithful (host-sequential) mode — it exchanges no update "
                "trees to compress")
        if self.tp > 1 and self.lora_rank <= 0:
            raise ValueError(
                "tp > 1 tensor-shards the FROZEN base and keeps per-client "
                "LoRA adapters; set lora_rank > 0 (full fine-tune is 1-D "
                "clients-only)")
        if self.lora_ranks and len(set(parse_lora_ranks(self.lora_ranks))) > 1:
            # heterogeneous ranks: the stacked adapter tree carries
            # STRUCTURAL zero padding per client (models/lora.py), and only
            # the rank-aware RBLA mean knows which coordinates are padding
            if self.aggregator != "mean":
                raise ValueError(
                    f"aggregator={self.aggregator!r} does not compose with "
                    "heterogeneous lora_ranks: order statistics have no "
                    "sound definition over structural zero padding (a "
                    "low-rank client's padded coordinate would vote an "
                    "exact 0 into every trim/median/krum decision) — use "
                    "aggregator='mean' (the rank-aware RBLA rule)")
            if self.mode != "server":
                raise ValueError(
                    "heterogeneous lora_ranks require mode='server': ring "
                    "gossip mixes whole neighbor trees, and the rank-aware "
                    "padded aggregation (RBLA) has no per-edge ring form")
            if self.faithful:
                raise ValueError(
                    "heterogeneous lora_ranks are not implemented for "
                    "faithful (host-sequential) mode — it averages host-"
                    "side with the reference's plain mean, which would "
                    "dilute low-rank clients' padded coordinates")
            if self.registry_size > 0:
                raise ValueError(
                    "heterogeneous lora_ranks do not compose with registry "
                    "sampling: ranks are cycled over the FIXED stacked "
                    "client slots, while sampling re-deals which registry "
                    "client sits in each slot every round — a client's "
                    "rank would change under it")
        if self.async_buffer < 0:
            raise ValueError(
                f"async_buffer must be >= 0, got {self.async_buffer}")
        if self.async_buffer > self.num_clients:
            # an oversized buffer can never fill: K arrivals would be waited
            # on forever while only num_clients exist — fail at config time
            # instead of silently degenerating
            raise ValueError(
                f"async_buffer {self.async_buffer} > num_clients "
                f"{self.num_clients}: the buffer could never fill (use 0 "
                "for 'aggregate when everyone arrived')")
        # --- cohort-mode capability table (SCALING.md "Cohort mode") ---
        for field in ("registry_size", "sample_clients", "cohort_size"):
            if getattr(self, field) < 0:
                raise ValueError(
                    f"{field} must be >= 0, got {getattr(self, field)}")
        if self.registry_size == 0 and (self.sample_clients
                                        or self.cohort_size):
            raise ValueError(
                "sample_clients/cohort_size have no effect without "
                "registry_size > 0 (they shape the sampled cohort of a "
                "client registry) — the same fail-loudly stance as the "
                "codec sub-flags")
        if self.registry_size > 0:
            active = self.sample_clients or self.num_clients
            if active > self.registry_size:
                raise ValueError(
                    f"sampled cohort {active} > registry_size "
                    f"{self.registry_size}: cannot draw without replacement")
            if self.cohort_size and active % self.cohort_size:
                raise ValueError(
                    f"cohort_size {self.cohort_size} must divide the "
                    f"sampled cohort size {active} (it is the per-device "
                    "stack of the cohort mesh)")
            if self.cohort_size and self.pod:
                # the pin truncates the device list to exactly
                # cohort/cohort_size shards; on a multi-host pod that can
                # exclude another process's addressable devices, which
                # fails at first dispatch with an opaque device-assignment
                # error — reject here instead
                raise ValueError(
                    "cohort_size is a single-host per-device-stack pin and "
                    "does not compose with pod=True (truncating the "
                    "hosts-major pod device list would strand other "
                    "processes' devices); leave cohort_size=0 and let "
                    "client_mesh lay the cohort over the full pod")
            # declared capability table: what composes with sampling today.
            # Aggregators (incl. robust rules), compression, ledger auth,
            # reputation, and the dropout/straggler/corrupt/churn/flaky
            # chaos lanes all compose (ids are registry ids). The paths
            # below hold per-client state the registry cannot carry — they
            # are rejected loudly rather than silently resampling it away.
            if self.mode != "server":
                raise ValueError(
                    "registry sampling requires mode='server': serverless "
                    "peers carry persistent per-client params, which a "
                    "registry >> cohort cannot keep resident (the stacked "
                    "tree IS the peer state)")
            if self.sync != "sync":
                raise ValueError(
                    "registry sampling is not implemented for sync='async': "
                    "the simulated network clock tracks per-client "
                    "completion/staleness for a FIXED client set, and a "
                    "per-round cohort would redefine that state each round")
            if self.faithful:
                raise ValueError(
                    "registry sampling is not implemented for faithful "
                    "(host-sequential) mode")
            if self.faults.partitions:
                raise ValueError(
                    "chaos partition does not compose with registry "
                    "sampling: components are defined over the full client "
                    "set, and a per-round cohort would dissolve them")

    @property
    def resolved_prng_impl(self) -> Optional[str]:
        """jax's registered name for ``prng_impl``: the config (and CLI)
        accept the colloquial ``"threefry"``, but jax registers the impl as
        ``"threefry2x32"`` — passing the config value straight to
        ``jax.random.key(impl=...)`` raised on the documented default's
        explicit spelling. None passes through (jax's process default)."""
        return ("threefry2x32" if self.prng_impl == "threefry"
                else self.prng_impl)

    @property
    def lora_rank_spec(self) -> Optional[Tuple[int, ...]]:
        """Parsed ``lora_ranks`` tuple, or None when unset (uniform rank)."""
        return parse_lora_ranks(self.lora_ranks) if self.lora_ranks else None

    @property
    def client_lora_ranks(self) -> Optional[Tuple[int, ...]]:
        """Per-client rank assignment — the spec cycled over the stacked
        client axis (length ``num_clients``), or None when uniform. This
        tuple is the static input to the padding mask and the program-cache
        key, so same spec + same fleet = same compiled program."""
        spec = self.lora_rank_spec
        if spec is None:
            return None
        return tuple(spec[i % len(spec)] for i in range(self.num_clients))

    def replace(self, **kw) -> "FedConfig":
        return dataclasses.replace(self, **kw)
