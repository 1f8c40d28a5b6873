"""Federated round engines — the orchestration layer.

Replaces both reference orchestrators with one config-driven loop
(SURVEY.md §1 L3a/L3b):

- ``mode="server"``  — centralized FedAvg (reference: Flower
  ``start_simulation`` + ``FedAvg`` strategy, ``server_IID_IMDB.py:205-218``),
- ``mode="serverless"`` — P2P gossip (reference: hand-rolled round loop +
  all-client mean, ``serverless_NonIID_IMDB.py:284-318``), with
  ``faithful=True`` reproducing the reference's sequential shared-model quirk
  exactly (clients mutate ONE model within a round — ``:288``, SURVEY.md §3.2),
- ``sync="async"`` — buffered asynchronous aggregation (FedBuff-style) under a
  simulated network clock derived from the latency graph; the reference only
  *models* asynchrony as max-instead-of-sum info-passing time (MT nb cell 23).

Per round the host control plane:
1. runs the anomaly filter over the latency graph -> participation mask
   (reference: offline notebook cells, never wired in — here it gates psum),
   composed with the fault plan's injected client dropout,
2. (ledger mode) commits each client's update digest to the hash chain,
   simulates transport (the fault plan's corruption stage), re-verifies
   digests, and zeroes the mask of any client whose shipped update fails
   authentication,
3. launches the compiled round program on the mesh (aggregation rule =
   ``cfg.aggregator``: mean or a Byzantine-robust statistic, ROBUSTNESS.md),
4. records the reference metric set + info-passing times (straggler delays
   from the fault plan included).

Fault injection (dropout / stragglers / corruption / host crash) is driven
by ``cfg.faults`` (:class:`bcfl_tpu.faults.FaultPlan`); an all-eliminated
round keeps the previous global model and is recorded ``degraded`` instead
of emitting a 0/0 NaN mean.

Peer lifecycle (ROBUSTNESS.md §6): ``cfg.reputation`` enables the
HEALTHY -> SUSPECT -> QUARANTINED -> PROBATION state machine
(:mod:`bcfl_tpu.reputation`) — per-round evidence (ledger-auth failures,
anomaly flags, corruption hits, staleness) drives an EWMA trust score whose
gate multiplier folds into the participation mask: quarantined peers carry
weight 0 for a configurable window, probation peers a reduced vote weight.
The chaos plan's **partition** lane routes the affected rounds through
:meth:`_partitioned_round` (per-component aggregation over the stacked
client view, robust reconciliation on heal); **churn** composes permanent
leave / late join into the mask; **flaky** bursts ride the corruption
transport stage. All of it is host-side mask/weight arithmetic feeding the
already-compiled programs — no per-round retraces.

Cohort-batched scale-out (SCALING.md "Cohort mode"): with
``cfg.registry_size > 0`` the run simulates a REGISTRY of clients far larger
than the mesh — per-client identity (data partition, PRNG stream, fault
schedule, reputation, EF residuals) is keyed by registry id in host state,
and each round a seeded sampler (:mod:`bcfl_tpu.fed.cohort`) draws
``sample_clients`` of them onto the stacked axis. The compiled programs and
their shapes never change (cohort ids are runtime values), aggregation runs
the explicit hierarchical within-device-stack -> cross-device reduction, and
device memory is bounded by the cohort, not the registry.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import time
import warnings
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bcfl_tpu.checkpoint import restore_latest, save_checkpoint
from bcfl_tpu.compression import codecs as cc
from bcfl_tpu.config import FedConfig
from bcfl_tpu.core import client_key_data, client_mesh, pod_devices
from bcfl_tpu.core.fence import fence
from bcfl_tpu.data import (
    Partitioner,
    TokenCache,
    client_batches,
    get_tokenizer,
    load_dataset,
)
from bcfl_tpu.data.pipeline import central_eval_batches
from bcfl_tpu.faults import FaultInjector, SimulatedCrash
from bcfl_tpu.fed.client_step import (
    FedPrograms, build_programs, model_counters, remat_saved, _merge)
from bcfl_tpu.fed.cohort import ClientSampler, EFRegistry, cohort_view
from bcfl_tpu.ledger import Ledger
from bcfl_tpu.ledger import fingerprint as fp_lib
from bcfl_tpu.metrics import (
    ResourceMonitor,
    RoundRecord,
    RunMetrics,
    StepClock,
    model_size_gb,
    scope,
    trace,
)
from bcfl_tpu.models import TextClassifier, lora as lora_lib
from bcfl_tpu.reputation import ReputationTracker
from bcfl_tpu import telemetry
from bcfl_tpu.topology import (
    anomaly_filter,
    partitioned_anomaly_filter,
    random_graph,
    reference_graph,
)
from bcfl_tpu.topology.graph import LatencyGraph


@dataclasses.dataclass
class RunResult:
    metrics: RunMetrics
    trainable: object  # final global trainable (params or adapters)
    params: object  # final merged full params
    ledger: Optional[Ledger]
    # final per-client state, [C, ...] leaves sharded over the clients mesh
    # axis — what a checkpoint would hold beside ``trainable``: the carried
    # per-client trainables (serverless mode) and the codec's error-feedback
    # residual (compression on). None where the run carries neither.
    stacked: object = None
    ef_residual: object = None


@dataclasses.dataclass
class ExchangeResult:
    """One update exchange through the engine's wire seam
    (:meth:`FedEngine._exchange_updates`) — the single code path every
    consumer of 'what crossed the wire' shares: the per-round split-phase
    bodies (server/serverless/partitioned/async) and the dist runtime's
    real TCP transport (bcfl_tpu.dist, which serializes ``sent`` and ships
    ``fp``-derived digests alongside it)."""

    # what arrived at the aggregation point: the transported stacked tree
    # (uncompressed) or the codec payload dict (compressed). Identity with
    # the input tree when nothing touched transport (clean, uncompressed).
    sent: object
    # receiver-side reconstruction to aggregate/mix: decoded ref+delta for
    # the compressed global/local modes, ``sent`` itself uncompressed,
    # None for mode="async" (the async merge decodes deltas itself)
    recon: object
    # ledger 0/1 auth mask over the stacked slots (None: ledger off or
    # commit=False)
    auth: Optional[np.ndarray]
    # [C, K] fingerprint rows of ``sent`` when commit=False (the dist wire
    # path: commit/verify happens at the remote leader, so the sender only
    # announces digests); None on the inline-commit path
    fp: Optional[np.ndarray]
    # the ledger struct-digest kind binding ``fp``/auth entries:
    # "stacked" (raw trees) or "payload" (codec payloads)
    wire_kind: str


# Cached jitted tree helpers. Defined once at module level so they compile
# once per shape signature — an inline ``jax.jit(lambda ...)`` built inside a
# round body would retrace EVERY round, and an unjitted ``jax.tree.map`` of
# arithmetic dispatches one op per leaf (hundreds of tiny dispatches).
_tree_sub = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
_tree_axpy = jax.jit(
    lambda y, x, a: jax.tree.map(lambda yy, xx: yy + a * xx, y, x))
_tree_select = jax.jit(
    lambda s, b, p: jax.tree.map(
        lambda x, y: jnp.where(p.reshape((-1,) + (1,) * (x.ndim - 1)) > 0, y, x),
        s, b))
_tree_wsum = jax.jit(
    lambda ws, trees: jax.tree.map(
        lambda *xs: sum(w * x for w, x in zip(ws, xs)), *trees))
# simulated transport of a stacked update tree on the per-round path: the
# buffer that "arrives" is new_t + scale per client (0 = clean, an exact
# float identity) — the same corruption model the fused *_fp programs apply
# in-graph (client_step._transport), so per-round and fused chaos runs are
# comparable
_tree_corrupt = jax.jit(scope("transport")(
    lambda t, s: jax.tree.map(
        lambda x: x + s.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype),
        t)))

logger = logging.getLogger(__name__)


def _nbytes(*trees) -> int:
    """Bytes of every array in ``trees`` (None leaves count nothing)."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(trees))


class FedEngine:
    def __init__(
        self,
        cfg: FedConfig,
        tamper_hook: Optional[Callable] = None,
        info_source: int = 1,
        fused_tamper: Optional[Callable] = None,
    ):
        # the construction's own span tree (OBSERVABILITY.md §7): held
        # until the first ``run`` has a stream to send it to
        self._setup_clock = StepClock(hold=True)
        with self._setup_clock.phase("setup"):
            self._build(cfg, tamper_hook, info_source, fused_tamper)

    def _build(self, cfg: FedConfig, tamper_hook: Optional[Callable],
               info_source: int, fused_tamper: Optional[Callable]) -> None:
        """The construction proper, inside the ``setup`` phase."""
        clock = self._setup_clock
        self.cfg = cfg
        # ``tamper_hook`` (host-tree byte tampering, forces the per-round
        # path) and ``fused_tamper`` ((rnd) -> [C] scales, in-graph transport
        # corruption for fused dispatches) are DEPRECATED shims over the
        # FaultPlan corruption API (bcfl_tpu.faults): new code schedules
        # corruption via cfg.faults, which works on both paths and composes
        # with every aggregator. The shims stay so existing tests/scripts
        # keep their exact semantics.
        if tamper_hook is not None or fused_tamper is not None:
            warnings.warn(
                "tamper_hook/fused_tamper are deprecated shims — schedule "
                "corruption via FedConfig.faults (bcfl_tpu.faults.FaultPlan)",
                DeprecationWarning, stacklevel=3)
        # --- cohort-batched scale-out (SCALING.md "Cohort mode") ---
        # self.C = the stacked client-axis width (the per-round cohort);
        # self.R = the client registry size. Sampling off: R == C ==
        # num_clients and every per-round id is an identity — the classic
        # layout, bit-identical to the pre-cohort engine. Sampling on:
        # registry-sized HOST arrays (faults, reputation, EF residuals)
        # carry per-client identity; only the sampled cohort's rows ever
        # reach the mesh.
        self.sampling = cfg.registry_size > 0
        self.C = ((cfg.sample_clients or cfg.num_clients) if self.sampling
                  else cfg.num_clients)
        self.R = cfg.registry_size if self.sampling else cfg.num_clients
        self.sampler = (ClientSampler(cfg.seed, self.R, self.C)
                        if self.sampling else None)
        self._cohort_cache = (-1, None)
        if self.sampling and (tamper_hook is not None
                              or fused_tamper is not None):
            raise ValueError(
                "the legacy tamper_hook/fused_tamper shims are positional "
                "over a fixed client set; with registry sampling schedule "
                "corruption via FedConfig.faults (its schedules are keyed "
                "by registry id)")
        self.faults = FaultInjector(
            cfg.faults, self.R,
            host_tamper=tamper_hook, fused_tamper=fused_tamper)
        # peer-lifecycle reputation (bcfl_tpu.reputation): host-side state
        # machine whose gate multiplier folds into each round's mask —
        # None when disabled; state rides the checkpoint. Sized by the
        # REGISTRY: a flaky peer keeps its record whether or not this
        # round's sampler drew it.
        self.reputation = (ReputationTracker(cfg.reputation, self.R)
                           if cfg.reputation.enabled else None)
        self.root_key = jax.random.key(cfg.seed,
                                       impl=cfg.resolved_prng_impl)
        # RESOLVED key impl: with prng_impl=None the run follows jax's
        # process default, which env vars can change — checkpoints must
        # record what actually ran, not the config field. The NAME is the
        # real identity (two different impls can share a key-data width,
        # e.g. rbg vs unsafe_rbg are both 4); the width stays recorded for
        # checkpoints written before the name existed
        self._prng_code = int(jax.random.key_data(self.root_key).shape[-1])
        self._prng_name = str(jax.random.key_impl(self.root_key))
        # lane 4 of the root key: the clients' dropout/codec streams
        self._client_key = jax.random.fold_in(self.root_key, 4)

        # --- data (tokenize once; SURVEY.md §3.2 fixes the 200x re-tokenize) ---
        with clock.span("data"):
            self.dataset = load_dataset(
                cfg.dataset, num_labels=cfg.num_labels,
                text_col=cfg.text_col, label_col=cfg.label_col)
            self.tokenizer = get_tokenizer(cfg.tokenizer, cfg.vocab_size)
            self.cache = TokenCache.build(self.dataset, self.tokenizer,
                                          cfg.seq_len)
            self.num_labels = max(cfg.num_labels, self.cache.num_labels)
            self.partitioner = Partitioner(
                cfg.partition, self.dataset.n_train, self.dataset.n_test,
                jax.random.fold_in(self.root_key, 1),
            )

        # --- mesh (before the model: sp injects the mesh into attention) ---
        # pod=True spans every host's devices (hosts-major, DCN-outermost);
        # tp>1 makes the mesh 2-D (clients, tp) and megatron-shards the
        # frozen base; sp>1 makes it (clients, seq) and rides ring attention
        devices = pod_devices() if cfg.pod else None
        if self.sampling and cfg.cohort_size:
            # pin the per-device stack: exactly C/cohort_size CLIENT shards
            # (config validated the divisibility), each vmapping a
            # cohort_size-client slab. With an inner tp/sp axis the mesh
            # reserves `inner` devices per client shard, so the device
            # budget scales by it — without this, client_mesh would quietly
            # fold the shortfall back into a bigger per-device stack,
            # breaking the documented pin.
            devices = list(devices if devices is not None
                           else jax.devices())
            inner = max(cfg.tp, cfg.sp)
            need = (self.C // cfg.cohort_size) * inner
            if need > len(devices):
                raise ValueError(
                    f"cohort_size {cfg.cohort_size} needs {need} devices "
                    f"for a {self.C}-client cohort"
                    + (f" x {inner} inner (tp/sp) shards" if inner > 1
                       else "")
                    + f", have {len(devices)}")
            devices = devices[:need]
        self.mesh = client_mesh(self.C, devices=devices,
                                tp=cfg.tp, sp=cfg.sp)

        # --- model ---
        # dtype/attention knobs flow from the config into EVERY build path:
        # a config that says float32 compute must not silently train bf16
        dtype_overrides = {"dtype": jnp.dtype(cfg.compute_dtype),
                           "param_dtype": jnp.dtype(cfg.param_dtype)}
        if cfg.remat:
            dtype_overrides["remat"] = True
        if cfg.use_flash is not None:
            dtype_overrides["use_flash"] = cfg.use_flash
            if cfg.use_flash:
                # an explicit "on" FORCES the blockwise path at every
                # length (both families otherwise gate on flash_min_seq,
                # which would silently run dense attention below 512)
                dtype_overrides["flash_min_seq"] = 0
        if cfg.sp > 1:
            from bcfl_tpu.parallel.sp import SEQ_AXIS, ring_override

            # each client's attention becomes exact ring attention over the
            # mesh's seq axis (activations shard O(S/sp) per device); both
            # model families expose the hook — llama rides the causal ring,
            # encoders the non-causal one
            assert SEQ_AXIS in self.mesh.mesh.shape
            dtype_overrides["attention_override"] = ring_override(
                self.mesh.mesh)
            dtype_overrides["use_flash"] = False
        from bcfl_tpu.models import lora_policy

        with clock.span("model_init"):
            if cfg.hf_checkpoint is not None:
                if cfg.task == "causal_lm":
                    raise ValueError(
                        "task='causal_lm' needs a decoder; the HF import path "
                        "builds encoder classifiers")
                from bcfl_tpu.models.hf_import import import_pretrained

                model_cfg, variables = import_pretrained(
                    cfg.hf_checkpoint, num_labels=self.num_labels,
                    reinit_classifier=True,
                )
                model_cfg = dataclasses.replace(model_cfg, **dtype_overrides)
                self.model = TextClassifier(model_cfg)
                self._lora_policy = lora_policy(self.model)
                # the importer materializes float32; the configured param dtype
                # must apply to the ARRAYS, not just the config record
                params = jax.tree.map(
                    lambda x: x.astype(model_cfg.param_dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x,
                    variables["params"])
            else:
                from bcfl_tpu.models import build as build_model

                self.model = build_model(
                    cfg.model, num_labels=self.num_labels,
                    vocab_size=self.tokenizer.vocab_size,
                    head="lm" if cfg.task == "causal_lm" else "classifier",
                    **dtype_overrides,
                )
                self._lora_policy = lora_policy(self.model)
                ids = jnp.ones((2, cfg.seq_len), jnp.int32)
                key = jax.random.fold_in(self.root_key, 2)
                init = self.model.init
                if model_size_gb(
                        jax.eval_shape(init, key, ids, ids)["params"]) >= 1.0:
                    # a large base: the draw alone, jitted (init's forward pass
                    # is dead code there), and born with the steady-state
                    # sharding, so that pinning it makes no second copy of a
                    # base that fills half a chip. Smaller ones keep the eager
                    # draw (its values differ from the jitted one's in the last
                    # bits, and seeded runs are pinned to them)
                    init = jax.jit(init, out_shardings=self.mesh.replicated())
                params = init(key, ids, ids)["params"]

            if cfg.lora_rank > 0:
                policy = self._lora_policy
                how = dict(targets=policy.targets,
                           head_modules=policy.head_modules,
                           dtype=policy.adapter_dtype, tied=policy.tied)
                self.frozen = params
                ranks = cfg.client_lora_ranks
                if ranks is not None and len(set(ranks)) > 1:
                    # heterogeneous fleet: each client's adapters initialize AT
                    # ITS OWN rank (own gaussian/sqrt(r_c) scale), zero-padded
                    # to the cohort max; the round-0 global is their RBLA mean
                    # (b starts at zeros everywhere, so the collapse only
                    # blends the per-rank-normalized 'a' factors)
                    from bcfl_tpu.parallel import gspmd

                    stacked0 = lora_lib.init_lora_ranks(
                        jax.random.fold_in(self.root_key, 3), params, ranks,
                        targets=policy.targets,
                        head_modules=policy.head_modules)
                    self.trainable0 = gspmd.rank_aware_weighted_mean(
                        stacked0, jnp.ones((len(ranks),), jnp.float32),
                        lora_lib.rank_mask(ranks))
                else:
                    self.trainable0 = lora_lib.init_lora(
                        jax.random.fold_in(self.root_key, 3), params,
                        cfg.lora_rank, **how)
            else:
                self.frozen = None
                self.trainable0 = params

        # --- programs ---
        if cfg.tp > 1:
            from jax.sharding import NamedSharding

            from bcfl_tpu.models import tp_param_specs

            # tp_param_specs dispatches on the BUILT model's family (an
            # hf_checkpoint always builds an encoder, even when cfg.model
            # names a llama config)
            specs = tp_param_specs(self.model, self.frozen)
            if not any("tp" in str(s) for s in jax.tree.leaves(specs)):
                raise ValueError(
                    "tp > 1 but no parameter matched the tensor-parallel "
                    "layout — model family unsupported for tp")
            self.frozen = jax.device_put(
                self.frozen,
                jax.tree.map(lambda s: NamedSharding(self.mesh.mesh, s),
                             specs))
        self._counters = model_counters(self.model)
        self.progs: FedPrograms = build_programs(
            self.model, self.mesh,
            optimizer=cfg.optimizer, learning_rate=cfg.learning_rate,
            max_grad_norm=cfg.max_grad_norm,
            gossip_alpha=cfg.topology.gossip_alpha,
            gossip_steps=cfg.topology.gossip_steps,
            task=cfg.task,
            aggregator=cfg.aggregator,
            aggregator_trim=cfg.aggregator_trim,
            prng_impl=cfg.resolved_prng_impl,
            compression=cfg.compression,
            donate=cfg.donate,
            # cohort mode compiles the explicit hierarchical (within-device
            # stack, then cross-device) reduction into every mean
            # aggregation point (SCALING.md); normalized away for robust
            # aggregators, whose order statistics stay global
            hierarchical=self.sampling,
            # heterogeneous LoRA ranks: the per-client tuple is part of the
            # program-cache key; build_programs normalizes a uniform tuple
            # (or None) to the plain programs
            lora_ranks=cfg.client_lora_ranks,
        )
        with clock.span("remat_saved"):
            self.remat_saved = self._remat_saved()
        # per-round rank-collapse guard (arXiv 2602.13486): mean effective
        # rank of the global adapter tree, one tiny separate jit (compiles
        # once — the round programs stay untouched); None when LoRA is off
        self._eff_rank = (jax.jit(lora_lib.effective_rank)
                          if cfg.lora_rank > 0 else None)
        # communication compression (COMPRESSION.md): None when disabled.
        # The error-feedback residual (stacked [C, ...] f32) is engine round
        # state, lazily initialized in _run and checkpointed — crash/resume
        # must reproduce compressed runs bit-for-bit too.
        self._comp = cfg.compression if cfg.compression.enabled else None
        self._ef = None
        self._ef_reg = None  # cohort-mode per-registry EF store, set below
        if self._comp is not None and tamper_hook is not None:
            # the legacy host-tamper shim byte-hashes FULL host trees; with
            # compression the wire carries payloads, so the two transport
            # models cannot compose (same exclusivity as FaultPlan corruption
            # vs tamper_hook)
            raise ValueError(
                "tamper_hook models byte-tampering of full host update "
                "trees; with compression enabled the wire carries encoded "
                "payloads — schedule corruption via FedConfig.faults "
                "(it corrupts the compressed representation)")
        if cfg.donate and (cfg.sync == "async" or cfg.faithful):
            warnings.warn(
                "donate=True has no effect on the async/faithful paths — "
                "they run only undonated split-phase programs, so peak HBM "
                "is unchanged", stacklevel=3)
        # Pin the global trees to their steady-state shardings NOW: the round
        # programs return replicated trees, so a single-device-committed
        # trainable0 would make round 2's input sharding differ from round
        # 1's — a full recompile of the round program on the second round
        # (measured as the r04 bench's 87.5 s/dispatch artifact,
        # results/dispatch_bisect.json). frozen keeps its tp layout when
        # tp > 1 (placed above).
        self.trainable0 = self.mesh.replicate(self.trainable0)
        if self.frozen is not None and cfg.tp == 1:
            self.frozen = self.mesh.replicate(self.frozen)
        if self.sampling and self._comp is not None:
            # cohort-mode error-feedback store: residuals live per REGISTRY
            # client on the host; each round the sampled cohort's rows are
            # gathered onto the device and scattered back after (fed.cohort)
            self._ef_reg = EFRegistry(self.trainable0)

        # --- topology graph (positional over the round's stacked slots:
        # in cohort mode the network model applies to whoever is sampled) ---
        if cfg.topology.bandwidth == "reference" and self.C == 10:
            self.graph: LatencyGraph = reference_graph()
        else:
            self.graph = random_graph(
                self.C, cfg.topology.bw_low, cfg.topology.bw_high,
                seed=cfg.seed,
            )
        self.info_source = info_source % self.C

        self.ledger = Ledger(cfg.ledger.use_native) if cfg.ledger.enabled else None
        # bytes-on-wire accounting (COMPRESSION.md): what ONE client ships
        # per round, raw vs through the configured codec — host-side shape
        # arithmetic, no device transfer. Equal when compression is off.
        # Feeds RoundRecord.bytes_*, the topology comms model (_payload_gb),
        # and the ledger's per-entry payload accounting: the chain covers
        # (and bills for) what is actually transmitted.
        self._raw_bytes_per_client = cc.payload_nbytes(None, self.trainable0)
        self._wire_bytes_per_client = cc.payload_nbytes(
            self._comp, self.trainable0)
        self._client_payload_bytes = int(self._wire_bytes_per_client)
        self._struct_cache: Dict[str, bytes] = {}
        if self._comp is not None and self.ledger is not None:
            # ledger entries digest the COMPRESSED payload: precompute its
            # structure digest from an eval_shape of the encoder (no device
            # work), so split-phase and fused rounds bind identical digests
            C = self.C

            def _payload_shape(t):
                stacked = jax.tree.map(
                    lambda x: jnp.zeros((C,) + x.shape, jnp.float32), t)
                return cc.encode_tree(self._comp, stacked, jax.random.key(0))

            self._struct_cache["payload"] = fp_lib.struct_digest(
                jax.eval_shape(_payload_shape, self.trainable0),
                cfg.ledger.use_native)
        with clock.span("eval_batches"):
            self.eval_batches = jax.tree.map(
                jnp.asarray,
                central_eval_batches(self.cache, cfg.batch_size,
                                     max_batches=cfg.max_eval_batches))
        self._static_batches = None  # cache when the partition is round-static
        # the run's StepClock: a fresh one per ``run``; None until then, so
        # the dist runtime's calls into the wire seam open no spans
        self.clock: Optional[StepClock] = None

    # ------------------------------------------------------------------ spans

    def _remat_saved(self) -> Dict[str, float]:
        """What the model's rematerialised layers keep for the backward pass
        (``client_step.remat_saved``; OBSERVABILITY.md §7): the named values
        a layer, and their megabytes over the layers and the clients one
        device stacks in a local step. Zeros for a model with no save set,
        or with ``remat`` off. On the ``run.start`` event."""
        cfg = self.cfg
        rows = jax.ShapeDtypeStruct((cfg.batch_size, cfg.seq_len), jnp.int32)
        per_row = jax.ShapeDtypeStruct((cfg.batch_size,), jnp.float32)
        values, nbytes = remat_saved(
            self.model, cfg.task, self.trainable0, self.frozen,
            {"ids": rows, "mask": rows, "example_mask": per_row})
        return {"remat_saved_values": values // self.model.cfg.num_layers,
                "remat_saved_mb_per_step":
                    round(nbytes * self.mesh.per_device / 1e6, 3)}

    def _span(self, name: str, **kw):
        """A child span of whatever phase is open (``round_program/inputs``,
        ``ledger/chain``, ...; OBSERVABILITY.md §7). No span may
        add a synchronisation: ``wait`` wraps only a block the engine makes
        anyway."""
        if self.clock is None:
            return contextlib.nullcontext({})
        return self.clock.span(name, **kw)

    @contextlib.contextmanager
    def _enqueued(self, program: str):
        """``with self._enqueued(name) as prog: out = prog(...)``: the call
        into ``self.progs.<name>`` under an ``enqueue`` span that lasts until
        the callable returns (the dispatch is asynchronous). ``compiled`` is
        1 when the callable's jit cache grew during the call: which step
        recompiled. The call is made from the round body's own frame, as
        deep as the engine made it before there were spans: where JAX's
        lowering sits on CPython's data stack decides seconds of a big
        program's first call (PERF.md section 6, PR 25)."""
        fn = getattr(self.progs, program)
        size = getattr(fn, "_cache_size", None)
        with self._span("enqueue", program=program) as counts:
            before = size() if size is not None else 0
            yield fn
            if size is not None:
                counts["compiled"] = int(size() > before)


    def _ef_gather(self, ids) -> None:
        """Cohort mode: the cohort's error-feedback rows onto the device
        (under no phase: stream and profiler only)."""
        with self._span("ef_gather") as counts:
            self._ef = self.mesh.shard_clients(jax.tree.map(
                jnp.asarray, self._ef_reg.gather(ids)))
            counts["h2d_bytes"] = _nbytes(self._ef)

    def _ef_scatter(self, ids) -> None:
        """Cohort mode: the updated rows back into the registry's store."""
        with self._span("ef_scatter") as counts:
            self._ef_reg.scatter(ids, jax.device_get(self._ef))
            counts["d2h_bytes"] = _nbytes(self._ef)

    def _fetch(self, *arrays):
        """Device results as numpy arrays: the ``wait`` is the block that
        the ``np.asarray`` behind it would make anyway, the ``fetch`` the
        copy to the host."""
        with self._span("wait"):
            jax.block_until_ready(arrays)
        with self._span("fetch") as counts:
            out = [np.asarray(a) for a in arrays]
            counts["d2h_bytes"] = _nbytes(out)
        return out

    # ------------------------------------------------------------------ utils

    def _cohort_ids(self, rnd: int) -> Optional[np.ndarray]:
        """The round's sampled registry ids ([C] int64), or None when
        sampling is off (stacked slot == client id). Cached per round —
        the sampler is a pure function of (seed, round), so the cache only
        saves the re-draw, never changes the value."""
        if self.sampler is None:
            return None
        if self._cohort_cache[0] != rnd:
            self._cohort_cache = (rnd, self.sampler.cohort_ids(rnd))
        return self._cohort_cache[1]

    def _client_id(self, rnd: int, pos: int) -> int:
        """Registry client id occupying stacked slot ``pos`` this round."""
        ids = self._cohort_ids(rnd)
        return int(ids[pos]) if ids is not None else pos

    def _transport_scales(self, rnd: int) -> Optional[np.ndarray]:
        """The round's transport-corruption scales for the STACKED slots:
        the plan draws per registry client; cohort mode slices the sampled
        rows (and an all-clean slice collapses to None, keeping the clean
        fast path). The one call-site rule of the FaultInjector still
        holds — every consumer (round bodies, reputation evidence) goes
        through here, so 'is corruption on the wire this round' can never
        disagree between them."""
        row = self.faults.transport_scales(rnd)
        ids = self._cohort_ids(rnd)
        if row is None or ids is None:
            return row
        row = row[ids]
        return row if row.any() else None

    def _round_batches(self, rnd: int):
        cfg = self.cfg
        # cohort mode: batches depend on WHO was sampled, so the
        # round-static cache only applies with sampling off
        static = (not (cfg.partition.kind == "iid"
                       and cfg.partition.resample_each_round)
                  and not self.sampling)
        if static and self._static_batches is not None:
            return self._static_batches
        ids = self._cohort_ids(rnd)
        tree, n_ex = client_batches(
            self.cache, self.partitioner,
            ids if ids is not None else self.C, rnd, cfg.batch_size,
            max_batches=cfg.max_local_batches,
        )
        out = (self.mesh.shard_clients(jax.tree.map(jnp.asarray, tree)),
               np.asarray(n_ex))
        if static:
            self._static_batches = out
        return out

    def _test_batches(self, rnd: int):
        cfg = self.cfg
        ids = self._cohort_ids(rnd)
        tree, _ = client_batches(
            self.cache, self.partitioner,
            ids if ids is not None else self.C, rnd, cfg.batch_size,
            max_batches=cfg.max_local_batches, split="test",
        )
        return self.mesh.shard_clients(jax.tree.map(jnp.asarray, tree))

    def _key_data(self, rnd: int, k: Optional[int] = None):
        """Client key data for round ``rnd`` ([C, K] uint32) or, given
        ``k``, for rounds [rnd, rnd+k) ([k, C, K]): ONE call of the cached
        key program (``client_key_data``), its rounds and ids built on the
        host. Keyed by REGISTRY id in cohort mode: a client's dropout/codec
        stream depends on (seed, id, round), never on its cohort slot."""
        rounds = np.arange(rnd, rnd + (k or 1), dtype=np.int32)
        ids = np.empty((len(rounds), self.C), np.int32)
        for i, r in enumerate(rounds):
            cohort = self._cohort_ids(int(r))
            ids[i] = np.arange(self.C) if cohort is None else cohort
        if k is None:
            ids, rounds = ids[0], rounds[0]
        if self.clock is not None:
            self.clock.count("key_programs")
        return client_key_data(self._client_key, ids, rounds)

    def _rngs(self, rnd: int):
        return self.mesh.shard_clients(self._key_data(rnd))

    def _participation(self, rnd: int, components=None) -> Dict:
        if components is not None:
            # under a chaos partition the filter sees each component's own
            # subgraph — cross-component links don't exist for the span
            return partitioned_anomaly_filter(
                self.cfg.topology.anomaly_filter, self.graph, components,
                protect=(self.info_source,),
            )
        return anomaly_filter(
            self.cfg.topology.anomaly_filter, self.graph,
            protect=(self.info_source,),
        )

    def _payload_gb(self) -> float:
        # the comms model scales by what actually crosses a link: the codec
        # payload when compression is on, the raw tree otherwise (for
        # compress=none this equals model_size_gb(trainable0) exactly —
        # both are sum(size * itemsize) / 1e9)
        return self._wire_bytes_per_client / 1e9

    def _comms_payload_bytes(self) -> int:
        """What one update exchange ships, for the info-passing model.

        Compression wins over the ledger constant: with a codec on, the
        update payload on the wire IS the compressed encoding (and the
        ledger's own accounting already bills those same bytes per entry —
        using the reference's fixed 0.043 GB blockchain figure here would
        make the two accountings disagree). Uncompressed ledger runs keep
        the reference's modeled ledger-entry payload (MT nb cell 27);
        everything else ships the raw tree."""
        if self._comp is not None:
            return int(self._wire_bytes_per_client)
        if self.ledger is not None:
            return int(self.cfg.ledger.entry_payload_bytes)
        return int(self._raw_bytes_per_client)

    def _global_eval(self, trainable) -> tuple:
        s = np.asarray(self.progs.eval_global(trainable, self.frozen, self.eval_batches))
        return float(s[0] / max(s[2], 1)), float(s[1] / max(s[2], 1))

    def _ledger_authenticate(self, rnd: int, host) -> np.ndarray:
        """Authenticate what 'arrived' against the already-committed chain
        (tamper_hook simulates in-flight modification). Returns 0/1 auth mask."""
        C = self.C
        tamper = self.faults.host_tamper
        shipped = tamper(rnd, host) if tamper else host
        auth = np.ones((C,), np.float32)
        for c in range(C):
            ok = self.ledger.authenticate(
                rnd, self._client_id(rnd, c),
                jax.tree.map(lambda x: x[c], shipped))
            auth[c] = 1.0 if ok else 0.0
        return auth

    def _entry_digest(self, kind: str, fp_row: np.ndarray) -> bytes:
        """Digest a device-computed fingerprint row, bound to the update
        tree's structure (names/dtypes/shapes). The structure template comes
        from ``jax.eval_shape`` over ``trainable0`` — no device transfer, and
        the fused and split-phase paths commit identical digests for the
        same content."""
        struct = self._struct_cache.get(kind)
        if struct is None:
            if kind == "payload":
                # precomputed in __init__ whenever ledger + compression are
                # both on; reaching here means a payload digest was requested
                # on an uncompressed run — a caller bug, not a cache miss
                raise RuntimeError(
                    "payload struct digest requested without compression")
            tmpl = self.trainable0
            if kind == "stacked":
                C = self.C
                tmpl = jax.eval_shape(
                    lambda t: jax.tree.map(
                        lambda x: jnp.broadcast_to(x[None], (C,) + x.shape),
                        t),
                    tmpl)
            struct = self._struct_cache[kind] = fp_lib.struct_digest(
                tmpl, self.cfg.ledger.use_native)
        return fp_lib.entry_digest(struct, fp_row,
                                   self.cfg.ledger.use_native)

    def _ledger_commit_rows(self, rnd: int, kind: str, fps) -> None:
        """Chain one entry per client for the given fingerprint rows [C, K].
        Entries are keyed by REGISTRY client id (slot id when sampling is
        off), so a client's chain history survives cohort reshuffles."""
        for c in range(self.C):
            self.ledger.append_digest(
                rnd, self._client_id(rnd, c),
                self._entry_digest(kind, fps[c]),
                self._client_payload_bytes)
        telemetry.emit("ledger", op="commit", round=int(rnd), n=self.C,
                       chain_len=len(self.ledger), rewrite=False,
                       head8=self.ledger.head.hex()[:16])

    def _ledger_auth_rows(self, rnd: int, kind: str, fps) -> np.ndarray:
        """0/1 auth mask: do the fingerprint rows match the committed chain
        entries for this round? Shared by the split-phase, fused, and
        faithful ledger paths so the digest binding cannot diverge."""
        return np.asarray([
            1.0 if self.ledger.authenticate_digest(
                rnd, self._client_id(rnd, c),
                self._entry_digest(kind, fps[c]))
            else 0.0
            for c in range(self.C)], np.float32)

    def _ledger_verify(self, rnd: int, stacked, sent=None,
                       kind: str = "stacked") -> np.ndarray:
        """Commit every client's update, then authenticate what arrived.
        Returns the 0/1 auth mask.

        ``stacked`` is the honest tree each client COMMITS; ``sent``
        (default: the same buffer) is the tree that survived the simulated
        transport stage and is about to be aggregated. When the fault plan
        corrupts transport the two differ, and authentication genuinely
        fails for exactly the corrupted clients — the per-round twin of the
        fused ``*_fp`` programs' in-graph commit/verify split.

        With compression on, callers pass the COMPRESSED payload trees and
        ``kind='payload'``: the chain then authenticates exactly the bytes
        on the wire, not a tree the network never carried.

        Default path: the content digest is a device-side fingerprint
        (:mod:`bcfl_tpu.ledger.fingerprint`) — only ``[C, K]`` floats cross
        the link instead of the full stacked tree (~4.4 GB/round for
        BERT-base x 10 clients over the r03 host path). A ``tamper_hook``
        simulates in-flight modification of HOST trees, so that path keeps
        the faithful full byte-hash flow."""
        C = self.C
        # dispatch is async: without this, the TRAINING compute of the
        # just-dispatched client_updates/local_updates program completes
        # inside this phase's first blocking transfer and gets billed to
        # the ledger (observed: a "90% ledger" reading that was ~95%
        # training wait)
        with self._span("wait"):
            fence(stacked if sent is None else sent)
        with self.clock.phase("ledger"):
            if self.faults.host_tamper is not None:
                with self._span("fetch") as counts:
                    host = jax.device_get(stacked)
                    counts["d2h_bytes"] = _nbytes(host)
                with self._span("chain"):
                    for c in range(C):
                        self.ledger.append(
                            rnd, c, jax.tree.map(lambda x: x[c], host))
                    return self._ledger_authenticate(rnd, host)
            fp = self._fingerprint("fingerprint", stacked)
            with self._span("chain"):
                self._ledger_commit_rows(rnd, kind, fp)
                if sent is None or sent is stacked:
                    # the committed HBM buffer IS the aggregated one:
                    # re-running the fingerprint program would reproduce
                    # `fp` bit-for-bit (device arrays are immutable), so
                    # auth re-derives digests from it directly
                    return self._ledger_auth_rows(rnd, kind, fp)
            fp_recv = self._fingerprint("fingerprint", sent)
            with self._span("chain"):
                return self._ledger_auth_rows(rnd, kind, fp_recv)

    def _fingerprint(self, program: str, tree) -> np.ndarray:
        """``ledger/fingerprint``: the device's digest program, its wait and
        the copy of its few floats to the host, apart from the host's
        hashing (``ledger/chain``)."""
        with self._span("fingerprint", program=program) as counts:
            fp = np.asarray(getattr(self.progs, program)(tree))
            counts["d2h_bytes"] = fp.nbytes
        return fp

    # ------------------------------------------------------- fault utilities

    def _exchange_updates(self, rnd, new_t, ref_t, rngs, scales, mode,
                          commit: bool = True) -> ExchangeResult:
        """The update-exchange seam: one wire exchange of the round's
        stacked updates, shared by EVERY consumer — the per-round
        split-phase bodies (server/serverless/partitioned/async) and the
        dist runtime's real TCP transport (bcfl_tpu.dist) — so the codec
        encode, corruption sharding, transported-payload decode, and
        ledger digest binding can never drift apart (the fused ``*_fp``
        programs apply the same sequence in-graph).

        ``mode`` picks the compressed encoder: "global" (delta vs the
        replicated global), "local" (vs the stacked round-start params), or
        "async" (recon-free — the async/dist merges decode deltas
        themselves). Uncompressed runs ignore ``ref_t``/``mode``: the wire
        quantity is the stacked tree itself.

        ``commit=True`` (the local engine) chains+verifies inline via
        :meth:`_ledger_verify`. ``commit=False`` (the dist wire) skips the
        inline chain and instead returns the fingerprint rows of ``sent``
        so the caller can announce digests to a REMOTE leader, which
        commits and re-verifies what actually arrived."""
        if self._comp is None:
            sent = self._transport(new_t, scales)
            auth = fp = None
            if self.ledger is not None:
                if commit:
                    auth = self._ledger_verify(rnd, new_t, sent)
                else:
                    fence(sent)
                    fp = np.asarray(self.progs.fingerprint(sent))
            return ExchangeResult(sent=sent, recon=sent, auth=auth, fp=fp,
                                  wire_kind="stacked")
        if mode == "async":
            with self._enqueued("encode_deltas_async") as prog:
                payload, self._ef = prog(new_t, ref_t, self._ef, rngs)
            recon = None
        else:
            with self._enqueued("encode_deltas" if mode == "global"
                                else "encode_deltas_local") as prog:
                payload, recon, self._ef = prog(new_t, ref_t, self._ef, rngs)
        if scales is None:
            sent_p = payload
        else:
            with self._enqueued("corrupt_payload") as prog:
                sent_p = prog(
                    payload, self.mesh.shard_clients(jnp.asarray(scales)))
            if recon is not None:
                # a corrupted wire yields a corrupted reconstruction —
                # re-decode the TRANSPORTED payload (the clean-path recon
                # came fused with the encode)
                with self._enqueued("decode_recon") as prog:
                    recon = prog(sent_p, ref_t, new_t)
        auth = fp = None
        if self.ledger is not None:
            if commit:
                auth = self._ledger_verify(rnd, payload, sent_p,
                                           kind="payload")
            else:
                fence(sent_p)
                fp = np.asarray(self.progs.fingerprint(sent_p))
        return ExchangeResult(sent=sent_p, recon=recon, auth=auth, fp=fp,
                              wire_kind="payload")

    def _transport(self, stacked, scales):
        """Simulated transport of the round's stacked updates: returns the
        tree that 'arrives' at aggregation. Identity (the same buffer) when
        ``scales`` is None — callers draw the round's schedule ONCE via
        ``faults.transport_scales(rnd)`` and thread it here, so the
        'is corruption scheduled' decision and the scales actually applied
        can never come from different draws."""
        if scales is None:
            return stacked
        return _tree_corrupt(stacked,
                             self.mesh.shard_clients(jnp.asarray(scales)))

    def _note_degraded(self, rec, participation: np.ndarray) -> None:
        """Mark (and warn about) a round whose every client was eliminated
        by the anomaly gate x dropout x churn x reputation x ledger auth —
        the aggregation programs keep the previous params via their
        fallback input, so the run continues NaN-free but made no progress
        this round."""
        if float(np.asarray(participation).sum()) > 0.0:
            return
        rec.degraded = True
        logger.warning(
            "round %d: every client eliminated from the aggregate "
            "(mask/auth all zero) — keeping the previous global model",
            rec.round)

    # --------------------------------------------------- partition round body

    def _partitioned_round(self, rnd, trainable, stacked, mask, comps):
        """One round under a chaos network partition (ROBUSTNESS.md §6).

        The mesh never reshapes: every client still trains in the same
        compiled ``local_updates`` dispatch, but aggregation runs PER
        CONNECTED COMPONENT — each component's participants collapse through
        the configured aggregator (robust rules included) and only the
        component's members adopt its aggregate, so the components evolve as
        genuinely independent federations for the span. ``trainable``
        becomes the robust cross-component consensus (collapse over the
        per-client component models, weighted by participation): the
        eval/checkpoint view during the span and the reconciliation the
        heal round adopts — never a silent global average of divergent
        components, and a fully-eliminated component keeps its previous
        model instead of NaN-ing out.

        Composes with the ledger (split-phase commit/verify on what each
        client shipped), compression (the wire quantity is the encoded
        delta vs the client's round-start params — ``mode='local'``), and
        transport corruption/flaky bursts. Everything here is pre-compiled
        programs fed runtime masks/weights: zero per-round retraces."""
        cfg = self.cfg
        C = self.C
        batches, n_ex, rngs, scales = self._round_inputs(rnd)
        if stacked is None:
            # span entry from server mode: every client starts the span
            # from the last whole-mesh global
            with self._enqueued("broadcast") as prog:
                stacked = prog(trainable)
        start = stacked
        with self._enqueued("local_updates") as prog:
            stacked, stats = prog(stacked, self.frozen, batches, rngs)
        stats, = self._fetch(stats)
        with self._span("records"):
            rec = self._stats_to_rec(rnd, stats)
        # wire exchange through the shared seam: the wire quantity is the
        # encoded delta vs the client's round-start params (mode="local")
        # when compression is on, the stacked tree itself otherwise
        ex = self._exchange_updates(rnd, stacked, start, rngs, scales,
                                    mode="local")
        agg_src, auth = ex.recon, ex.auth
        if auth is not None:
            rec.auth = auth.tolist()
            mask = mask * auth
        w = np.asarray(mask, np.float32) * (
            np.asarray(n_ex, np.float32) if cfg.weighted_agg else 1.0)
        part_id = np.full((C,), -1, np.int64)
        out = stacked
        for ci, comp in enumerate(comps):
            cm = np.zeros((C,), np.float32)
            cm[list(comp)] = 1.0
            part_id[list(comp)] = ci
            wc = w * cm
            if float(wc.sum()) <= 0.0:
                # fully-eliminated component: in server mode its members
                # keep the component's round-start model (identical rows by
                # construction); serverless members keep their own
                # post-train state, the existing all-masked semantics
                if cfg.mode == "server":
                    out = _tree_select(
                        out, start, self.mesh.shard_clients(jnp.asarray(cm)))
                logger.warning(
                    "round %d: partition component %d fully eliminated — "
                    "keeping its previous model", rnd, ci)
                continue
            with self._enqueued("collapse") as prog:
                comp_mean = prog(agg_src, self._shard_mask(wc), trainable)
            if cfg.mode == "server":
                pull = cm  # every member receives the component model
            else:
                # serverless: masked clients keep their own carried state
                pull = cm * (np.asarray(mask) > 0)
            with self._enqueued("adopt") as prog:
                out = prog(out, comp_mean, self._shard_mask(pull))
        # robust consensus ACROSS components (participation-weighted
        # collapse over the per-client component models): the span's
        # eval/checkpoint view and what the heal round reconciles onto
        with self._enqueued("collapse") as prog:
            consensus = prog(out, self._shard_mask(w), trainable)
        with self._span("records"):
            rec.partition = part_id.tolist()
            self._note_degraded(rec, mask)
        return consensus, out, rec

    def _heal_partition(self, trainable, stacked, mask):
        """First whole-mesh round after a partition span: the reconciled
        global — the robust cross-component consensus the last partitioned
        round computed — becomes the starting point. Server mode resumes
        from it directly (the stacked per-component view is dropped);
        serverless participants adopt it into their carried state. Either
        way the components reconcile through the configured aggregator,
        deterministically, rather than silently averaging divergent models
        inside the next round's mix."""
        if self.cfg.mode == "server":
            return trainable, None
        pull = self.mesh.shard_clients(jnp.asarray(
            (np.asarray(mask) > 0).astype(np.float32)))
        return trainable, self.progs.adopt(stacked, trainable, pull)

    # ------------------------------------------------------ reputation bridge

    def _reputation_observe(self, rnd: int, rec, gate: Dict) -> None:
        """Fold this round's evidence into the peer-lifecycle tracker and
        record the post-round states on the RoundRecord. Evidence sources
        (combined per client by max, each weighted by the config):

        - ledger-auth failure — the update that arrived failed chain
          authentication (the hard, protocol-level evidence),
        - anomaly-filter flag — the topology heuristics singled the peer out,
        - injected corruption hit — the chaos plan corrupted this peer's
          transport this round (the simulation's stand-in for a local
          detector; coincides with auth failure when the ledger is on;
          disable via reputation.observe_injected=False),
        - async staleness beyond ``staleness_limit``.

        Quarantined peers accrue nothing (they were excluded); the tracker
        just ticks their sentence. Every input derives from seeded draws
        and recorded round outputs, so the trajectory is deterministic and
        crash/resume-stable."""
        rcfg = self.cfg.reputation
        C = self.C
        fault = np.zeros((C,), np.float64)
        if rec.auth is not None:
            failed = (np.asarray(rec.auth, np.float64) == 0.0)
            fault = np.maximum(fault, rcfg.w_auth * failed)
        if gate["anomalies"]:
            flag = np.zeros((C,), np.float64)
            flag[list(gate["anomalies"])] = 1.0
            fault = np.maximum(fault, rcfg.w_anomaly * flag)
        if rcfg.observe_injected:
            scales = self._transport_scales(rnd)  # deterministic redraw
            if scales is not None:
                hit = (np.asarray(scales, np.float64) != 0.0)
                fault = np.maximum(fault, rcfg.w_corrupt * hit)
        if rec.staleness is not None and rcfg.staleness_limit > 0:
            stale = (np.asarray(rec.staleness, np.float64)
                     > rcfg.staleness_limit)
            fault = np.maximum(fault, rcfg.w_staleness * stale)
        ids = self._cohort_ids(rnd)
        if ids is None:
            self.reputation.observe(fault)
            rec.reputation_state = self.reputation.state_names()
            rec.reputation_trust = [float(t) for t in self.reputation.trust]
            return
        # cohort mode: scatter the cohort's evidence into the
        # registry-sized tracker. Only sampled peers are 'active' — their
        # EWMA and probation clocks advance; a non-sampled peer's trust
        # must not drift on rounds it never participated in (quarantine
        # sentences still tick: wall rounds pass either way). The record
        # carries the cohort's post-round view, slot-aligned with
        # mask/auth.
        fault_r = np.zeros((self.R,), np.float64)
        fault_r[ids] = fault
        active = np.zeros((self.R,), bool)
        active[ids] = True
        self.reputation.observe(fault_r, active=active)
        names = self.reputation.state_names()
        rec.reputation_state = [names[int(i)] for i in ids]
        rec.reputation_trust = [float(self.reputation.trust[int(i)])
                                for i in ids]

    # ------------------------------------------------------------------- run

    def run(self, resume: bool = False, on_round=None) -> RunResult:
        """on_round: optional callable(RoundRecord), invoked after each round
        record is finalized (long runs are otherwise silent until the end)."""
        # event telemetry (OBSERVABILITY.md): the local engine streams only
        # when a directory is named (the dist runtime defaults ON instead —
        # its run dir is the natural home). Installed around the whole run
        # so StepClock phases, ledger commits, reputation transitions, and
        # checkpoint events all land in one stream; a SimulatedCrash still
        # closes it with its status.
        cfg = self.cfg
        installed = None
        if cfg.telemetry_dir and cfg.telemetry_dir != "off":
            installed = telemetry.install(telemetry.EventWriter(
                os.path.join(cfg.telemetry_dir, "events_engine.jsonl"),
                peer=None, run=cfg.name, sample=cfg.telemetry_sample))
            telemetry.emit("run.start", role="engine", resume=resume,
                           clients=self.C, rounds=cfg.num_rounds,
                           **self.remat_saved)
            # the construction's spans, at the first run's start only
            self._setup_clock.emit_held()
        status = "crashed"
        try:
            with trace(cfg.profile_dir):
                out = self._run(resume, on_round)
            status = "ok"
            return out
        finally:
            if installed is not None:
                telemetry.emit("run.end", status=status)
                telemetry.uninstall()

    def _run(self, resume: bool = False, on_round=None) -> RunResult:
        cfg = self.cfg
        monitor = ResourceMonitor()
        metrics = RunMetrics()
        clock = self.clock = StepClock()
        start_round = 0
        trainable = self.trainable0
        stacked = None

        resumed_from_checkpoint = False
        if resume and cfg.checkpoint_dir:
            restored = restore_latest(cfg.checkpoint_dir)
            if restored is not None:
                resumed_from_checkpoint = True
                start_round, state, ledger_json = restored
                start_round += 1
                ck_name = state.get("prng_impl_name")
                if ck_name is not None:
                    ck_name = bytes(np.asarray(ck_name, np.uint8)).decode()
                    if ck_name != self._prng_name:
                        raise ValueError(
                            f"checkpoint prng impl {ck_name!r} != this run's "
                            f"{self._prng_name!r} "
                            f"(prng_impl={cfg.prng_impl!r}): resuming would "
                            "change the RNG stream")
                # width-only fallback for checkpoints that predate the name
                # field (cannot distinguish same-width impls, e.g. rbg vs
                # unsafe_rbg — the name check above exists for exactly that)
                ck_impl = state.get("prng_impl_code")
                if ck_impl is not None and int(ck_impl) != self._prng_code:
                    raise ValueError(
                        f"checkpoint prng key width {int(ck_impl)} != this "
                        f"run's {self._prng_code} "
                        f"(prng_impl={cfg.prng_impl!r}): resuming would "
                        "change the RNG stream")
                ck_comp = state.get("compress_format")
                if ck_comp is not None:
                    ck_comp = bytes(np.asarray(ck_comp, np.uint8)).decode()
                    here = cc.wire_format(self._comp)
                    if ck_comp != here:
                        # a codec change across resume would re-inject the
                        # checkpointed error-feedback residual into a
                        # different encode (or drop it) silently — same
                        # guard class as the prng-impl check above
                        raise ValueError(
                            f"checkpoint was written with compress="
                            f"{ck_comp!r} but this run has {here!r}: "
                            "resuming would change the wire format under "
                            "the carried error-feedback state")
                ck_lora = state.get("lora_format")
                if ck_lora is not None:
                    ck_lora = bytes(np.asarray(ck_lora, np.uint8)).decode()
                    here = self._lora_format()
                    if ck_lora != here:
                        raise ValueError(
                            f"checkpoint was written with LoRA layout "
                            f"{ck_lora!r} but this run has {here!r}: "
                            "resuming would reinterpret the checkpointed "
                            "adapter (and error-feedback) trees under a "
                            "different rank layout")
                ck_seed = state.get("seed")
                if ck_seed is not None and int(ck_seed) != cfg.seed:
                    raise ValueError(
                        f"checkpoint was written with seed {int(ck_seed)} but "
                        f"config has seed {cfg.seed}: resuming would break the "
                        "per-(client, round) RNG stream")
                # cohort identity: the sampler is a pure function of
                # (seed, registry_size, sample_clients, round) — the seed
                # check above plus these two pin the remaining rounds'
                # cohorts bit-for-bit; a change would silently re-deal
                # every future cohort
                ck_reg = state.get("registry_size")
                want_sc = self.C if self.sampling else 0
                if ck_reg is not None:
                    ck_sc = int(state.get("sample_clients") or 0)
                    if (int(ck_reg) != int(cfg.registry_size)
                            or ck_sc != want_sc):
                        raise ValueError(
                            "checkpoint was written with registry_size="
                            f"{int(ck_reg)}/sample_clients={ck_sc} but this "
                            f"run has {cfg.registry_size}/{want_sc}: "
                            "resuming would change the per-round cohort "
                            "stream")
                elif self.sampling:
                    raise ValueError(
                        "checkpoint predates cohort mode (no registry_size "
                        "recorded) but this run samples a registry: "
                        "resuming would change every remaining round's "
                        "cohort")
                # checkpoints written under a different param_dtype must not
                # silently override the configured one on resume
                pd = jnp.dtype(cfg.param_dtype)

                def _cast(t):
                    return jax.tree.map(
                        lambda x: jnp.asarray(x, pd)
                        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                        else jnp.asarray(x), t)

                if state.get("stacked") is not None:
                    stacked = self.mesh.shard_clients(_cast(state["stacked"]))
                if (state.get("ef_residual") is not None
                        and self._comp is not None):
                    # error-feedback state travels with the checkpoint: a
                    # compressed crash/resume must re-inject exactly the
                    # residual the uninterrupted run would have carried
                    self._ef = self.mesh.shard_clients(jax.tree.map(
                        lambda x: jnp.asarray(x, jnp.float32),
                        state["ef_residual"]))
                if self._ef_reg is not None:
                    # cohort mode carries residuals per REGISTRY client
                    # instead (ef_ids + ef_registry); the round loop
                    # re-gathers each cohort's rows from the restored store
                    self._ef_reg.restore(state)
                # replicate: a resumed tree left on the default device would
                # re-trigger the round-2 recompile (tests/test_recompile.py)
                trainable = self.mesh.replicate(_cast(state["trainable"]))
                if (self.reputation is not None
                        and state.get("rep_trust") is not None):
                    # peer-lifecycle state travels with the checkpoint: a
                    # resumed run must pick up every trust score, lifecycle
                    # state, and quarantine timer exactly where the crash
                    # left them (tests/test_reputation.py pins bit-equality)
                    self.reputation.restore(state)
                if ledger_json and self.ledger is not None:
                    self.ledger = Ledger.from_json(
                        ledger_json, cfg.ledger.use_native)

        # single-shot guard AFTER the restore branch: resume supplies a
        # fresh trainable, so a donated-away trainable0 only matters when
        # it is actually the tree this run will consume
        if (cfg.donate and trainable is self.trainable0
                and any(getattr(x, "is_deleted", lambda: False)()
                        for x in jax.tree.leaves(self.trainable0))):
            raise RuntimeError(
                "engine.run() is single-shot with donate=True: round 1 "
                "donated the initial trainable buffers to the round "
                "program. Build a fresh FedEngine (or resume from a "
                "checkpoint, or set donate=False) to run again.")

        if (self._comp is not None and self._ef is None
                and self._ef_reg is None):
            # fresh error-feedback state (zeros): round 1's encode sees the
            # pure delta, later rounds re-inject what compression dropped.
            # Cohort mode skips this — each round gathers its cohort's
            # residual rows from the registry store instead.
            self._ef = self.progs.ef_init(trainable)

        if cfg.mode == "serverless" and not cfg.faithful and stacked is None:
            stacked = self.progs.broadcast(trainable)

        async_state = self._init_async_state() if cfg.sync == "async" else None

        rnd = start_round
        while rnd < cfg.num_rounds:
            if not resumed_from_checkpoint and self.faults.should_crash(rnd):
                # chaos-plan host crash: nothing of round `rnd` runs; the
                # newest checkpoint is the only state that survives. Raised
                # BEFORE any dispatch so a resumed run reproduces the
                # uninterrupted one bit-for-bit (tests/test_faults.py).
                # The crash models ONE host failure, so a run that actually
                # restored a checkpoint does not re-fire it — otherwise the
                # documented crash -> --resume workflow could never get
                # past the crash round (resume restarts at or before it).
                # Gated on the RESTORE, not the resume flag: a standing
                # --resume on a fresh checkpoint dir must still crash, or
                # the chaos experiment silently never happens
                raise SimulatedCrash(rnd)
            clock.round = rnd
            chunk = self._chunk_rounds(rnd)
            if chunk > 1:
                t0 = time.time()
                with clock.phase("round_program"):
                    if cfg.mode == "server":
                        trainable, recs = self._server_chunk(
                            rnd, trainable, chunk)
                    else:
                        stacked, trainable, recs = self._serverless_chunk(
                            rnd, stacked, trainable, chunk)
                # host work between phases: under no phase, so it reaches
                # the stream and the profiler and stays out of summary().
                # On a round where they are due, the eval phase and the
                # checkpoint save run inside it as well
                with clock.span("post_round"):
                    self._annotate_chunk(recs, time.time() - t0)
                    if self._eff_rank is not None and recs:
                        # fused dispatch: only the chunk's FINAL global
                        # exists host-side; the guard statistic lands on its
                        # record
                        recs[-1].effective_rank = float(
                            self._eff_rank(trainable))
                    last_rnd = rnd + chunk - 1
                    self._maybe_eval(last_rnd, recs[-1], trainable, stacked,
                                     clock)
                    metrics.rounds.extend(recs)
                    self._maybe_checkpoint(last_rnd, trainable, stacked)
                    for r in recs:
                        telemetry.emit("round", round=r.round,
                                       wall_s=r.wall_s, fused=True,
                                       degraded=r.degraded,
                                       **(r.counters or {}))
                if on_round is not None:
                    with clock.span("on_round"):
                        for r in recs:
                            on_round(r)
                rnd += chunk
                continue

            if (self.faults.fused_tamper is not None
                    and self.faults.fused_tamper(rnd) is not None):
                # the transport-corruption stage only exists inside the fused
                # *_fp programs: silently dropping a requested corruption on
                # a per-round-path round would let a verification test pass
                # vacuously (auth all-ones because nothing was corrupted)
                raise ValueError(
                    f"fused_tamper requests corruption for round {rnd}, but "
                    "this round runs the per-round path (chunk=1: check "
                    "rounds_per_dispatch, eval/checkpoint boundaries, and "
                    "_chunk_rounds eligibility) — the corruption would be "
                    "silently ignored; use tamper_hook for per-round "
                    "tampering")

            t0 = time.time()
            ids = self._cohort_ids(rnd)
            comps = self.faults.partition_components(rnd)
            with clock.phase("control_plane"):
                with clock.span("gate"):
                    gate = self._participation(rnd, comps)
                mask = gate["mask"].astype(np.float32)
                # chaos dropout composes with the anomaly gate exactly like
                # a second filter: the mesh never reshapes, dropped clients
                # carry weight 0 for the round. All chaos lanes draw per
                # REGISTRY client; cohort_view slices the sampled rows
                # (identity when sampling is off).
                keep = cohort_view(self.faults.dropout_keep(rnd), ids)
                dropped = None
                if keep is not None:
                    # SLOT indices, like every other per-client index list
                    # on the record (anomalies, mask positions); cohort
                    # mode recovers registry identity via rec.cohort[slot]
                    dropped = [c for c in range(self.C) if keep[c] == 0.0]
                    mask = mask * keep
                # churn: permanently-departed / not-yet-joined clients carry
                # weight 0 — the monotone twin of dropout
                alive = cohort_view(self.faults.churn_alive(rnd), ids)
                if alive is not None:
                    mask = mask * alive
                # reputation gate: quarantined peers 0, probation peers a
                # reduced vote weight (bcfl_tpu.reputation; registry-sized,
                # cohort-sliced)
                if self.reputation is not None:
                    with clock.span("reputation"):
                        mask = mask * cohort_view(self.reputation.gate(), ids)
                healed = False
                if (comps is None and stacked is not None and rnd > 0
                        and self.faults.partition_components(rnd - 1)
                        is not None):
                    # partition span just ended: reconcile (derived from the
                    # PLAN, not carried flags, so a resumed run heals at
                    # exactly the same round as the uninterrupted one)
                    trainable, stacked = self._heal_partition(
                        trainable, stacked, mask)
                    healed = True

            delays = cohort_view(self.faults.straggler_delays(rnd), ids)
            if delays is not None and not delays.any():
                delays = None  # no sampled client straggles this round
            if self._ef_reg is not None:
                # gather the cohort's error-feedback residual rows from the
                # per-registry store (zeros for never-sampled clients) —
                # the compiled codec programs see the usual [C, ...] carry
                self._ef_gather(ids)
            with clock.phase("round_program"):
                if comps is not None:
                    trainable, stacked, rec = self._partitioned_round(
                        rnd, trainable, stacked, mask, comps)
                elif cfg.sync == "async":
                    trainable, stacked, rec = self._async_round(
                        rnd, trainable, stacked, mask, async_state,
                        delays=delays)
                elif cfg.mode == "server":
                    trainable, rec = self._server_round(rnd, trainable, mask)
                elif cfg.faithful:
                    trainable, rec = self._faithful_round(rnd, trainable, mask)
                else:
                    stacked, trainable, rec = self._serverless_round(
                        rnd, stacked, trainable, mask)
            if self._ef_reg is not None:
                # scatter the updated residual rows back by registry id
                # BEFORE eval/checkpoint, so the checkpointed store matches
                # the uninterrupted run's at every boundary
                self._ef_scatter(ids)
            with clock.span("post_round"):
                rec.mask = mask.tolist()
                if ids is not None:
                    rec.cohort = ids.tolist()
                rec.anomalies = list(gate["anomalies"])
                rec.healed = healed
                if dropped is not None:
                    rec.dropped = dropped
                if alive is not None:
                    rec.churn_alive = alive.tolist()
                if delays is not None:
                    rec.straggler_s = delays.tolist()
                # info passing: during a partition the source informs only its
                # own component; churned-out clients are not targets either
                # (the source itself always stays in the restricted set — a
                # departed source degenerates to informing whoever remains,
                # which with everyone else gone is (0, 0), not a crash)
                restrict = None
                if comps is not None:
                    restrict = list(next(
                        c for c in comps if self.info_source in c))
                if alive is not None:
                    base = (restrict if restrict is not None
                            else range(self.C))
                    restrict = [c for c in base
                                if alive[c] > 0 or c == self.info_source]
                sync_t, async_t = self.graph.info_passing_time(
                    0.0, source=self.info_source, anomalies=gate["anomalies"],
                    extra_delay=delays,
                    payload_bytes=self._comms_payload_bytes(),
                    restrict=restrict,
                )
                rec.info_passing_sync_s = sync_t
                rec.info_passing_async_s = async_t
                rec.wall_s = time.time() - t0
                if self._eff_rank is not None:
                    rec.effective_rank = float(self._eff_rank(trainable))

                if self.reputation is not None:
                    # evidence folds in BEFORE eval/checkpoint so the
                    # checkpointed tracker state matches the uninterrupted
                    # run's at every checkpoint boundary
                    self._reputation_observe(rnd, rec, gate)
                self._maybe_eval(rnd, rec, trainable, stacked, clock)
                metrics.rounds.append(rec)
                self._maybe_checkpoint(rnd, trainable, stacked)
                telemetry.emit("round", round=rnd, wall_s=rec.wall_s,
                               degraded=rec.degraded, healed=rec.healed,
                               partitioned=rec.partition is not None,
                               **(rec.counters or {}))
            if on_round is not None:
                with clock.span("on_round"):
                    on_round(rec)
            rnd += 1

        # the model's parameters as they are applied: the trained tree, the
        # adapters merged into the base, or (adapters applied on the
        # activations) the base, with the adapters beside it in ``trainable``
        params = (self.frozen if self._lora_policy.on_activations
                  and self.frozen is not None
                  else _merge(trainable, self.frozen))
        metrics.model_size_gb = model_size_gb(params)
        metrics.resources = monitor.snapshot()
        metrics.phases = clock.summary()
        # run-level bytes-on-wire accounting (COMPRESSION.md): per-round
        # totals are on every RoundRecord; this is the headline rollup
        # (per-cohort in sampling mode: only sampled clients ship updates)
        C = self.C
        metrics.comms = {
            "compress": cfg.compression.kind,
            "bytes_raw_per_round": float(self._raw_bytes_per_client * C),
            "bytes_on_wire_per_round": float(
                self._wire_bytes_per_client * C),
            "compression_ratio": float(
                self._raw_bytes_per_client
                / max(self._wire_bytes_per_client, 1)),
        }
        if self.ledger is not None and len(self.ledger):
            metrics.ledger = self.ledger.payload_accounting()
            metrics.ledger["chain_ok"] = float(self.ledger.verify_chain() == -1)
        if self.reputation is not None:
            metrics.reputation = self.reputation.summary()
        return RunResult(metrics=metrics, trainable=trainable, params=params,
                         ledger=self.ledger, stacked=stacked,
                         ef_residual=self._ef)

    # ------------------------------------------------- eval/checkpoint cadence

    def _maybe_eval(self, rnd: int, rec: RoundRecord, trainable, stacked,
                    clock) -> None:
        cfg = self.cfg
        # the FINAL round always evaluates (when eval is on at all): with
        # eval_every=N and rounds % N != 0 the run would otherwise end
        # without a final-round number, and callers report accs[-1] as the
        # final accuracy
        due = ((rnd + 1) % cfg.eval_every == 0
               or rnd == cfg.num_rounds - 1) if cfg.eval_every else False
        if not due:
            return
        with clock.phase("eval"):
            loss, acc = self._global_eval(trainable)
            rec.global_loss, rec.global_acc = loss, acc
            # reference-style per-client local accuracy on each client's
            # LOCAL TEST split (serverless_NonIID_IMDB.py:291-292; Flower
            # client.evaluate server_IID_IMDB.py:176-179)
            tb = self._test_batches(rnd)
            if stacked is not None:
                s = self.progs.eval_clients(stacked, self.frozen, tb)
            else:
                s = self.progs.eval_clients_global(trainable, self.frozen, tb)
            s = np.asarray(s)
            rec.local_acc = (s[:, 1] / np.maximum(s[:, 2], 1)).tolist()

    def _lora_format(self) -> str:
        """Checkpoint identity of the LoRA layout: ``full`` (no adapters),
        ``r<k>`` uniform, or the per-client spec ``ranks:2,4,8,...``. Like
        ``compress_format``, a change across resume would silently
        reinterpret the restored trainable/EF trees — resume refuses it."""
        cfg = self.cfg
        if cfg.lora_rank <= 0:
            return "full"
        ranks = cfg.client_lora_ranks
        if ranks is None or len(set(ranks)) <= 1:
            return f"r{cfg.lora_rank}"
        return "ranks:" + ",".join(str(r) for r in ranks)

    def _maybe_checkpoint(self, rnd: int, trainable, stacked) -> None:
        cfg = self.cfg
        if not (cfg.checkpoint_dir and cfg.checkpoint_every
                and (rnd + 1) % cfg.checkpoint_every == 0):
            return
        state = {
            "trainable": jax.device_get(trainable),
            "stacked": jax.device_get(stacked) if stacked is not None else None,
            # compression error-feedback residual (None when compression is
            # off); required for bit-identical compressed crash/resume.
            # Cohort mode stores the per-REGISTRY store (ef_ids/ef_registry
            # below) instead — the stacked device buffer is just the last
            # cohort's gathered view.
            "ef_residual": (jax.device_get(self._ef)
                            if self._ef is not None and self._ef_reg is None
                            else None),
            # cohort identity: with cfg.seed these pin the sampler's entire
            # cohort stream; resume refuses a change (above)
            "registry_size": np.int64(cfg.registry_size),
            "sample_clients": np.int64(self.C if self.sampling else 0),
            # codec identity, uint8-encoded (orbax trees hold arrays):
            # resume refuses a wire-format change under the carried residual
            "compress_format": np.frombuffer(
                cc.wire_format(self._comp).encode(), np.uint8).copy(),
            # the RNG stream is derived deterministically from the seed +
            # round + key impl; storing both lets resume verify them
            "seed": np.int64(cfg.seed),
            # resolved key-data width (orbax trees hold arrays): threefry=2,
            # rbg=4 — see __init__._prng_code
            "prng_impl_code": np.int64(self._prng_code),
            # resolved impl NAME, uint8-encoded (orbax trees hold arrays):
            # distinguishes same-width impls (rbg vs unsafe_rbg)
            "prng_impl_name": np.frombuffer(
                self._prng_name.encode(), np.uint8).copy(),
            # LoRA rank identity, uint8-encoded ("r<uniform>" or the
            # per-client spec): resuming under a different rank layout
            # would reinterpret the checkpointed adapter (and EF) trees —
            # resume refuses a mismatch (below)
            "lora_format": np.frombuffer(
                self._lora_format().encode(), np.uint8).copy(),
        }
        if self.reputation is not None:
            # rep_trust / rep_state / rep_timer / counters: the peer
            # lifecycle must resume exactly where the crash left it
            state.update(self.reputation.checkpoint_state())
        if self._ef_reg is not None and len(self._ef_reg):
            # per-registry-client EF residuals (fed.cohort.EFRegistry)
            state.update(self._ef_reg.checkpoint_state())
        save_checkpoint(
            cfg.checkpoint_dir, rnd, state,
            self.ledger.to_json() if self.ledger else None,
        )

    # -------------------------------------------------- multi-round fast path

    def _chunk_rounds(self, rnd: int) -> int:
        """How many rounds starting at ``rnd`` can fuse into one dispatch.

        Eligible only when the host has nothing to do between rounds: sync
        server FedAvg or sync parallel serverless gossip (NOT the faithful
        host-sequential mode), no anomaly filter (the mask is all-ones), no
        host tamper hook. The LEDGER no longer blocks fusion: the fused
        ``*_fp`` programs commit each round's per-client fingerprints
        in-graph BEFORE a simulated-transport stage, re-fingerprint the
        transported buffer AFTER it, gate the aggregation by the in-graph
        comparison, and the host chain authenticates the post-transport
        fingerprints — so fused-mode auth genuinely fails for a corrupted
        update (``fused_tamper``) instead of being an identity. A host
        tamper hook falls back to per-round. Chunks never cross an eval or
        checkpoint boundary, so the observable cadence is identical to the
        per-round path."""
        cfg = self.cfg
        k = cfg.rounds_per_dispatch
        if (k <= 1 or cfg.sync != "sync"
                or (cfg.mode != "server" and cfg.faithful)
                or self.faults.host_tamper is not None
                or self.faults.blocks_fusion()
                or self.reputation is not None
                or self.sampling
                or cfg.topology.anomaly_filter is not None):
            # reputation needs the host between rounds: the lifecycle state
            # machine consumes each round's evidence before gating the next.
            # Cohort sampling does too: each round's batches/rngs/ledger ids
            # belong to a different sampled cohort, and the EF-residual
            # gather/scatter is host work between rounds by construction.
            return 1
        k = min(k, cfg.num_rounds - rnd)
        if cfg.eval_every:
            k = min(k, cfg.eval_every - rnd % cfg.eval_every)
        if cfg.checkpoint_dir and cfg.checkpoint_every:
            k = min(k, cfg.checkpoint_every - rnd % cfg.checkpoint_every)
        return max(k, 1)

    def _chunk_inputs(self, rnd: int, k: int):
        """Stage batches/rngs/example-counts for rounds [rnd, rnd+k).

        Returns ``(static, batches, rrngs, n_ex_list)``: ``static=True``
        means ONE batch tree [C, ...] reused every round (round-static
        partition cache hit — stacking k identical copies would be a k-fold
        HBM blowup for no information), else ``batches`` is the stacked
        [k, C, ...] tree."""
        batch_list, n_ex_list = [], []
        for r in range(rnd, rnd + k):
            b, n_ex = self._round_batches(r)
            batch_list.append(b)
            n_ex_list.append(n_ex)
        rrngs = self.mesh.shard_round_clients(self._key_data(rnd, k))
        if all(b is batch_list[0] for b in batch_list):
            return True, batch_list[0], rrngs, n_ex_list
        rbatches = self.mesh.shard_round_clients(
            jax.tree.map(lambda *xs: jnp.stack(xs), *batch_list))
        return False, rbatches, rrngs, n_ex_list

    def _commit_chunk_fps(self, rnd: int, k: int, fps_commit, fps_recv,
                          recs) -> None:
        """Fused-mode ledger flow: chain each round's PRE-transport commit
        fingerprints ([k, C, K], computed in-graph before the simulated
        transport stage), then authenticate the POST-transport fingerprints
        against the chain. The two trees differ whenever transport corrupted
        an update (``fused_tamper``), so this auth can genuinely fail — and
        the in-graph aggregation already excluded exactly those clients.
        Both arrive as host arrays (the caller's ``_fetch``): this phase is
        the host's hashing alone."""
        # compressed fused rounds fingerprint the PAYLOAD (client_step
        # _fp_auth_payload), so the chain entry binds the payload structure
        kind = "stacked" if self._comp is None else "payload"
        with self.clock.phase("ledger"), self._span("chain"):
            for i in range(k):
                self._ledger_commit_rows(rnd + i, kind, fps_commit[i])
            for i, rec in enumerate(recs):
                rec.auth = self._ledger_auth_rows(
                    rnd + i, kind, fps_recv[i]).tolist()

    def _chunk_corrupts(self, rnd: int, k: int):
        """[k, C] transport-corruption scales for the fused fp programs
        (zeros = clean; see ``fused_tamper`` in ``__init__``)."""
        corr = np.zeros((k, self.C), np.float32)
        if self.faults.fused_tamper is not None:
            for i in range(k):
                row = self.faults.fused_tamper(rnd + i)
                if row is not None:
                    corr[i] = np.asarray(row, np.float32)
        return self.mesh.shard_round_clients(jnp.asarray(corr))

    def _server_chunk_inputs(self, rnd: int, k: int):
        """``(program name, its arguments after the carry and the frozen
        tree)`` for one fused server dispatch, staged under ``inputs``."""
        cfg = self.cfg
        with self._span("inputs") as counts:
            fresh = self._static_batches is None
            static, batches, rrngs, n_ex_list = self._chunk_inputs(rnd, k)
            rweights = self.mesh.shard_round_clients(jnp.asarray(np.stack([
                np.full((self.C,),
                        n_ex if cfg.weighted_agg else 1.0, np.float32)
                for n_ex in n_ex_list])))
            corrupts = (self._chunk_corrupts(rnd, k)
                        if self.ledger is not None else None)
            counts["h2d_bytes"] = _nbytes(
                batches if fresh else None, rrngs, rweights, corrupts)
        name = "server_rounds_static" if static else "server_rounds"
        if self.ledger is not None:
            return name + "_fp", (batches, rweights, rrngs, corrupts)
        return name, (batches, rweights, rrngs)

    def _server_chunk(self, rnd: int, trainable, k: int):
        """Run rounds [rnd, rnd+k) in ONE XLA dispatch via server_rounds."""
        name, inputs = self._server_chunk_inputs(rnd, k)
        # compressed programs carry (params, error-feedback residual)
        carry = trainable if self._comp is None else (trainable, self._ef)
        with self._enqueued(name) as prog:
            carry, out = prog(carry, self.frozen, *inputs)
        if self._comp is not None:
            carry, self._ef = carry
        if self.ledger is None:
            stats, = self._fetch(out)  # [k, C, 3]
            with self._span("records"):
                return carry, [self._stats_to_rec(rnd + i, stats[i])
                               for i in range(k)]
        stats, fpc, fpr = self._fetch(*out[:3])  # out[3]: in-graph auth
        with self._span("records"):
            recs = [self._stats_to_rec(rnd + i, stats[i]) for i in range(k)]
        self._commit_chunk_fps(rnd, k, fpc, fpr, recs)
        return carry, recs

    def _serverless_chunk(self, rnd, stacked, prev_consensus, k):
        """Run gossip rounds [rnd, rnd+k) in ONE dispatch via gossip_rounds.

        Only reached with an all-ones participation mask (``_chunk_rounds``
        rejects filters/ledger/tamper), so the consensus view for eval/
        checkpoint is computed once at the chunk end — the per-round
        consensus values it skips are unobservable (no eval inside a
        chunk)."""
        cfg = self.cfg
        with self._span("inputs") as counts:
            fresh = self._static_batches is None
            static, batches, rrngs, _ = self._chunk_inputs(rnd, k)
            masks = self.mesh.shard_round_clients(
                jnp.ones((k, self.C), jnp.float32))
            corrupts = (self._chunk_corrupts(rnd, k)
                        if self.ledger is not None else None)
            counts["h2d_bytes"] = _nbytes(
                batches if fresh else None, rrngs, masks, corrupts)
        fps = ()
        carry = stacked if self._comp is None else (stacked, self._ef)
        name = "gossip_rounds_static" if static else "gossip_rounds"
        if self.ledger is not None:
            with self._enqueued(name + "_fp") as prog:
                carry, (stats, fpc, fpr, _auth) = prog(
                    carry, self.frozen, batches, masks, rrngs, corrupts)
            fps = (fpc, fpr)
        else:
            with self._enqueued(name) as prog:
                carry, stats = prog(carry, self.frozen, batches, masks, rrngs)
        if self._comp is None:
            stacked = carry
        else:
            stacked, self._ef = carry
        # collapse (a full-tree consensus all-reduce + host round-trip) only
        # when this chunk's end is observable — an eval round, a checkpoint
        # round, or the end of the run; otherwise the value would be
        # discarded, re-paying the dispatch overhead fusing exists to avoid
        last = rnd + k - 1
        observed = (
            last == cfg.num_rounds - 1
            or (cfg.eval_every and (last + 1) % cfg.eval_every == 0)
            or (cfg.checkpoint_dir and cfg.checkpoint_every
                and (last + 1) % cfg.checkpoint_every == 0))
        consensus = prev_consensus
        if observed:
            m = self._shard_mask(np.ones((self.C,), np.float32))
            with self._enqueued("collapse") as prog:
                consensus = prog(stacked, m, prev_consensus)
        stats, *fps = self._fetch(stats, *fps)  # stats [k, C, 3]
        with self._span("records"):
            recs = [self._stats_to_rec(rnd + i, stats[i]) for i in range(k)]
        if fps:
            self._commit_chunk_fps(rnd, k, fps[0], fps[1], recs)
        return stacked, consensus, recs

    def _annotate_chunk(self, recs, wall: float) -> None:
        """Participation/info-passing fields for fused rounds (all-ones mask
        by construction). The measured unit is the CHUNK: ``wall_chunk_s``
        carries the real dispatch wall time, ``wall_s`` its even split
        across the chunk's rounds, and ``fused=True`` marks both as
        chunk-derived so consumers can tell interpolated from measured."""
        C = self.C
        sync_t, async_t = self.graph.info_passing_time(
            0.0, source=self.info_source, anomalies=(),
            payload_bytes=self._comms_payload_bytes())
        for rec in recs:
            rec.mask = [1.0] * C
            rec.anomalies = []
            rec.info_passing_sync_s = sync_t
            rec.info_passing_async_s = async_t
            rec.fused = True
            rec.wall_chunk_s = wall
            rec.wall_s = wall / max(len(recs), 1)

    # ----------------------------------------------------------- round bodies

    def _stats_to_rec(self, rnd: int, stats) -> RoundRecord:
        s = np.asarray(stats)  # [C, 3 + the model's counters]
        n = np.maximum(s[:, 2], 1)
        total = s.sum(0)
        C = self.C
        raw = float(self._raw_bytes_per_client * C)
        wire = float(self._wire_bytes_per_client * C)
        # what the model counted (client_step.model_counters): over the
        # clients by the counter's kind, and added to the open ``records``
        # span's counts (a span that covers several rounds sums them)
        counters = {
            name: float(s[:, 3 + i].max() if kind == "max" else total[3 + i])
            for i, (name, kind) in enumerate(self._counters)} or None
        if counters and self.clock is not None:
            for name, value in counters.items():
                self.clock.count(name, int(value))
        return RoundRecord(
            round=rnd,
            counters=counters,
            train_loss=float(total[0] / max(total[2], 1)),
            train_acc=float(total[1] / max(total[2], 1)),
            local_acc=(s[:, 1] / n).tolist(),
            # bytes-on-wire accounting: one shipped update per client per
            # round, raw vs through the configured codec (equal at
            # compress=none)
            bytes_raw=raw,
            bytes_on_wire=wire,
            compression_ratio=raw / max(wire, 1.0),
        )

    def _weights(self, mask: np.ndarray, n_ex: np.ndarray) -> jnp.ndarray:
        w = np.asarray(mask, np.float32) * (
            np.asarray(n_ex, np.float32) if self.cfg.weighted_agg else 1.0)
        if not np.isfinite(w).all():
            # a NaN/Inf weight would silently poison every aggregation
            # fallback comparison downstream (NaN > 0 is False but NaN * x
            # propagates); an all-MASKED round is fine — the aggregators'
            # fallback keeps the params and the round is recorded degraded
            raise ValueError(
                f"non-finite aggregation weights at round mask={mask!r} "
                f"n_ex={n_ex!r}")
        return self._shard_mask(w)

    def _round_inputs(self, rnd):
        """The round's batches, example counts, rngs and transport scales
        under one ``inputs`` span (PERF.md's ``data`` phase)."""
        with self._span("inputs") as counts:
            fresh = self._static_batches is None
            batches, n_ex = self._round_batches(rnd)
            rngs = self._rngs(rnd)
            scales = self._transport_scales(rnd)
            counts["h2d_bytes"] = _nbytes(batches if fresh else None, rngs)
        return batches, n_ex, rngs, scales

    def _shard_mask(self, mask) -> jnp.ndarray:
        """A per-client mask or weight row placed on the clients axis."""
        with self._span("inputs") as counts:
            m = self.mesh.shard_clients(jnp.asarray(mask, jnp.float32))
            counts["h2d_bytes"] = _nbytes(m)
        return m

    def _round_record(self, rnd, stats, mask, auth=None) -> RoundRecord:
        """``records``: the round's statistics (already on the host) as its
        RoundRecord, with the ledger's auth mask and the degraded mark."""
        with self._span("records"):
            rec = self._stats_to_rec(rnd, stats)
            if auth is not None:
                rec.auth = auth.tolist()
            self._note_degraded(rec, mask)
        return rec

    def _server_round(self, rnd, trainable, mask):
        batches, n_ex, rngs, scales = self._round_inputs(rnd)
        if self.ledger is None and scales is None:
            w = self._weights(mask, n_ex)
            if self._comp is None:
                with self._enqueued("server_round") as prog:
                    trainable, stats = prog(
                        trainable, self.frozen, batches, w, rngs)
            else:
                # compressed carry: (params, error-feedback residual)
                with self._enqueued("server_round") as prog:
                    (trainable, self._ef), stats = prog(
                        (trainable, self._ef), self.frozen, batches, w, rngs)
            stats, = self._fetch(stats)
            return trainable, self._round_record(rnd, stats, mask)
        # split-phase flow: train -> (ledger commit) -> transport ->
        # (ledger verify) -> aggregate; if every update is eliminated the
        # round keeps its starting params (collapse fallback). Without the
        # ledger a corrupted update reaches the aggregation rule — the
        # robust aggregators (cfg.aggregator) are the defense there.
        with self._enqueued("client_updates") as prog:
            stacked, stats = prog(trainable, self.frozen, batches, rngs)
        # the wire quantity is the compressed payload when a codec is on
        # (the ledger commits/authenticates ITS fingerprints, transport
        # corruption perturbs IT) and the stacked tree otherwise; either
        # way the server aggregates what ARRIVED (ex.recon)
        ex = self._exchange_updates(rnd, stacked, trainable, rngs, scales,
                                    mode="global")
        auth = ex.auth
        if auth is not None:
            mask = mask * auth
        w = self._weights(mask, n_ex)
        with self._enqueued("collapse") as prog:
            trainable = prog(ex.recon, w, trainable)
        # no wait on ``collapse``: the next round's client_updates consumes
        # its result while the host does its post-round work
        stats, = self._fetch(stats)
        return trainable, self._round_record(rnd, stats, mask, auth)

    def _serverless_round(self, rnd, stacked, prev_consensus, mask):
        batches, n_ex, rngs, scales = self._round_inputs(rnd)
        m = self._shard_mask(mask)
        auth = None
        if self.ledger is None and scales is None:
            if self._comp is None:
                with self._enqueued("gossip_round") as prog:
                    stacked, stats = prog(
                        stacked, self.frozen, batches, m, rngs)
            else:
                with self._enqueued("gossip_round") as prog:
                    (stacked, self._ef), stats = prog(
                        (stacked, self._ef), self.frozen, batches, m, rngs)
        else:
            # split-phase: peers ship their update (the encoded delta vs
            # their own round-start params under a codec, the stacked tree
            # otherwise) through the shared wire seam; the mix consumes
            # what ARRIVED (ex.recon) while each sender's self-term stays
            # its honest post-train tree (mix_recv). An untouched wire
            # (clean, uncompressed) keeps the one-buffer mix_only path.
            start = stacked  # pre-train params: what an all-rejected round keeps
            with self._enqueued("local_updates") as prog:
                stacked, stats = prog(stacked, self.frozen, batches, rngs)
            ex = self._exchange_updates(rnd, stacked, start, rngs, scales,
                                        mode="local")
            auth = ex.auth
            if auth is not None:
                mask = mask * auth
                m = self._shard_mask(mask)
            if ex.recon is not stacked:
                # corruption/codec reconstruction poisons only the RECEIVED
                # copies: neighbor and aggregate terms come from the
                # transported tree, each sender's own carry stays its honest
                # local state (__init__ rejects corrupting serverless
                # configs whose impl has no mix_recv, so this cannot
                # silently fall through to a mix that rewrites the sender's
                # state with the corruption)
                with self._enqueued("mix_recv") as prog:
                    stacked = prog(stacked, ex.recon, m, start)
            else:
                with self._enqueued("mix_only") as prog:
                    stacked = prog(stacked, m, start)
        # consensus view for eval/checkpoint (mask-weighted aggregation)
        with self._enqueued("collapse") as prog:
            consensus = prog(stacked, m, prev_consensus)
        stats, = self._fetch(stats)
        return stacked, consensus, self._round_record(rnd, stats, mask, auth)

    def _faithful_round(self, rnd, trainable, mask):
        """Reference-exact serverless semantics: clients sequentially mutate a
        shared model within the round, snapshots are averaged unweighted
        (``serverless_NonIID_IMDB.py:284-297``). Host-sequential by nature.

        With the ledger on, each snapshot is committed as it is produced and
        re-authenticated before aggregation — a tampered snapshot is excluded
        exactly as in the parallel paths. An all-excluded round keeps the
        round's starting params instead of zeroing the model."""
        cfg = self.cfg
        with self._span("inputs"):
            batches, n_ex = self._round_batches(rnd)
            keys = jax.random.wrap_key_data(
                self._key_data(rnd), impl=cfg.resolved_prng_impl)
        snapshots, host_snaps, snap_fps, all_stats = [], [], [], []
        fp_mode = self.ledger is not None and self.faults.host_tamper is None
        # Pin the sequential path to ONE device when the model fits on one.
        # The engine holds trainable replicated over the mesh (the r04
        # steady-state-sharding fix), and jitting the per-client program on
        # replicated-committed inputs executes EVERY replica — pure
        # redundant FLOPs on a pod, and an 8x wall-clock multiplier on the
        # serialized virtual CPU mesh (measured: small-bert x 10 clients,
        # round 0 went 536 s pinned vs >60 min replicated). The result is
        # put back into the caller's sharding so the parallel eval/round
        # programs see their layout. With tp/sp > 1 the model is sharded
        # BECAUSE it exceeds one device — there the GSPMD path stands.
        pin = cfg.tp == 1 and cfg.sp == 1
        if pin:
            out_sharding = jax.tree.map(lambda x: x.sharding, trainable)
            dev = jax.local_devices()[0]
            shared = jax.device_put(trainable, dev)
            frozen = getattr(self, "_frozen_dev0", None)
            if frozen is None:
                frozen = self._frozen_dev0 = jax.device_put(self.frozen, dev)
            keys = jax.device_put(keys, dev)
            # one bulk transfer, sliced on-device per client — not a
            # device_get + per-client re-upload round trip
            dev_b = jax.device_put(batches, dev)
        else:
            shared, frozen = trainable, self.frozen
            host_b = jax.device_get(batches)
        for c in range(self.C):
            cb = (jax.tree.map(lambda x: x[c], dev_b) if pin
                  else jax.tree.map(lambda x: jnp.asarray(x[c]), host_b))
            with self._enqueued("single_update") as prog:
                shared, stats = prog(shared, frozen, cb, keys[c])
            if fp_mode:
                # device-side digest: K floats cross the link, not the tree
                with self._span("wait"):
                    fence(shared)  # single_update is async; see _ledger_verify
                with self.clock.phase("ledger"):
                    fp = self._fingerprint("fingerprint_one", shared)
                    snap_fps.append(fp)
                    with self._span("chain"):
                        self.ledger.append_digest(
                            rnd, c, self._entry_digest("one", fp),
                            self._client_payload_bytes)
            elif self.ledger is not None:
                with self.clock.phase("ledger"):
                    with self._span("fetch") as counts:
                        snap = jax.device_get(shared)
                        counts["d2h_bytes"] = _nbytes(snap)
                    with self._span("chain"):
                        self.ledger.append(rnd, c, snap)
                    host_snaps.append(snap)
            snapshots.append(shared)
            all_stats.extend(self._fetch(stats))
        with self._span("records"):
            rec = self._stats_to_rec(rnd, np.stack(all_stats))
        w = np.asarray(mask, np.float32)
        if fp_mode:
            with self.clock.phase("ledger"), self._span("chain"):
                # reuse the commit-time fingerprints: the snapshots are
                # immutable device buffers, so recomputing would reproduce
                # them bit-for-bit at 2x the fingerprint cost
                auth = self._ledger_auth_rows(rnd, "one", snap_fps)
            rec.auth = auth.tolist()
            w = w * auth
        elif self.ledger is not None:
            with self.clock.phase("ledger"), self._span("chain"):
                stacked_host = jax.tree.map(
                    lambda *xs: np.stack(xs), *host_snaps)
                auth = self._ledger_authenticate(rnd, stacked_host)
            rec.auth = auth.tolist()
            w = w * auth
        total = float(w.sum())
        if total <= 0.0:
            self._note_degraded(rec, w)
            return trainable, rec
        avg = _tree_wsum(jnp.asarray(w / total), snapshots)
        return (jax.device_put(avg, out_sharding) if pin else avg), rec

    # ------------------------------------------------------------------ async

    def _init_async_state(self) -> Dict:
        """Simulated network clock: per-client round duration = local compute
        (proportional to the client's example count, mean-normalized to 1) +
        transfer time to the aggregation point over the latency graph (the
        quantity the notebooks call information passing time)."""
        cfg = self.cfg
        times = self.graph.shortest_path_times(self._payload_gb())
        src = self.info_source
        transfer = np.array([
            times[c, src] if c != src else 0.0 for c in range(self.C)])
        _, n_ex = self._round_batches(0)
        n_ex = np.asarray(n_ex, np.float64)
        compute = n_ex / max(n_ex.mean(), 1e-9)  # relative local-compute cost
        duration = compute + transfer
        return {
            "duration": duration,
            "next_done": duration.copy(),
            "version": np.zeros((self.C,), np.int64),
            "global_version": 0,
            "clock": 0.0,
        }

    def _async_merge_scale(self, alpha, arrived, n_ex) -> float:
        """sum(decayed weights) / sum(un-decayed weights) over the arrived
        buffer — the factor that survives collapse's normalization, in (0, 1]:
        1.0 when every arrival is fresh, ``staleness_decay ** s`` when a lone
        arrival is ``s`` versions stale."""
        if self.cfg.weighted_agg:
            base = float(np.asarray(n_ex)[arrived].sum())
        else:
            base = float(len(arrived))
        return float(alpha[arrived].sum() / max(base, 1e-9))

    def _async_round(self, rnd, trainable, stacked, mask, st, delays=None):
        """One buffered-async aggregation event (FedBuff-style): the K
        earliest-finishing clients merge their local DELTAS, each decayed by
        ``staleness_decay ** staleness``; the global takes an
        ``async_server_lr`` step along the weighted-mean delta. Clients that
        haven't arrived keep training on their stale base."""
        cfg = self.cfg
        K = cfg.async_buffer or self.C
        if stacked is None:
            with self._enqueued("broadcast") as prog:
                stacked = prog(trainable)
        base = stacked  # each client's round-start params (delta reference)
        batches, n_ex, rngs, _ = self._round_inputs(rnd)
        with self._enqueued("local_updates") as prog:
            stacked, stats = prog(stacked, self.frozen, batches, rngs)
        stats, = self._fetch(stats)
        with self._span("records"):
            rec = self._stats_to_rec(rnd, stats)

        # chaos stragglers: an affected client's completion slips by the
        # injected delay, so it arrives later and accumulates staleness —
        # the fault plan feeding the simulated network clock directly.
        # ``delays`` is threaded from the run loop's single per-round draw
        # (None from direct callers, who draw here instead)
        if delays is None:
            delays = self.faults.straggler_delays(rnd)
        if delays is not None:
            st["next_done"] = st["next_done"] + delays
            rec.straggler_s = delays.tolist()

        # transport corruption: the transmitted copies (deltas) may be
        # perturbed; each client's own carried state stays honest. With
        # compression the transmitted quantity IS the encoded delta payload
        # (async is delta-exchange by construction, so the codec slots in
        # exactly where _tree_sub used to run). EF semantics under partial
        # arrival: the residual advances for EVERY client each round, but a
        # non-arrived client's base is its OWN carried post-train state, so
        # its next delta stays incremental — the kept mass of an unmerged
        # payload is dropped exactly like the uncompressed path drops
        # unmerged deltas, and the residual re-delivers only compression
        # error (no update mass is ever applied twice).
        scales = self.faults.transport_scales(rnd)
        ex = self._exchange_updates(rnd, stacked, base, rngs, scales,
                                    mode="async")
        auth = ex.auth
        if auth is not None:
            rec.auth = auth.tolist()
            mask = mask * auth

        self._note_degraded(rec, mask)
        # pick the K earliest arrivals among participating clients
        order = np.argsort(st["next_done"])
        arrived = [c for c in order if mask[c] > 0][:K]
        st["clock"] = float(st["next_done"][arrived].max()) if arrived else st["clock"]

        staleness = st["global_version"] - st["version"]
        # staleness is reputation evidence (a chronically stale peer is a
        # flaky peer) and run observability either way
        rec.staleness = [max(int(s), 0) for s in staleness]
        alpha = np.zeros((self.C,), np.float32)
        for c in arrived:
            # mask[c] folds in the reputation gate: a probation peer's
            # merge weight is scaled down exactly like its sync vote
            alpha[c] = (float(mask[c])
                        * cfg.staleness_decay ** max(int(staleness[c]), 0))
        rec.async_alpha = alpha.tolist()
        if self.cfg.weighted_agg:
            alpha = alpha * n_ex

        if arrived:
            if self._comp is None:
                deltas = _tree_sub(ex.sent, base)
            else:
                with self._enqueued("decode_delta") as prog:
                    deltas = prog(ex.sent, stacked)
            zero = jax.tree.map(jnp.zeros_like, trainable)
            # collapse is a weight-NORMALIZED mean (divides by sum(alpha)), so
            # on its own the staleness decay would cancel out of the update
            # magnitude; rescale by sum(alpha)/sum(un-decayed weights) so a
            # stale delta really is applied smaller, FedBuff-style.
            with self._enqueued("collapse") as prog:
                merged_delta = prog(deltas, self._shard_mask(alpha), zero)
            scale = self._async_merge_scale(alpha, arrived, n_ex)
            trainable = _tree_axpy(
                trainable, merged_delta, cfg.async_server_lr * scale)
            # arrived clients pull the fresh global and restart (adopt
            # fuses the broadcast into the select: one dispatch, no
            # materialized [C, ...] broadcast buffer)
            pull = np.zeros((self.C,), np.float32)
            pull[arrived] = 1.0
            with self._enqueued("adopt") as prog:
                stacked = prog(stacked, trainable, self._shard_mask(pull))
            st["global_version"] += 1
            for c in arrived:
                st["version"][c] = st["global_version"]
                st["next_done"][c] = st["clock"] + st["duration"][c]

        return trainable, stacked, rec
