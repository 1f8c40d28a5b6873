"""The compiled federated round: every client's local fine-tune + the
aggregation collective in ONE XLA program.

Reference equivalents (SURVEY.md §3):

- local step (hot loop): 1-epoch AdamW lr=5e-5 full fine-tune, fresh optimizer
  per round — ``train``, ``src/Servercase/server_IID_IMDB.py:108-118`` and
  ``IMDBClient.train_model``, ``serverless_NonIID_IMDB.py:188-199``. Here it is
  a ``lax.scan`` over static-shape batches, vmapped over the stacked clients;
  the client dim is sharded over the mesh and XLA's SPMD partitioner inserts
  the collectives (:mod:`bcfl_tpu.parallel.gspmd`).
- server aggregation: Flower FedAvg (``server_IID_IMDB.py:205-218``) ->
  :func:`bcfl_tpu.parallel.gspmd.masked_weighted_mean` (all-reduce).
- serverless aggregation: all-client unweighted mean
  (``serverless_NonIID_IMDB.py:296``) -> masked ring gossip
  (:func:`bcfl_tpu.parallel.gspmd.gossip_mix`, collective-permute) or exact
  mean when ``gossip_steps == 0``.

Trainable tree is either the full param tree (reference behaviour) or a LoRA
adapter tree over a frozen base (``frozen``), chosen by the engine; the round
program is identical.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from bcfl_tpu.compression import CompressionConfig, codecs as cc
from bcfl_tpu.core.mesh import ClientMesh
from bcfl_tpu.ledger.fingerprint import client_fingerprint, tree_fingerprint
from bcfl_tpu.metrics.tracing import scope
from bcfl_tpu.models import lora as lora_lib
from bcfl_tpu.parallel import gspmd

Tree = Any


def make_optimizer(name: str, lr: float, max_grad_norm: float = 0.0):
    """Reference: fresh ``AdamW(lr=5e-5)`` torch defaults each round
    (``server_IID_IMDB.py:109``); torch AdamW weight_decay default is 0.01."""
    if name == "adamw":
        tx = optax.adamw(lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    elif name == "sgd":
        tx = optax.sgd(lr)
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    if max_grad_norm and max_grad_norm > 0:
        tx = optax.chain(optax.clip_by_global_norm(max_grad_norm), tx)
    return tx


def _merge(trainable: Tree, frozen: Optional[Tree]) -> Tree:
    """Full fine-tune: trainable IS the param tree. LoRA: merge adapters into
    the frozen base."""
    if frozen is None:
        return trainable
    with scope("lora_merge"):
        return lora_lib.apply_lora(frozen, trainable)


def model_variables(model, trainable: Tree, frozen: Optional[Tree]) -> dict:
    """The variables ``model.apply`` takes. Full fine-tune: the trained
    tree. LoRA: by the family's policy (``models.lora_policy``) the adapters
    merged into the frozen base, ``W + a b``, or handed to the model beside
    it as the ``lora`` collection, applied on the activations."""
    from bcfl_tpu.models import lora_policy

    if frozen is not None and lora_policy(model).on_activations:
        return {"params": frozen, "lora": lora_lib.as_collection(trainable)}
    return {"params": _merge(trainable, frozen)}


def model_counters(model) -> tuple:
    """``((name, "sum" | "max"), ...)``: what the model counts in a step
    (a flax ``counters`` collection), sums first; ``()`` for most models."""
    return tuple(getattr(model, "COUNTERS", ()))


def _named_values(jaxpr):
    """Every ``checkpoint_name`` in ``jaxpr`` and the jaxprs nested in it:
    ``(name, aval)``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            yield eqn.params["name"], eqn.outvars[0].aval
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _named_values(sub)


def remat_saved(model, task: str, trainable, frozen, batch) -> Tuple[int, int]:
    """``(values, bytes)``: what a model built with ``remat`` and a named save
    set (the family's ``REMAT_SAVED``, the names its ``nn.remat`` policy
    keeps) holds of one client's local step for the backward pass: the
    values that carry one of those names in the step's forward pass as a
    VJP traces it (a ``custom_vjp``'s forward rule is in it), and their
    bytes at the shapes of ``batch``. Nothing runs, so ``batch`` may be
    ``jax.ShapeDtypeStruct``s. ``(0, 0)`` for a model that keeps everything
    or rematerialises with no save set."""
    names = set(getattr(model, "REMAT_SAVED", ()))
    if not names or not model.cfg.remat:
        return 0, 0
    loss_fn = make_loss_fn(model, task)

    def forward(t, f, b):
        return jax.vjp(lambda t_: loss_fn(t_, f, b, None)[0], t)[0]

    kept = [aval for name, aval in _named_values(
        jax.make_jaxpr(forward)(trainable, frozen, batch).jaxpr) if name in names]
    return len(kept), sum(a.size * a.dtype.itemsize for a in kept)


def _counter_values(state, counters):
    """One value a counter from the ``counters`` collection a step filled:
    every module's entry folded by the counter's kind."""
    by_name = {name: [] for name, _ in counters}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        by_name[getattr(path[-1], "key", str(path[-1]))].append(leaf)
    return tuple(
        (jnp.max if kind == "max" else jnp.sum)(jnp.stack(by_name[name]))
        for name, kind in counters)


def make_loss_fn(model, task: str = "classification") -> Callable:
    """Per-batch loss + (correct, n) stats, shared by train and eval.

    ``classification``: softmax CE over the label column (reference task).
    ``causal_lm``: next-token CE — targets are ``ids`` shifted left, token
    positions weighted by the padding mask x example mask; ``n`` counts
    TOKENS, so the engine's loss/acc normalization is per-token.

    ``loss_fn(...) -> (loss, (correct, n, *counted))``: ``counted`` is one
    value for each of the model's counters (:func:`model_counters`; none for
    most models), which the local step carries out with its statistics.
    """
    counters = model_counters(model)

    def _forward(trainable, frozen, batch, rng):
        variables = model_variables(model, trainable, frozen)
        # under value_and_grad the backward pass of everything in here is
        # named transpose(jvp(fed.forward))
        with scope("forward"):
            out = model.apply(
                variables, batch["ids"], batch["mask"],
                deterministic=rng is None,
                rngs=None if rng is None else {"dropout": rng},
                **({"mutable": ["counters"]} if counters else {}),
            )
        if not counters:
            return out, ()
        logits, state = out
        return logits, jax.lax.stop_gradient(
            _counter_values(state["counters"], counters))

    @scope("loss")
    def _loss_cls(logits, batch):
        labels = batch["labels"]
        ex = batch["example_mask"].astype(jnp.float32)
        per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        n = jnp.maximum(ex.sum(), 1.0)
        loss = (per_ex * ex).sum() / n
        correct = ((jnp.argmax(logits, -1) == labels).astype(jnp.float32) * ex).sum()
        return loss, (correct, ex.sum())

    def with_counted(loss_of):
        def loss(trainable, frozen, batch, rng):
            logits, counted = _forward(trainable, frozen, batch, rng)
            value, aux = loss_of(logits, batch)
            return value, aux + counted

        loss.counters = counters
        return loss

    @scope("loss")
    def _loss_lm(logits, batch):  # logits [B, S, V]
        targets = batch["ids"][:, 1:]
        logits = logits[:, :-1]
        w = (batch["mask"][:, 1:].astype(jnp.float32)
             * batch["example_mask"].astype(jnp.float32)[:, None])
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets)
        n = jnp.maximum(w.sum(), 1.0)
        loss = (per_tok * w).sum() / n
        correct = ((jnp.argmax(logits, -1) == targets).astype(jnp.float32)
                   * w).sum()
        return loss, (correct, w.sum())

    if task == "classification":
        return with_counted(_loss_cls)
    if task == "causal_lm":
        return with_counted(_loss_lm)
    raise ValueError(f"unknown task {task!r}")


def _unstack_rng(r, impl=None):
    # rngs arrive as stacked key-data uint32 [..., K] (threefry K=2,
    # rbg K=4); rebuild typed keys. impl=None follows jax's default —
    # passing an explicit impl makes the programs independent of the
    # process-global config (FedConfig.prng_impl).
    return jax.random.wrap_key_data(r, impl=impl)


def make_eval_one(loss_fn) -> Callable:
    """(trainable, frozen, batches) -> summed [loss*n, correct, n] over the
    scanned eval batches."""

    def eval_one(trainable, frozen, batches):
        def step(carry, batch):
            loss, (correct, n, *_) = loss_fn(trainable, frozen, batch, None)
            return carry, jnp.stack([loss * n, correct, n])

        _, stats = lax.scan(step, 0.0, batches)
        return stats.sum(axis=0)

    return eval_one


def make_broadcast(mesh: ClientMesh) -> Callable:
    """global tree -> stacked per-client tree [C, ...] on the clients axis."""

    def broadcast(global_t):
        return jax.device_put(
            jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (mesh.num_clients,) + x.shape), global_t
            ),
            mesh.client_sharding(),
        )

    return broadcast


def _adopt_pull(client_t: Tree, global_t: Tree, pull: jnp.ndarray) -> Tree:
    """Pull-masked clients adopt the replicated ``global_t`` (broadcast
    fused into the select); everyone else keeps their stacked row: the
    ``adopt`` program's body."""
    return jax.tree.map(
        lambda x, g: jnp.where(
            pull.reshape((-1,) + (1,) * (x.ndim - 1)) > 0,
            jnp.broadcast_to(g, x.shape).astype(x.dtype), x),
        client_t, global_t)


def _exact_mean_spread(avg: Tree, new_t: Tree, mask: jnp.ndarray) -> Tree:
    """Serverless exact-mean aggregation: every unmasked client adopts the
    (mask-weighted) average, masked clients keep their own state (the
    ``gossip_steps == 0`` path)."""
    return jax.tree.map(
        lambda a, x: jnp.where(
            mask.reshape((-1,) + (1,) * (x.ndim - 1)) > 0,
            jnp.broadcast_to(a, x.shape), x),
        avg, new_t,
    )


def make_local_train(tx, loss_fn) -> Callable:
    """One client's local round: fresh optimizer state (reference semantics,
    ``server_IID_IMDB.py:109``), ``lax.scan`` over static-shape batches.
    ``(trainable, frozen, batches, rng) -> (trainable, [loss*n, correct, n])``.
    Shared by the 1-D clients mesh programs and the clients x tp composition
    (:mod:`bcfl_tpu.parallel.fed_tp`). A model that counts
    (``loss_fn.counters``) appends its counters to the statistics, summed or
    largest over the steps by their kind: they leave the device in the fetch
    the round makes anyway."""
    kinds = [k for _, k in getattr(loss_fn, "counters", ())]
    n_max = kinds.count("max")

    def local_train(trainable, frozen, batches, rng):
        with scope("optimizer_init"):
            opt_state = tx.init(trainable)
        steps = batches["ids"].shape[0]
        step_rngs = jax.random.split(rng, steps)

        def step(carry, xs):
            t, opt = carry
            batch, r = xs
            (loss, (correct, n, *counted)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(t, frozen, batch, r)
            with scope("optimizer"):
                updates, opt = tx.update(grads, opt, t)
                t = optax.apply_updates(t, updates)
            return (t, opt), jnp.stack([loss * n, correct, n, *counted])

        (trainable, _), stats = lax.scan(
            step, (trainable, opt_state), (batches, step_rngs))
        if n_max:  # the counters of kind "max" come last
            return trainable, jnp.concatenate(
                [stats[:, :-n_max].sum(axis=0), stats[:, -n_max:].max(axis=0)])
        return trainable, stats.sum(axis=0)

    return local_train


@dataclasses.dataclass
class FedPrograms:
    """Compiled round/eval programs bound to one (model, mesh, optimizer)."""

    mesh: ClientMesh
    server_round: Callable  # (global_t, frozen, batches, weights, rngs) -> (global_t, metrics)
    server_rounds: Callable  # R rounds in one program; batches/weights/rngs leaves [R, C, ...]
    server_rounds_static: Callable  # same, ONE batch tree [C, ...] reused every round
    gossip_round: Callable  # (client_t, frozen, batches, mask, rngs) -> (client_t, metrics)
    gossip_rounds: Callable  # R gossip rounds in one program; batches/masks/rngs leaves [R, C, ...]
    gossip_rounds_static: Callable  # same, ONE batch tree [C, ...] reused every round
    eval_clients: Callable  # (client_t, frozen, batches) -> per-client [C, 3] stats
    eval_clients_global: Callable  # (global_t, frozen, batches) -> per-client [C, 3] stats
    eval_global: Callable  # (trainable, frozen, batches) -> [loss*n, correct, n]
    broadcast: Callable  # global_t -> stacked client_t [C, ...]
    collapse: Callable  # (stacked client_t, weights, fallback) -> global mean
    # split-phase programs for the ledger flow (commit -> verify -> aggregate)
    # and the async engine:
    client_updates: Callable  # (global_t, frozen, batches, rngs) -> (stacked_t, metrics)
    local_updates: Callable  # (client_t, frozen, batches, rngs) -> (stacked_t, metrics)
    mix_only: Callable  # (client_t, mask, start_t) -> client_t (gossip mix / full mean)
    single_update: Callable  # (trainable, frozen, batches, rng) -> (trainable, stats);
    # one unstacked client, used by the reference-faithful sequential
    # serverless mode (SURVEY.md §3.2)
    # device-side ledger digests (bcfl_tpu.ledger.fingerprint) — [C, K] / [K]
    # content fingerprints so the ledger never pulls the full tree to host:
    fingerprint: Callable  # stacked client_t -> [C, K]
    fingerprint_one: Callable  # trainable -> [K]
    # transport-aware serverless mix for the split-phase corruption flow
    # (faults.FaultPlan): (self_t, recv_t, mask, start_t) -> client_t —
    # neighbor/aggregate terms from the TRANSPORTED tree, self-terms from
    # the honest local tree
    mix_recv: Callable
    # (client_t, global_t, pull) -> client_t: pull-masked clients adopt the
    # replicated global (broadcast fused into the select — ONE dispatch, no
    # materialized [C, ...] broadcast buffer). Used by the async engine's
    # post-merge pull and the chaos-partition scatter/heal (component
    # members adopt their component aggregate / the reconciled global).
    adopt: Callable
    # fused-round twins that ALSO emit each round's per-client update
    # fingerprints [R, C, K], so the ledger can fuse:
    server_rounds_fp: Callable
    server_rounds_static_fp: Callable
    gossip_rounds_fp: Callable
    gossip_rounds_static_fp: Callable
    # --- communication-compression programs (COMPRESSION.md), present iff
    # the builder's CompressionConfig is enabled. When
    # compression is on, the round/fused programs above change signature:
    # their first argument and first result become the carry tuple
    # ``(params_tree, ef_residual)`` — the error-feedback residual rides the
    # round state so compression error never accumulates. Split-phase twins:
    # (new_t, ref_t, resid, rngs) -> (payload, recon, resid'); ref is the
    # REPLICATED global (server) or the stacked round-start params
    # (serverless/async). ``recon`` is the clean-transport reconstruction
    # (ref + decoded delta) computed inside the encode program — the
    # roundtrip already decodes to derive the residual, so returning it
    # saves the engine a redundant full-tree decode on every uncorrupted
    # ledger round (corrupted rounds re-decode the TRANSPORTED payload via
    # decode_recon)
    encode_deltas: Optional[Callable] = None
    encode_deltas_local: Optional[Callable] = None
    # async twin WITHOUT the recon output: the async merge decodes the
    # (possibly corrupted) transported payload itself via decode_delta, so
    # a returned recon would be computed and thrown away every round
    encode_deltas_async: Optional[Callable] = None
    # (payload, ref_t, like_t) -> stacked recon tree (ref + decoded delta,
    # cast back to the param dtype) — what the receivers aggregate/mix
    decode_recon: Optional[Callable] = None
    # (payload, like_t) -> stacked decoded delta (param dtype) — async merge
    decode_delta: Optional[Callable] = None
    # (payload, [C] scales) -> transport-corrupted payload (float parts only)
    corrupt_payload: Optional[Callable] = None
    # (trainable_like) -> [C, ...] f32 zero error-feedback state
    ef_init: Optional[Callable] = None


def build_programs(
    model,
    mesh: ClientMesh,
    optimizer: str = "adamw",
    learning_rate: float = 5e-5,
    max_grad_norm: float = 0.0,
    gossip_alpha: float = 0.5,
    gossip_steps: int = 1,
    task: str = "classification",
    # Byzantine-robust aggregation rule (parallel.gspmd.AGGREGATORS,
    # ROBUSTNESS.md). A build-time static: each choice is its own compiled
    # program, so switching it never retraces inside a run.
    aggregator: str = "mean",
    aggregator_trim: float = 0.2,
    # typed-key impl for the stacked per-client rngs: None follows jax's
    # process default; "rbg" opts into the TPU hardware generator
    # (dropout RNG is +38% of step time under threefry, PERF.md)
    prng_impl: Optional[str] = None,
    # communication compression for the update exchange (COMPRESSION.md).
    # A build-time static like the aggregator: every CompressionConfig is
    # its own compiled program set (the config is part of the program-cache
    # key below), so switching codecs never retraces inside a run. None or
    # kind='none' builds EXACTLY today's uncompressed programs — that path
    # is untouched, bit-for-bit.
    compression: Optional[CompressionConfig] = None,
    # donate=True deletes the caller's input param/opt buffers after each call
    # (halves peak HBM for the round-chained engine); leave False if you reuse
    # the input tree afterwards.
    donate: bool = False,
    # hierarchical=True compiles the explicit two-level device -> global
    # aggregation (gspmd.hierarchical_weighted_mean) into every mean
    # aggregation point — cohort mode's within-cohort-then-cross-device
    # reduction (SCALING.md). Only meaningful for aggregator='mean' (the
    # robust order statistics are global by definition); normalized away
    # otherwise so equal program sets share one cache entry.
    hierarchical: bool = False,
    # per-client LoRA rank tuple (FedConfig.client_lora_ranks) for
    # HETEROGENEOUS fleets: every client is materialized zero-padded at
    # max(lora_ranks), the [C, R] padding mask compiles in as a closure
    # constant (static in this tuple — part of the cache key below, zero
    # per-round retraces), locals are clipped to their own rank at
    # train entry, and every 'mean' aggregation point becomes the
    # rank-aware RBLA rule (gspmd.rank_aware_weighted_mean). None or a
    # uniform tuple builds EXACTLY the plain programs.
    lora_ranks: Optional[tuple] = None,
) -> FedPrograms:
    if lora_ranks is not None and len(set(lora_ranks)) <= 1:
        # uniform spec == plain build: the all-ones clip would be a
        # different (wastefully retraced) program computing the identity
        lora_ranks = None
    if compression is not None and not compression.enabled:
        # normalize so compress='none' and no-compression callers share ONE
        # cache entry — they are the same programs by construction (the
        # builders never branch on a disabled config), and the shared entry
        # makes that identity observable: build_programs(compression=none)
        # IS build_programs() (tests/test_compression.py pins it)
        compression = None
    # same normalization for the hierarchical flag: it only changes the
    # 'mean' aggregation body, so a hierarchical trimmed_mean/median/krum
    # build IS the plain build — sharing the entry keeps cohort-mode robust
    # runs on the exact programs the chaos matrix already compiled
    hierarchical = bool(hierarchical) and aggregator == "mean"
    # Program memoization: flax modules and jax Meshes hash/compare by VALUE
    # (module config dataclasses, mesh devices + axis names), so two engines
    # over equal configs get the SAME jitted program objects — and with them
    # XLA's compile cache. Sweeps (run_results, scaling ladders) and the test
    # suite re-create engines constantly; without this every one recompiles
    # every program (~half the r04 suite's 36 minutes). Unhashable inputs
    # (e.g. an sp-injected attention closure compares by identity) just skip
    # the cache — never wrong, only cold.
    try:
        # ClientMesh is a frozen dataclass: hashing the instance covers every
        # mesh field, including any added later that changes program layout
        key = (model, mesh, optimizer, learning_rate, max_grad_norm,
               gossip_alpha, gossip_steps, task, aggregator, aggregator_trim,
               prng_impl, donate, compression, hierarchical, lora_ranks)
        hash(key)
    except TypeError:
        key = None
    if os.environ.get("BCFL_PROGRAM_CACHE", "1") == "0":  # debug kill-switch
        key = None
    if key is not None and key in _PROGRAM_CACHE:
        return _PROGRAM_CACHE[key]
    progs = _build_programs(
        model, mesh, optimizer=optimizer, learning_rate=learning_rate,
        max_grad_norm=max_grad_norm, gossip_alpha=gossip_alpha,
        gossip_steps=gossip_steps, donate=donate, task=task,
        aggregator=aggregator, aggregator_trim=aggregator_trim,
        prng_impl=prng_impl, compression=compression,
        hierarchical=hierarchical, lora_ranks=lora_ranks)
    if key is not None:
        while len(_PROGRAM_CACHE) >= _PROGRAM_CACHE_MAX:
            # FIFO eviction bounds the compiled-executable footprint over a
            # long sweep; live engines keep their own references, so an
            # evicted entry frees only once no engine uses it
            _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
        _PROGRAM_CACHE[key] = progs
    return progs


_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_MAX = 32


def clear_program_cache() -> None:
    """Drop all memoized program sets (their compiled executables free once
    no live engine references them)."""
    _PROGRAM_CACHE.clear()


def _build_programs(
    model,
    mesh: ClientMesh,
    *,
    optimizer: str,
    learning_rate: float,
    max_grad_norm: float,
    gossip_alpha: float,
    gossip_steps: int,
    donate: bool,
    task: str,
    aggregator: str,
    aggregator_trim: float,
    prng_impl: Optional[str],
    compression: Optional[CompressionConfig],
    hierarchical: bool,
    lora_ranks: Optional[tuple],
) -> FedPrograms:
    """What :func:`build_programs` memoizes, on its normalized arguments
    (``compression`` None unless enabled). Every program takes and returns global stacked-client arrays; the
    bodies are plain global-array math under ``jit`` with sharding
    annotations — reductions/rolls over the sharded client dim become XLA
    all-reduce / collective-permute (:mod:`bcfl_tpu.parallel.gspmd`).

    ``aggregator`` swaps the masked weighted mean for a Byzantine-robust
    rule at every aggregation point that consumes a full stacked-client
    view: server FedAvg (per-round and fused), the consensus ``collapse``,
    and the serverless exact-mean (``gossip_steps == 0``). Ring-gossip
    diffusion (``gossip_steps > 0``) keeps its pairwise mixing rule — a
    two-neighbour exchange has no order statistics to harden.

    ``compression`` (enabled) compiles the update-exchange codecs
    (:mod:`bcfl_tpu.compression`, COMPRESSION.md) into every aggregation
    path: each client's post-train DELTA vs the round's reference params is
    error-feedback-compensated, encoded, and only the DECODED (lossy)
    reconstruction reaches the aggregator / gossip mix — the sender's own
    carried state stays its honest local tree (the existing ``mix_recv``
    transport split). The round/fused programs then carry
    ``(params, ef_residual)`` tuples instead of a bare tree; the fused
    ``*_fp`` twins fingerprint the COMPRESSED payload before and after the
    simulated transport stage, so ledger auth covers exactly the bytes on
    the wire. ``None``/'none' leaves every body below byte-identical to the
    uncompressed build."""
    comp = compression
    # hierarchical (cohort mode): every 'mean' aggregation point — server
    # FedAvg, collapse, the serverless exact-mean — becomes the explicit
    # within-device-stack then cross-device reduction; groups = the mesh's
    # clients-axis extent, so each inner group IS one device's cohort slice
    groups = int(mesh.mesh.shape[mesh.axis]) if hierarchical else 0
    # heterogeneous LoRA ranks: the [C, R] padding mask is a CLOSURE
    # CONSTANT derived from the static rank tuple — it compiles into every
    # program below (clipped train entry, RBLA aggregation, clipped codec
    # deltas), so which client trains at which rank never retraces
    rmask = (None if lora_ranks is None
             else lora_lib.rank_mask(lora_ranks))
    agg = scope("aggregate")(gspmd.make_aggregator(
        aggregator, aggregator_trim, hierarchical_groups=groups,
        rank_mask=rmask))
    tx = make_optimizer(optimizer, learning_rate, max_grad_norm)
    loss_fn = make_loss_fn(model, task)
    unstack = lambda r: _unstack_rng(r, prng_impl)  # noqa: E731
    local_train = make_local_train(tx, loss_fn)
    jmesh = mesh.mesh
    cl = NamedSharding(jmesh, P(mesh.axis))
    rcl = NamedSharding(jmesh, P(None, mesh.axis))
    repl = NamedSharding(jmesh, P())

    def _c(tree, sh):
        return jax.tree.map(lambda x: lax.with_sharding_constraint(x, sh), tree)

    def _don(*idx):
        return idx if donate else ()

    # every client trains from the same replicated trainable. Heterogeneous
    # ranks clip the replicated global to EACH client's own rank at train
    # entry (a low-rank client never sees the fleet's higher-rank
    # components); both factors of a padded dim enter at exactly 0, so
    # grads there are 0 and AdamW keeps them exactly 0 through the round —
    # no post-aggregation re-clip is needed on any path.
    def train_clients(global_t, frozen, batches, rngs):
        if rmask is None:
            new_t, stats = jax.vmap(
                lambda b, r: local_train(global_t, frozen, b, unstack(r))
            )(batches, rngs)
        else:
            new_t, stats = jax.vmap(
                lambda mrow, b, r: local_train(
                    lora_lib.clip_adapters(global_t, mrow), frozen, b,
                    unstack(r))
            )(rmask, batches, rngs)
        return _c(new_t, cl), _c(stats, cl)

    @scope("transport")
    def _transport(new_t, c_row):
        """Simulated transport of a client-stacked update tree: the buffer
        that reaches aggregation is ``new_t + c_row`` (per-client scalar,
        0 = clean — an exact float identity, so an honest round's post-
        transport fingerprints match the committed ones bit-for-bit). The
        corruption input is what makes fused-mode ledger auth a real check
        rather than an identity: commit fingerprints are taken BEFORE this
        point, verification fingerprints AFTER."""
        return jax.tree.map(
            lambda x: x + c_row.reshape((-1,) + (1,) * (x.ndim - 1))
            .astype(x.dtype), new_t)

    @scope("fingerprint")
    def _fp_auth(new_t, c_row):
        """(sent_t, fp_commit, fp_recv, auth): fingerprint the update before
        and after simulated transport and compare in-graph. ``auth`` [C] is
        1.0 iff every fingerprint lane survived transport unchanged."""
        fp_commit = _c(client_fingerprint(new_t), cl)
        sent_t = _transport(new_t, c_row)
        fp_recv = _c(client_fingerprint(sent_t), cl)
        auth = jnp.all(fp_recv == fp_commit, axis=-1).astype(jnp.float32)
        return sent_t, fp_commit, fp_recv, _c(auth, cl)

    # ---- communication-compression stages (comp is not None only) ----
    def _ckey(rngs):
        # codec stochastic-rounding key: derived from the same per-round
        # stacked key rows the training consumes, on a lane the training
        # stream never touches — identical on the per-round and fused paths
        return cc.codec_key(unstack(rngs))

    @scope("codec.encode")
    def _compress_stage(new_t, ref_t, resid, rngs):
        """Sender side of one wire exchange: ``(payload, decoded, resid')``
        for ``delta = new_t - ref_t`` (+ the carried error-feedback
        residual). ``ref_t`` may be the replicated global (server) or the
        stacked round-start params (serverless) — the subtract broadcasts."""
        delta = jax.tree.map(
            lambda n, g: n.astype(jnp.float32) - g.astype(jnp.float32),
            new_t, ref_t)
        if rmask is not None:
            # a client's delta on its PADDING dims is -ref there (its local
            # is structurally 0, the global needn't be): those dims aren't
            # the client's to ship — clip them so the codec budget (top-k
            # slots, quantization range) is spent on real coordinates and
            # the EF residual stays exactly 0 on padding
            delta = jax.vmap(lora_lib.clip_adapters)(delta, rmask)
        payload, dec, resid = cc.roundtrip(comp, delta, resid, _ckey(rngs))
        return _c(payload, cl), dec, _c(resid, cl)

    @scope("codec.decode")
    def _recon(ref_t, dec, like_t):
        """Receiver-side reconstruction ``ref + decoded delta``, cast back to
        the param dtype — the stacked tree the aggregator/mix consumes."""
        return _c(jax.tree.map(
            lambda g, d, n: (g.astype(jnp.float32) + d).astype(n.dtype),
            ref_t, dec, like_t), cl)

    @scope("fingerprint")
    def _fp_auth_payload(payload, c_row):
        """Compressed twin of ``_fp_auth``: fingerprints are taken over the
        COMPRESSED payload (the bytes actually on the wire), transport
        corrupts the payload's float parts, and auth is the in-graph
        comparison. c_row == 0 keeps the payload bit-identical (exact float
        identity), so clean rounds authenticate bit-for-bit."""
        fp_commit = _c(client_fingerprint(payload), cl)
        sent = cc.corrupt_payload(payload, c_row)
        fp_recv = _c(client_fingerprint(sent), cl)
        auth = jnp.all(fp_recv == fp_commit, axis=-1).astype(jnp.float32)
        return sent, fp_commit, fp_recv, _c(auth, cl)

    @scope("aggregate")
    def _mix(self_t, recv_t, mask, fallback):
        """Post-train serverless aggregation. gossip_steps == 0 -> exact
        mask-weighted all-client aggregate (the configured rule), the
        reference-faithful serverless aggregation
        (serverless_NonIID_IMDB.py:296): every participating client ends the
        round with the same average; ``fallback`` (the round's STARTING
        per-client params) is what the average of an all-masked round
        reads. gossip_steps > 0 -> masked ring diffusion.

        Transport-aware: neighbor/aggregate terms come from ``recv_t`` (the
        TRANSPORTED or reconstructed tree), the self-term (and a masked
        client's kept state) from the client's own honest post-train tree
        ``self_t`` — in-flight corruption must not rewrite the sender's
        local copy. A clean exchange passes the same tree twice."""
        if gossip_steps == 0:
            avg = agg(recv_t, mask, fallback)
            return _exact_mean_spread(avg, self_t, mask)
        return gspmd.gossip_mix_recv(self_t, recv_t, mask, gossip_alpha,
                                     steps=gossip_steps)

    # each client trains from its OWN stacked params (same per-client rank
    # clip at entry as train_clients — an adopted global's higher-rank
    # components are chopped before a low-rank client optimizes)
    def local_updates_body(client_t, frozen, batches, rngs):
        if rmask is None:
            new_t, stats = jax.vmap(
                lambda t, b, r: local_train(t, frozen, b, unstack(r))
            )(client_t, batches, rngs)
        else:
            new_t, stats = jax.vmap(
                lambda mrow, t, b, r: local_train(
                    lora_lib.clip_adapters(t, mrow), frozen, b, unstack(r))
            )(rmask, client_t, batches, rngs)
        return _c(new_t, cl), _c(stats, cl)

    def _round_step(mode: str, with_fp: bool):
        """THE definition of one federated round; every round program below
        is this step, jitted alone (``server_round``, ``gossip_round``) or
        scanned over R rounds (:func:`_make_rounds`). Four stages, each an
        identity when its feature is off:

        - train: ``server`` trains every client from the replicated global,
          ``gossip`` each client from its own stacked params;
        - encode (compression on): the carry is ``(params, EF residual)``;
          each client's delta vs the round's reference (the global, or its
          own round-start params, which its neighbours hold from the
          previous exchange) is error-feedback-compensated and encoded, and
          only the lossy RECONSTRUCTION reaches the combine stage — never
          the honest full-precision update. The residual is per-client
          sender state riding the carry: compression error re-enters the
          next round's encode instead of accumulating (COMPRESSION.md);
        - verify (``with_fp``): takes the round's per-client transport
          corruption row [C] and emits ``(stats, fp_commit, fp_recv, auth)``:
          ``fp_commit`` digests the pre-transport update or payload (what
          each client commits to the ledger), ``fp_recv`` the
          post-transport buffer that is actually combined, and the combine
          weights are gated by the in-graph comparison — a corrupted update
          is EXCLUDED from the aggregate, not just flagged, which keeps the
          fused fast path a real verification, not an accounting identity.
          Off, ledger/corruption rounds run split-phase instead;
        - combine: ``server`` aggregates to the replicated global (an
          all-masked round keeps the round's starting params);
          ``gossip`` mixes — neighbour/aggregate terms from what crossed the
          wire (the transported or reconstructed tree), each sender's
          self-term from its honest post-train tree (``_mix``).
        """
        server = mode == "server"
        train = train_clients if server else local_updates_body

        def step(carry, frozen, batches, weights, rngs, c_row=None):
            ref_t, resid = carry if comp is not None else (carry, None)
            new_t, stats = train(ref_t, frozen, batches, rngs)
            recv_t, out = new_t, stats
            if comp is not None:
                payload, dec, resid = _compress_stage(
                    new_t, ref_t, resid, rngs)
                if with_fp:
                    sent, fpc, fpr, auth = _fp_auth_payload(payload, c_row)
                    # decode the TRANSPORTED payload: a corrupted wire
                    # yields a corrupted reconstruction, which auth
                    # excludes from the combine
                    dec = cc.decode_tree(comp, sent, new_t)
                recv_t = _recon(ref_t, dec, new_t)
            elif with_fp:
                recv_t, fpc, fpr, auth = _fp_auth(new_t, c_row)
            if with_fp:
                weights, out = weights * auth, (stats, fpc, fpr, auth)
            if server:
                nxt = _c(agg(recv_t, weights, ref_t), repl)
            else:
                nxt = _c(_mix(new_t, recv_t, weights, ref_t), cl)
            return (nxt if comp is None else (nxt, resid)), out

        return step

    def _carry_sharding(mode: str):
        params = repl if mode == "server" else cl
        return params if comp is None else (params, cl)

    def _make_round(mode: str):
        """The per-round program: one step, no scan."""
        return jax.jit(_round_step(mode, with_fp=False),
                       donate_argnums=_don(0),
                       out_shardings=(_carry_sharding(mode), cl))

    def _make_rounds(mode: str, static: bool, with_fp: bool):
        """Fused R-round program: the step scanned over round-leading
        weights/rngs [R, C, ...] (and corruption rows [R, C] with
        ``with_fp``, whose outputs then carry fingerprints [R, C, K]).
        ``static`` reuses ONE batch tree [C, ...] every round instead of
        consuming batches [R, C, ...]."""
        step = _round_step(mode, with_fp)

        def body(carry, frozen, batches, weights, rngs, corrupts=None):
            def one_round(t, xs):
                if static:
                    return step(t, frozen, batches, *xs)
                return step(t, frozen, *xs)

            xs = (weights, rngs) if static else (batches, weights, rngs)
            if with_fp:
                xs = xs + (corrupts,)
            return lax.scan(one_round, carry, xs)

        out_sh = (rcl, rcl, rcl, rcl) if with_fp else rcl
        return jax.jit(body, donate_argnums=_don(0),
                       out_shardings=(_carry_sharding(mode), out_sh))

    client_updates = jax.jit(train_clients, out_shardings=(cl, cl))

    local_updates = jax.jit(local_updates_body, out_shardings=(cl, cl))

    mix_only = jax.jit(
        lambda client_t, mask, fallback: _c(
            _mix(client_t, client_t, mask, fallback), cl),
        out_shardings=cl)

    mix_recv = jax.jit(
        lambda self_t, recv_t, mask, fallback: _c(
            _mix(self_t, recv_t, mask, fallback), cl),
        out_shardings=cl)

    single_update = jax.jit(local_train)

    eval_one = make_eval_one(loss_fn)

    eval_clients = jax.jit(
        lambda client_t, frozen, b: _c(
            jax.vmap(lambda t, bb: eval_one(t, frozen, bb))(client_t, b), cl),
        out_shardings=cl)

    eval_clients_global = jax.jit(
        lambda g, f, b: _c(jax.vmap(lambda bb: eval_one(g, f, bb))(b), cl),
        out_shardings=cl)

    eval_global = jax.jit(eval_one)

    broadcast = make_broadcast(mesh)

    collapse = jax.jit(
        lambda t, w, fallback: _c(agg(t, w, fallback), repl),
        out_shardings=repl)

    adopt = jax.jit(
        lambda client_t, global_t, pull: _c(
            _adopt_pull(client_t, global_t, pull), cl),
        out_shardings=cl)

    # ---- split-phase codec programs (per-round ledger/corruption flow) ----
    # The engine composes these exactly like the uncompressed split-phase
    # sequence (client_updates -> commit -> transport -> verify ->
    # aggregate), except the quantity that is fingerprinted, corrupted, and
    # shipped is the compressed payload. Same codec math as the in-graph
    # stages above, so fused and per-round rounds commit identical digests
    # for identical content.
    encode_deltas = encode_deltas_local = decode_recon = decode_delta = None
    encode_deltas_async = corrupt_payload_p = ef_init = None
    if comp is not None:
        def _enc(new_t, ref_t, resid, rngs):
            payload, dec, resid = _compress_stage(new_t, ref_t, resid, rngs)
            return payload, _recon(ref_t, dec, new_t), resid

        def _enc_delta(new_t, ref_t, resid, rngs):
            payload, _, resid = _compress_stage(new_t, ref_t, resid, rngs)
            return payload, resid

        # separate jit objects so the replicated-ref (server/global) and
        # stacked-ref (serverless) traces each own one cache entry
        encode_deltas = jax.jit(_enc)
        encode_deltas_local = jax.jit(_enc)
        encode_deltas_async = jax.jit(_enc_delta)
        decode_recon = jax.jit(
            lambda payload, ref_t, like_t: _recon(
                ref_t, cc.decode_tree(comp, payload, like_t), like_t))
        decode_delta = jax.jit(
            lambda payload, like_t: _c(jax.tree.map(
                lambda d, n: d.astype(n.dtype),
                cc.decode_tree(comp, payload, like_t), like_t), cl))
        corrupt_payload_p = jax.jit(
            lambda payload, scales: _c(cc.corrupt_payload(payload, scales),
                                       cl))
        ef_init = jax.jit(
            lambda t: cc.zero_residual(t, mesh.num_clients),
            out_shardings=cl)

    # the eight fused programs under their field names: {mode}_rounds,
    # then _static, then _fp (the engine composes the same names)
    fused = {
        f"{mode}_rounds" + "_static" * static + "_fp" * with_fp:
            _make_rounds(mode, static, with_fp)
        for mode in ("server", "gossip")
        for static in (False, True) for with_fp in (False, True)}

    return FedPrograms(
        mesh=mesh,
        server_round=_make_round("server"),
        gossip_round=_make_round("gossip"),
        **fused,
        eval_clients=eval_clients,
        eval_clients_global=eval_clients_global,
        eval_global=eval_global,
        broadcast=broadcast,
        collapse=collapse,
        client_updates=client_updates,
        local_updates=local_updates,
        mix_only=mix_only,
        single_update=single_update,
        adopt=adopt,
        fingerprint=jax.jit(lambda t: _c(client_fingerprint(t), cl),
                            out_shardings=cl),
        fingerprint_one=jax.jit(lambda t: tree_fingerprint(t)),
        mix_recv=mix_recv,
        encode_deltas=encode_deltas,
        encode_deltas_local=encode_deltas_local,
        encode_deltas_async=encode_deltas_async,
        decode_recon=decode_recon,
        decode_delta=decode_delta,
        corrupt_payload=corrupt_payload_p,
        ef_init=ef_init,
    )
