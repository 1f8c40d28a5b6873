"""CLI: ``python -m bcfl_tpu.entrypoints --preset serverless_noniid_imdb``.

Replaces running the 11 reference scripts directly; every SURVEY.md §2.1
config knob is an override flag.

Subcommands: ``bcfl-tpu trace RUN_DIR`` collates a run's per-process event
streams into one causally-ordered timeline and runs the invariant checks
(bcfl_tpu.telemetry, OBSERVABILITY.md) — exit 1 on any violation.
``bcfl-tpu monitor RUN_DIR`` is the LIVE counterpart: incremental
collation + streaming invariants + the per-round health series over a run
that is still going (OBSERVABILITY.md §6).
``bcfl-tpu lint [PATHS]`` runs the AST static-analysis checkers over the
package (bcfl_tpu.analysis, ANALYSIS.md) — exit 1 on any unsuppressed
finding; ``--list-checkers`` prints the catalogue.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

from bcfl_tpu.compression import KINDS as COMPRESS_KINDS
from bcfl_tpu.core.hostenv import compile_cache
from bcfl_tpu.entrypoints.presets import _HF, get_preset, list_presets
from bcfl_tpu.entrypoints.run import run, run_sweep


def main(argv=None):
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "trace":
        # the observability subcommand: no jax import, works on any
        # machine that can read the stream files
        from bcfl_tpu.telemetry import trace_main

        raise SystemExit(trace_main(argv[1:]))
    if argv and argv[0] == "monitor":
        # the LIVE observability subcommand (OBSERVABILITY.md §6): tails
        # a possibly-running fleet's streams; no jax import, exits 1 on
        # any invariant violation or unhealed critical alert
        from bcfl_tpu.telemetry.live import monitor_main

        raise SystemExit(monitor_main(argv[1:]))
    if argv and argv[0] == "lint":
        # the static-analysis subcommand (ANALYSIS.md): the checkers are
        # stdlib-ast only (the package import chain still pays the usual
        # bcfl_tpu config imports, like trace); exits nonzero on any
        # unsuppressed finding
        from bcfl_tpu.analysis import lint_main

        raise SystemExit(lint_main(argv[1:]))
    ap = argparse.ArgumentParser(prog="bcfl_tpu")
    ap.add_argument("--preset", default="smoke",
                    help=f"one of: {', '.join(list_presets())}")
    ap.add_argument("--hf", action="store_true",
                    help="import real HF checkpoint weights (needs hub access)")
    ap.add_argument("--sweep", action="store_true",
                    help="run the 5/10/20-worker sweep like "
                         "serverless_cancer_biobert_allclients.py")
    ap.add_argument("--resume", action="store_true")
    # common overrides (None = keep preset value)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--model", default=None)
    ap.add_argument("--dataset", default=None)
    ap.add_argument("--mode", choices=["server", "serverless"], default=None)
    # real multi-process async P2P runtime (bcfl_tpu.dist, RUNTIME.md):
    # spawns and supervises one OS process per peer over loopback TCP
    ap.add_argument("--runtime", choices=["local", "dist"], default=None,
                    help="'dist' runs the real multi-process async P2P "
                         "runtime: --peers OS processes, each owning a "
                         "client slice, exchanging updates over TCP with "
                         "FedBuff-buffered aggregation and MEASURED "
                         "staleness (implies sync=async, eval_every=0; "
                         "feature support per the config capability table)")
    ap.add_argument("--peers", type=int, default=None,
                    help="peer process count for --runtime dist "
                         "(num_clients must split evenly across them)")
    ap.add_argument("--dist-deadline", type=float, default=600.0,
                    help="hard per-peer wall deadline in seconds for "
                         "--runtime dist (a hung peer fails the run)")
    ap.add_argument("--dist-buffer", type=int, default=None,
                    metavar="N",
                    help="FedBuff merge target for --runtime dist, in "
                         "DISTINCT sending peers (0 = merge on every "
                         "arrival, the pure-async default; must be <= "
                         "peers). The robust --aggregator rules need "
                         ">= 3 (krum: >= 2f+3) — RUNTIME.md §5")
    ap.add_argument("--dist-quorum", type=float, default=None,
                    metavar="FRAC",
                    help="quorum fraction for --runtime dist leaders: the "
                         "merge target counts only peers the failure "
                         "detector does NOT hold DOWN, and below this "
                         "reachable fraction of the component the leader "
                         "stops advancing the global (default 0.5; "
                         "RUNTIME.md 'Delivery contract')")
    ap.add_argument("--no-dist-pipeline", action="store_true",
                    help="disable the comms/compute overlap pipeline for "
                         "--runtime dist (per-destination sender workers + "
                         "double-buffered merge intake, on by default — "
                         "RUNTIME.md §4); the serial PR 7-10 loop is the "
                         "wire_perf.py A/B baseline")
    ap.add_argument("--dist-pipeline-depth", type=int, default=None,
                    metavar="N",
                    help="bounded per-destination handoff queue for the "
                         "pipelined sender (default 2): a slow link blocks "
                         "the round loop after N queued frames "
                         "(back-pressure) instead of buffering unbounded "
                         "model-sized trees")
    ap.add_argument("--task", choices=["classification", "causal_lm"],
                    default=None,
                    help="causal_lm = federated next-token fine-tuning "
                         "(llama-family models; label columns ignored)")
    ap.add_argument("--sync", choices=["sync", "async"], default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--lora-rank", type=int, default=None)
    ap.add_argument("--lora-ranks", type=str, default=None,
                    help="heterogeneous per-client adapter ranks, e.g. "
                         "'2,4,8' cycled over clients (RBLA aggregation; "
                         "COMPRESSION.md 'Adapter exchange'). Exclusive "
                         "with --lora-rank")
    ap.add_argument("--max-local-batches", type=int, default=None)
    # cohort-batched client scale-out (SCALING.md "Cohort mode"): simulate
    # a registry far larger than the mesh; a seeded sampler draws each
    # round's active cohort onto the stacked axis
    ap.add_argument("--registry-size", type=int, default=None,
                    help="simulate a registry of N clients (host state "
                         "only); each round a seeded sampler draws "
                         "--sample-clients of them onto the mesh. Device "
                         "memory and per-round cost are bounded by the "
                         "cohort, not N. Requires mode=server")
    ap.add_argument("--sample-clients", type=int, default=None,
                    help="per-round sampled cohort size (the stacked "
                         "client-axis width) under --registry-size; "
                         "defaults to --clients")
    ap.add_argument("--cohort-size", type=int, default=None,
                    help="clients stacked (vmapped) per device: pins the "
                         "mesh to sample_clients/cohort_size devices; must "
                         "divide the sampled cohort size")
    ap.add_argument("--rounds-per-dispatch", type=int, default=None,
                    help="fuse up to N federated rounds into one XLA dispatch "
                         "(sync server FedAvg or parallel gossip; the ledger "
                         "fuses too via in-graph fingerprints — only anomaly "
                         "filters, tamper hooks, and faithful mode fall back "
                         "to per-round)")
    ap.add_argument("--sp", type=int, default=None,
                    help="sequence-parallel shards per client: 2-D "
                         "(clients, seq) mesh, ring attention over the seq "
                         "axis (llama causal / encoder non-causal)")
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel shards per client (2-D clients x tp "
                         "mesh; requires --lora-rank > 0)")
    ap.add_argument("--pod", action="store_true",
                    help="span the mesh over every host in the pod "
                         "(jax.distributed must be initialized; see "
                         "core.mesh.distributed_init)")
    ap.add_argument("--eval-every", type=int, default=None,
                    help="evaluate every Nth round (per-round eval caps "
                         "fused dispatches at 1 round and dominates wall on "
                         "slow hosts; the final round always evaluates). "
                         "0 disables evaluation entirely — including the "
                         "final round (pure-throughput runs)")
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--use-flash", choices=["on", "off"], default=None,
                    help="force the O(S)-memory blockwise/Pallas attention "
                         "path on or off (default: the model family's "
                         "choice — llama flashes from seq 512, encoders "
                         "stay dense)")
    ap.add_argument("--compute-dtype", default=None,
                    choices=["float32", "bfloat16", "float16"])
    ap.add_argument("--donate", action="store_true",
                    help="donate each round's input param/opt buffers to "
                         "the round program: half the per-round peak HBM "
                         "(one run per engine)")
    ap.add_argument("--remat", action="store_true",
                    help="per-layer activation rematerialization: less HBM "
                         "per client (more clients stack per chip). Encoders "
                         "and llama keep a layer's input and run its forward "
                         "again (~1/3 more FLOPs); latent_moe keeps a named "
                         "set of values too (53 KB a position a layer at the "
                         "published widths) and runs no product twice")
    ap.add_argument("--prng-impl", default=None,
                    choices=["threefry", "rbg", "unsafe_rbg"],
                    help="typed-key PRNG: rbg = TPU hardware generator "
                         "(dropout RNG is +38%% of step time under the "
                         "threefry default; a different deterministic "
                         "stream, like changing the seed); unsafe_rbg "
                         "trades cross-version reproducibility for the "
                         "fastest fold/split path")
    ap.add_argument("--param-dtype", default=None,
                    choices=["float32", "bfloat16", "float16"])
    ap.add_argument("--faithful", action="store_true",
                    help="reference-exact sequential serverless semantics")
    ap.add_argument("--anomaly-filter",
                    choices=["pagerank", "dbscan", "zscore", "community", "none"],
                    default=None)
    ap.add_argument("--gossip-steps", type=int, default=None,
                    help="ring-gossip diffusion steps per serverless round "
                         "(0 = exact mask-weighted mean via the configured "
                         "--aggregator — required for --chaos-partition in "
                         "serverless mode; ring diffusion has no "
                         "per-component form)")
    ap.add_argument("--fused-tamper", action="append", default=None,
                    metavar="ROUND:CLIENT:SCALE",
                    help="inject a simulated transport corruption (additive "
                         "SCALE) into CLIENT's update in fused round ROUND "
                         "(repeatable). The corrupted update fails ledger "
                         "auth and is excluded from the aggregate — the "
                         "BC-FL tamper-resistance demo. Needs --ledger and "
                         "a fused dispatch (--rounds-per-dispatch > 1); a "
                         "request landing on a per-round-path round fails "
                         "loudly instead of being ignored")
    ap.add_argument("--ledger", action="store_true",
                    help="enable the hash-chained weight ledger (BC-FL)")
    ap.add_argument("--aggregator", default=None,
                    choices=["mean", "trimmed_mean", "median", "krum"],
                    help="aggregation rule compiled into the round program "
                         "(ROBUSTNESS.md): mean = reference FedAvg; the "
                         "robust rules survive up to an aggregator-trim "
                         "fraction of Byzantine clients without the ledger")
    ap.add_argument("--aggregator-trim", type=float, default=None,
                    help="assumed Byzantine fraction for trimmed_mean/krum "
                         "(default 0.2, must be < 0.5)")
    # communication compression (bcfl_tpu.compression, COMPRESSION.md):
    # quantized / top-k client deltas with error feedback, compiled into
    # the round programs; bytes-on-wire lands in the round records
    ap.add_argument("--compress", default=None,
                    choices=list(COMPRESS_KINDS),
                    help="compress the update exchange: int8 = per-chunk "
                         "quantized deltas (stochastic rounding), topk = "
                         "top-k sparsified deltas, int8+topk = both; error-"
                         "feedback residuals keep compression error from "
                         "accumulating. 'none' is bit-identical to the "
                         "uncompressed round programs")
    ap.add_argument("--compress-topk", type=float, default=None,
                    metavar="FRAC",
                    help="fraction of coordinates the topk codecs keep "
                         "(default 0.05)")
    ap.add_argument("--compress-chunk", type=int, default=None, metavar="N",
                    help="elements per int8 quantization chunk — one f32 "
                         "scale each (default 256)")
    ap.add_argument("--no-compress-ef", action="store_true",
                    help="disable the error-feedback residual (ablation; "
                         "compression error then accumulates)")
    # chaos harness (bcfl_tpu.faults.FaultPlan, ROBUSTNESS.md): seeded,
    # deterministic fault injection — the resilience demo knobs
    ap.add_argument("--chaos-dropout", type=float, default=None,
                    metavar="P", help="per-round per-client dropout "
                    "probability (fault injection)")
    ap.add_argument("--chaos-straggler", type=float, default=None,
                    metavar="P", help="per-round per-client straggler "
                    "probability (simulated-clock delay)")
    ap.add_argument("--chaos-straggler-delay", type=float, default=30.0,
                    metavar="SECONDS", help="injected straggler delay")
    ap.add_argument("--chaos-corrupt", type=float, default=None,
                    metavar="P", help="per-round per-client transport-"
                    "corruption probability; with --ledger corrupted "
                    "updates fail auth, without it use a robust "
                    "--aggregator")
    ap.add_argument("--chaos-crash-round", type=int, default=None,
                    metavar="N", help="inject a host crash at round N "
                    "(resume afterwards with --resume)")
    # partition / churn / flaky lanes (ROBUSTNESS.md §6)
    ap.add_argument("--chaos-partition", default=None, metavar="GROUPS",
                    help="split the mesh into isolated components for the "
                         "--chaos-partition-rounds span: explicit groups "
                         "like '0,1/2,3' (slash-separated; unlisted clients "
                         "form one extra component) or an integer N for a "
                         "seeded N-way split. Each component aggregates "
                         "independently with the configured --aggregator "
                         "and the components reconcile through the same "
                         "rule on heal")
    ap.add_argument("--chaos-partition-rounds", default=None,
                    metavar="START:END",
                    help="half-open round span the partition lasts, e.g. "
                         "'2:5' = rounds 2,3,4 (required with "
                         "--chaos-partition)")
    ap.add_argument("--chaos-churn-leave", action="append", default=None,
                    metavar="CLIENT:ROUND",
                    help="client CLIENT permanently leaves at round ROUND "
                         "(repeatable; the mesh never reshapes — the client "
                         "carries weight 0 from then on)")
    ap.add_argument("--chaos-churn-join", action="append", default=None,
                    metavar="CLIENT:ROUND",
                    help="client CLIENT joins late at round ROUND "
                         "(repeatable; absent — weight 0 — before it)")
    ap.add_argument("--chaos-flaky", default=None, metavar="CLIENTS",
                    help="comma-separated client ids that corrupt transport "
                         "in intermittent multi-round bursts — the "
                         "repeat-offender input reputation quarantine "
                         "exists for (see --reputation)")
    ap.add_argument("--chaos-flaky-burst", type=int, default=None,
                    metavar="N", help="rounds per flaky burst window "
                    "(default 3)")
    ap.add_argument("--chaos-flaky-on-prob", type=float, default=None,
                    metavar="P", help="probability each flaky window "
                    "actually bursts (default 0.5)")
    ap.add_argument("--chaos-wire", default=None, metavar="SPEC",
                    help="wire-fault lane for --runtime dist (RUNTIME.md "
                         "'Delivery contract'): comma list of K=V with K in "
                         "{drop,dup,reorder,delay,corrupt} (per-message "
                         "probabilities) plus optional delay-s / hold-s "
                         "(seconds), e.g. "
                         "'drop=0.2,dup=0.2,reorder=0.2,corrupt=0.05' — "
                         "seeded socket-level frame drop / duplication / "
                         "reorder-hold / delay-jitter / byte-corruption, "
                         "absorbed by the self-healing transport")
    ap.add_argument("--chaos-wire-rounds", default=None, metavar="START:END",
                    help="bound the wire lane to this half-open span of the "
                         "sender's local-round clock (default: every round)")
    ap.add_argument("--chaos-byz", default=None, metavar="PEERS",
                    help="byzantine lane for --runtime dist (ROBUSTNESS.md "
                         "§8): comma-separated ADVERSARIAL peer ids — each "
                         "rewrites its outbound updates above the wire "
                         "(scaled/sign-flipped/garbage payloads, stale "
                         "replays, digest forgeries, equivocation); caught "
                         "by the robust --aggregator rules, the ledger "
                         "refingerprint, and --reputation quarantine")
    ap.add_argument("--chaos-byz-behaviors", default=None, metavar="LIST",
                    help="comma subset of scale,sign_flip,garbage,replay,"
                         "digest_forge,equivocate (default: all)")
    ap.add_argument("--chaos-byz-prob", type=float, default=None,
                    metavar="P", help="per-(peer, round) probability an "
                    "adversarial peer acts (default 1.0)")
    ap.add_argument("--chaos-byz-scale", type=float, default=None,
                    metavar="S", help="payload perturbation magnitude for "
                    "the scale/garbage behaviors (default 25.0)")
    ap.add_argument("--chaos-byz-rounds", default=None, metavar="START:END",
                    help="bound the byzantine lane to this half-open span "
                         "of the adversary's local-round clock (default: "
                         "every round)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed of the chaos schedule (independent of --seed)")
    # peer-lifecycle reputation (bcfl_tpu.reputation, ROBUSTNESS.md §6)
    ap.add_argument("--reputation", action="store_true",
                    help="enable the peer-lifecycle state machine: EWMA "
                         "trust over per-round evidence (ledger-auth "
                         "failures, anomaly flags, corruption hits, "
                         "staleness) drives HEALTHY -> SUSPECT -> "
                         "QUARANTINED -> PROBATION; quarantined peers are "
                         "excluded for --reputation-quarantine-rounds and "
                         "readmitted at --reputation-probation-weight")
    ap.add_argument("--reputation-alpha", type=float, default=None,
                    metavar="A", help="EWMA trust update rate (default 0.4)")
    ap.add_argument("--reputation-suspect-below", type=float, default=None,
                    metavar="T", help="trust below T -> SUSPECT "
                    "(default 0.7)")
    ap.add_argument("--reputation-quarantine-below", type=float,
                    default=None, metavar="T",
                    help="trust below T -> QUARANTINED (default 0.4)")
    ap.add_argument("--reputation-quarantine-rounds", type=int, default=None,
                    metavar="N", help="rounds a quarantined peer sits out "
                    "(default 3)")
    ap.add_argument("--reputation-probation-rounds", type=int, default=None,
                    metavar="N", help="clean probation rounds before full "
                    "readmission (default 2)")
    ap.add_argument("--reputation-probation-weight", type=float,
                    default=None, metavar="W",
                    help="vote weight while on probation (default 0.5)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=None)
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="event-stream directory (bcfl_tpu.telemetry, "
                         "OBSERVABILITY.md). Default: dist runs stream "
                         "into their run dir, local runs emit nothing; "
                         "naming a dir enables streaming on both")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable event streaming everywhere (the "
                         "overhead-measurement setting)")
    ap.add_argument("--telemetry-sample", type=float, default=None,
                    metavar="P",
                    help="sampling rate in [0,1] for high-rate transport "
                         "events (per-attempt outcomes, chaos draws); "
                         "invariant-grade events are never sampled")
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. 'cpu' for the virtual "
                         "host mesh); same effect as the JAX_PLATFORMS "
                         "environment variable, and passed on to dist peers")
    args = ap.parse_args(argv)
    compile_cache()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    cfg = get_preset(args.preset, hf=args.hf)
    simple = {
        "clients": "num_clients", "rounds": "num_rounds", "model": "model",
        "dataset": "dataset", "mode": "mode", "sync": "sync", "task": "task",
        "seq_len": "seq_len", "batch_size": "batch_size",
        "lr": "learning_rate", "lora_rank": "lora_rank",
        "lora_ranks": "lora_ranks",
        "max_local_batches": "max_local_batches", "seed": "seed",
        "registry_size": "registry_size", "sample_clients": "sample_clients",
        "cohort_size": "cohort_size",
        "rounds_per_dispatch": "rounds_per_dispatch", "tp": "tp", "sp": "sp",
        "eval_every": "eval_every",
        "checkpoint_dir": "checkpoint_dir", "checkpoint_every": "checkpoint_every",
        "compute_dtype": "compute_dtype", "param_dtype": "param_dtype",
        "prng_impl": "prng_impl",
    }
    overrides = {}
    for arg_name, cfg_name in simple.items():
        v = getattr(args, arg_name)
        if v is not None:
            overrides[cfg_name] = v
    if args.lora_ranks is not None and args.lora_rank is None:
        # a per-client spec supersedes a preset's uniform rank (FedConfig
        # rejects setting both and re-canonicalizes lora_rank to max(spec));
        # an EXPLICIT --lora-rank alongside --lora-ranks still reaches
        # FedConfig and fails there with its clear set-one-not-both message
        overrides["lora_rank"] = 0
    if args.model is not None and cfg.hf_checkpoint is not None:
        # keep checkpoint/tokenizer consistent with the overridden architecture
        if args.model not in _HF:
            raise SystemExit(
                f"--model {args.model!r} has no HF checkpoint mapping; "
                f"under --hf use one of {sorted(_HF)}")
        overrides["hf_checkpoint"] = _HF[args.model]
        overrides["tokenizer"] = _HF[args.model]
    if args.use_flash is not None:
        overrides["use_flash"] = args.use_flash == "on"
    if args.remat:
        overrides["remat"] = True
    if args.donate:
        overrides["donate"] = True
    if args.faithful:
        overrides["faithful"] = True
    if args.anomaly_filter is not None or args.gossip_steps is not None:
        topo_kw = {}
        if args.anomaly_filter is not None:
            topo_kw["anomaly_filter"] = (None if args.anomaly_filter == "none"
                                         else args.anomaly_filter)
        if args.gossip_steps is not None:
            topo_kw["gossip_steps"] = args.gossip_steps
        overrides["topology"] = dataclasses.replace(cfg.topology, **topo_kw)
    if args.ledger:
        overrides["ledger"] = dataclasses.replace(cfg.ledger, enabled=True)
    if args.pod:
        overrides["pod"] = True
    if args.aggregator is not None:
        overrides["aggregator"] = args.aggregator
    if args.aggregator_trim is not None:
        overrides["aggregator_trim"] = args.aggregator_trim
    if (args.compress is not None or args.compress_topk is not None
            or args.compress_chunk is not None or args.no_compress_ef):
        comp_kw = {"kind": args.compress if args.compress is not None
                   else cfg.compression.kind}
        if comp_kw["kind"] == "none" and args.compress != "none":
            # a codec sub-flag with no codec selected would silently ship
            # full-precision trees under a compression-tweak label — the
            # same fail-loudly stance as the bench's rejections
            raise SystemExit(
                "--compress-topk/--compress-chunk/--no-compress-ef have no "
                "effect without a codec: add --compress "
                "{int8,topk,int8+topk}")
        if args.compress_topk is not None:
            comp_kw["topk_frac"] = args.compress_topk
        if args.compress_chunk is not None:
            comp_kw["chunk"] = args.compress_chunk
        if args.no_compress_ef:
            comp_kw["error_feedback"] = False
        overrides["compression"] = dataclasses.replace(
            cfg.compression, **comp_kw)
    def _pair_schedule(entries, flag):
        if not entries:
            return None
        out = []
        for s in entries:
            try:
                c, r = s.split(":")
                out.append((int(c), int(r)))
            except ValueError:
                raise SystemExit(f"{flag} {s!r}: expected CLIENT:ROUND")
        return tuple(out)

    chaos_flags = (
        args.chaos_dropout is not None or args.chaos_straggler is not None
        or args.chaos_corrupt is not None
        or args.chaos_crash_round is not None
        or args.chaos_partition is not None
        or args.chaos_churn_leave or args.chaos_churn_join
        or args.chaos_flaky is not None or args.chaos_wire is not None
        or args.chaos_byz is not None
        # byz sub-flags enter the gate so "--chaos-byz-prob without
        # --chaos-byz" reaches the fail-loudly check below instead of
        # being silently ignored
        or args.chaos_byz_behaviors is not None
        or args.chaos_byz_prob is not None
        or args.chaos_byz_scale is not None
        or args.chaos_byz_rounds is not None)
    if chaos_flags:
        from bcfl_tpu.faults import FaultPlan

        plan_kw = dict(
            seed=args.chaos_seed,
            dropout_prob=args.chaos_dropout or 0.0,
            straggler_prob=args.chaos_straggler or 0.0,
            straggler_delay_s=args.chaos_straggler_delay,
            corrupt_prob=args.chaos_corrupt or 0.0,
            crash_at_round=args.chaos_crash_round,
            churn_leave=_pair_schedule(args.chaos_churn_leave,
                                       "--chaos-churn-leave"),
            churn_join=_pair_schedule(args.chaos_churn_join,
                                      "--chaos-churn-join"),
        )
        if args.chaos_partition is not None:
            if args.chaos_partition_rounds is None:
                raise SystemExit("--chaos-partition needs "
                                 "--chaos-partition-rounds START:END")
            try:
                lo, hi = (int(x) for x in
                          args.chaos_partition_rounds.split(":"))
            except ValueError:
                raise SystemExit(
                    f"--chaos-partition-rounds "
                    f"{args.chaos_partition_rounds!r}: expected START:END")
            if hi <= lo:
                # an empty span would make the partition silently never
                # fire (FaultPlan rejects it too; fail in CLI style here)
                raise SystemExit(
                    f"--chaos-partition-rounds "
                    f"{args.chaos_partition_rounds!r}: empty span "
                    "(END must be > START; the span is half-open)")
            plan_kw["partition_rounds"] = tuple(range(lo, hi))
            spec = args.chaos_partition
            if "/" in spec or "," in spec:
                try:
                    plan_kw["partition_groups"] = tuple(
                        tuple(int(c) for c in g.split(","))
                        for g in spec.split("/") if g)
                except ValueError:
                    raise SystemExit(f"--chaos-partition {spec!r}: expected "
                                     "groups like 0,1/2,3 or an integer N")
            else:
                try:
                    plan_kw["partition_count"] = int(spec)
                except ValueError:
                    raise SystemExit(f"--chaos-partition {spec!r}: expected "
                                     "groups like 0,1/2,3 or an integer N")
        if args.chaos_flaky is not None:
            try:
                plan_kw["flaky_clients"] = tuple(
                    int(c) for c in args.chaos_flaky.split(","))
            except ValueError:
                raise SystemExit(f"--chaos-flaky {args.chaos_flaky!r}: "
                                 "expected comma-separated client ids")
            if args.chaos_flaky_burst is not None:
                plan_kw["flaky_burst_len"] = args.chaos_flaky_burst
            if args.chaos_flaky_on_prob is not None:
                plan_kw["flaky_on_prob"] = args.chaos_flaky_on_prob
        if args.chaos_wire is not None:
            wire_keys = {"drop": "wire_drop_prob", "dup": "wire_dup_prob",
                         "reorder": "wire_reorder_prob",
                         "delay": "wire_delay_prob",
                         "corrupt": "wire_corrupt_prob",
                         "delay-s": "wire_delay_s",
                         "hold-s": "wire_reorder_hold_s"}
            for part in args.chaos_wire.split(","):
                try:
                    k, v = part.split("=")
                    plan_kw[wire_keys[k.strip()]] = float(v)
                except (ValueError, KeyError):
                    raise SystemExit(
                        f"--chaos-wire {part!r}: expected K=V with K in "
                        f"{sorted(wire_keys)}")
            if not any(plan_kw.get(wire_keys[k])
                       for k in ("drop", "dup", "reorder", "delay",
                                 "corrupt")):
                # delay-s/hold-s alone arm nothing: the lane fires off
                # probabilities — fail loudly instead of silently
                # injecting zero faults under a chaos-looking flag
                raise SystemExit(
                    f"--chaos-wire {args.chaos_wire!r} sets no "
                    "probability: add at least one of "
                    "drop/dup/reorder/delay/corrupt > 0")
        if args.chaos_byz is not None:
            try:
                plan_kw["byz_peers"] = tuple(
                    int(p) for p in args.chaos_byz.split(","))
            except ValueError:
                raise SystemExit(f"--chaos-byz {args.chaos_byz!r}: "
                                 "expected comma-separated peer ids")
            if args.chaos_byz_behaviors is not None:
                plan_kw["byz_behaviors"] = tuple(
                    b.strip() for b in args.chaos_byz_behaviors.split(",")
                    if b.strip())
            if args.chaos_byz_prob is not None:
                plan_kw["byz_prob"] = args.chaos_byz_prob
            if args.chaos_byz_scale is not None:
                plan_kw["byz_scale"] = args.chaos_byz_scale
            if args.chaos_byz_rounds is not None:
                try:
                    lo, hi = (int(x) for x in
                              args.chaos_byz_rounds.split(":"))
                except ValueError:
                    raise SystemExit(f"--chaos-byz-rounds "
                                     f"{args.chaos_byz_rounds!r}: "
                                     "expected START:END")
                if hi <= lo:
                    raise SystemExit(f"--chaos-byz-rounds "
                                     f"{args.chaos_byz_rounds!r}: empty "
                                     "span (END must be > START; the span "
                                     "is half-open)")
                plan_kw["byz_rounds"] = tuple(range(lo, hi))
        elif (args.chaos_byz_behaviors is not None
              or args.chaos_byz_prob is not None
              or args.chaos_byz_scale is not None
              or args.chaos_byz_rounds is not None):
            # same fail-loudly stance as the codec sub-flags
            raise SystemExit("--chaos-byz-* tuning flags have no effect "
                             "without --chaos-byz PEERS")
        if args.chaos_wire_rounds is not None:
            if args.chaos_wire is None:
                raise SystemExit("--chaos-wire-rounds has no effect "
                                 "without --chaos-wire")
            try:
                lo, hi = (int(x) for x in args.chaos_wire_rounds.split(":"))
            except ValueError:
                raise SystemExit(f"--chaos-wire-rounds "
                                 f"{args.chaos_wire_rounds!r}: expected "
                                 "START:END")
            if hi <= lo:
                raise SystemExit(f"--chaos-wire-rounds "
                                 f"{args.chaos_wire_rounds!r}: empty span "
                                 "(END must be > START; the span is "
                                 "half-open)")
            plan_kw["wire_rounds"] = tuple(range(lo, hi))
        overrides["faults"] = FaultPlan(**plan_kw)
    rep_tweaks = {
        "ewma_alpha": args.reputation_alpha,
        "suspect_below": args.reputation_suspect_below,
        "quarantine_below": args.reputation_quarantine_below,
        "quarantine_rounds": args.reputation_quarantine_rounds,
        "probation_rounds": args.reputation_probation_rounds,
        "probation_weight": args.reputation_probation_weight,
    }
    rep_tweaks = {k: v for k, v in rep_tweaks.items() if v is not None}
    if rep_tweaks and not args.reputation:
        # same fail-loudly stance as the codec sub-flags: a tuning flag
        # with the subsystem off would silently change nothing
        raise SystemExit("--reputation-* tuning flags have no effect "
                         "without --reputation")
    if args.reputation:
        overrides["reputation"] = dataclasses.replace(
            cfg.reputation, enabled=True, **rep_tweaks)
    if args.no_telemetry and args.telemetry_dir is not None:
        raise SystemExit("--no-telemetry contradicts --telemetry-dir")
    if args.no_telemetry:
        overrides["telemetry_dir"] = "off"
    elif args.telemetry_dir is not None:
        overrides["telemetry_dir"] = args.telemetry_dir
    if args.telemetry_sample is not None:
        overrides["telemetry_sample"] = args.telemetry_sample
    if args.peers is not None and args.runtime != "dist":
        raise SystemExit("--peers only applies to --runtime dist")
    if args.dist_quorum is not None and args.runtime != "dist":
        raise SystemExit("--dist-quorum only applies to --runtime dist")
    if args.dist_buffer is not None and args.runtime != "dist":
        raise SystemExit("--dist-buffer only applies to --runtime dist")
    if args.no_dist_pipeline and args.runtime != "dist":
        raise SystemExit("--no-dist-pipeline only applies to "
                         "--runtime dist")
    if args.dist_pipeline_depth is not None and args.runtime != "dist":
        raise SystemExit("--dist-pipeline-depth only applies to "
                         "--runtime dist")
    if args.runtime is not None:
        # runtime joins the ONE combined replace below: applying sync/mode/
        # faults first with runtime still "local" would run the local-
        # runtime validation on an intermediate config and reject legal
        # dist combinations (e.g. dist + --chaos-partition) with the wrong
        # error. Only fields the user did NOT set are defaulted — explicit
        # conflicting overrides still fail in the capability table.
        overrides["runtime"] = args.runtime
        if args.runtime == "dist":
            overrides.setdefault("sync", "async")
            overrides.setdefault("mode", "server")
            overrides.setdefault("eval_every", 0)
            dist_kw = dict(peers=args.peers or cfg.dist.peers,
                           peer_deadline_s=args.dist_deadline)
            if args.dist_quorum is not None:
                dist_kw["quorum_frac"] = args.dist_quorum
            if args.dist_buffer is not None:
                dist_kw["buffer"] = args.dist_buffer
            if args.no_dist_pipeline:
                dist_kw["pipeline"] = False
            if args.dist_pipeline_depth is not None:
                dist_kw["pipeline_depth"] = args.dist_pipeline_depth
            overrides["dist"] = dataclasses.replace(cfg.dist, **dist_kw)
    cfg = cfg.replace(**overrides)

    fused_tamper = None
    if args.fused_tamper:
        import numpy as np

        if not cfg.ledger.enabled:
            # without the ledger the engine runs the non-fp programs, which
            # have no transport stage — the corruption would be silently
            # dropped and the demo would pass vacuously
            raise SystemExit("--fused-tamper needs --ledger (the transport-"
                             "verification stage lives in the ledger's "
                             "fused fingerprint programs)")
        spec = {}
        for s in args.fused_tamper:
            try:
                r, c, scale = s.split(":")
                r, c, scale = int(r), int(c), float(scale)
            except ValueError:
                raise SystemExit(
                    f"--fused-tamper {s!r}: expected ROUND:CLIENT:SCALE")
            if not 0 <= c < cfg.num_clients:
                raise SystemExit(
                    f"--fused-tamper {s!r}: client out of range "
                    f"[0, {cfg.num_clients})")
            if not 0 <= r < cfg.num_rounds:
                # rounds are 0-indexed; a never-reached round would make the
                # demo pass vacuously (no corruption, all auth 1.0)
                raise SystemExit(
                    f"--fused-tamper {s!r}: round out of range "
                    f"[0, {cfg.num_rounds}) (rounds are 0-indexed)")
            spec.setdefault(r, []).append((c, scale))

        def fused_tamper(rnd, _spec=spec, _n=cfg.num_clients):
            rows = _spec.get(rnd)
            if not rows:
                return None
            row = np.zeros((_n,), np.float32)
            for c, scale in rows:
                row[c] = scale
            return row

    if cfg.runtime == "dist":
        if args.sweep or fused_tamper is not None or args.resume:
            raise SystemExit("--runtime dist composes with neither --sweep "
                             "nor --fused-tamper nor --resume (peer "
                             "crash/rejoin is driven by "
                             "scripts/dist_async.py --kill-peer)")
        import json as _json
        import os as _os

        from bcfl_tpu.dist.harness import run_dist

        run_dir = _os.path.join("/tmp", f"bcfl_dist_cli_{_os.getpid()}")
        result = run_dist(cfg, run_dir, platform=args.platform)
        summary = {
            "ok": result["ok"],
            "supervisor_backend_initialized":
                result["supervisor_backend_initialized"],
            "process_count": result["process_count"],
            "returncodes": result["returncodes"],
            "final_versions": {p: r.get("final_version")
                               for p, r in result["reports"].items()},
            "final_eval": result["reports"].get(0, {}).get("final_eval"),
            "run_dir": run_dir,
        }
        if result["event_streams"]:
            # collate the run's event streams right here: the timeline
            # block + invariant verdicts are the run's observability
            # surface (re-query any time: `bcfl-tpu trace <run_dir>`).
            # Collate the paths the harness found — with --telemetry-dir
            # the streams live outside run_dir
            from bcfl_tpu.telemetry import collate

            col = collate(result["event_streams"])
            summary["event_streams"] = result["event_streams"]
            summary["timeline"] = col["timeline"]
            summary["invariants"] = col["invariants"]
            summary["invariants_ok"] = col["ok"]
        print(_json.dumps(summary, indent=2), flush=True)
        if not result["ok"] or not summary.get("invariants_ok", True):
            raise SystemExit(1)
    elif args.sweep:
        if fused_tamper is not None:
            raise SystemExit("--fused-tamper does not compose with --sweep "
                             "(client indices change per sweep point)")
        run_sweep(cfg, resume=args.resume)
    else:
        run(cfg, resume=args.resume, fused_tamper=fused_tamper)


if __name__ == "__main__":
    main()
