"""Headline benchmark: BERT-base federated fine-tune throughput per chip.

Times the on-device multi-round federated program (``server_rounds``: R whole
FedAvg rounds — every client's AdamW fine-tune + the psum collective —
scanned inside ONE XLA dispatch), in one process, in one attempt, and prints
ONE JSON line {"metric", "value", "unit", "vs_baseline", ...extras}. Every
line names the platform, ``device_kind`` and device count it ran on. A
failure is an exception and a non-zero exit, never a zero-valued line.

The backend must be a TPU. ``BCFL_BENCH_PLATFORM=cpu`` asks for the CPU
explicitly; such a row is stamped ``plumbing_only`` and carries no device
metric. A ``device_kind`` missing from the peak table
(``bcfl_tpu.core.hostenv.PEAK_BF16_FLOPS``) is an error, and so is a result
whose implied FLOP rate exceeds the device's peak (a timing that did not
wait for the device).

Baseline derivation (BASELINE.md): the reference's serverless IMDB run —
10 clients x 20 rounds x 100 samples, 40 min wall (All_graphs_IMDB_dataset
.ipynb cell 15, 10-worker serverless latency) — is 20_000 samples / 2_400 s
= 8.33 samples/sec on its CPU host. ``vs_baseline`` is the speedup over that.

MFU: training FLOPs ~= 6 * params * tokens (fwd 2PD + bwd 4PD) over the
chip's bf16 peak; reported for full fine-tune rows only (with LoRA the
frozen base needs no weight-gradient pass, so the formula overstates).

Env knobs: BCFL_BENCH_TRACE=<dir> captures a jax.profiler trace of the timed
block; BCFL_BENCH_ROUNDS/STEPS/ITERS override the shape;
BCFL_BENCH_MODE=serverless times the fused gossip program (gossip_rounds —
per-client params held in HBM across the block) instead of server FedAvg.
BCFL_BENCH_MODE=dist times the REAL multi-process async P2P runtime
(RUNTIME.md): BCFL_BENCH_PEERS peer OS processes co-train to a target
version count and the row reports end-to-end federated throughput
(samples/sec across the fleet, from the per-peer reports). One process
owns a chip, so in dist mode THIS process never initializes a jax backend
while peers live: the harness pins each peer to a chip of its own and the
device stamp comes from the peers' reports. Dist knobs: BCFL_BENCH_PEERS
(default 3), BCFL_BENCH_DIST_ROUNDS (target versions, default 6),
BCFL_BENCH_DIST_MODEL (default tiny-bert — peers each compile their own
engine), BCFL_BENCH_DIST_PIPELINE=0 disables the comms/compute overlap
pipeline (the A/B axis scripts/wire_perf.py sweeps), and
BCFL_BENCH_DIST_DISPATCH={leader,gossip} selects the execution mode
(RUNTIME.md "Gossip dispatch") — the gossip row lands under its own
metric name (dist_fed_gossip_samples_per_sec) so the leaderless
throughput sits NEXT to the leadered one instead of overwriting it.
BCFL_BENCH_COMPRESS={none,int8,topk,int8+topk} compiles the update-exchange
codec (COMPRESSION.md) into the timed round program and adds bytes-on-wire
fields to the JSON line.
BCFL_BENCH_CODEC_IMPL={auto,xla,pallas} selects the codec kernel impl
(PERF.md "Custom kernels"; payloads byte-identical under every value) and
stamps codec_impl plus a codec_encode_ms encode-only sub-timing.
BCFL_BENCH_LORA_RANK=<r> (r > 0) makes the LoRA adapter the trainable /
exchanged tree (COMPRESSION.md "Adapter exchange"): the timed program
fine-tunes rank-r adapters over the frozen base, and every JSON line —
local and dist mode — stamps lora_rank, the adapter param count, and the
per-round adapter payload bytes (through the configured codec, so the
axis composes with BCFL_BENCH_COMPRESS).
BCFL_BENCH_PRNG=<impl> sets the default PRNG impl (e.g. rbg).
BCFL_BENCH_TELEMETRY_DIR=<dir> streams run/phase events there.
"""

from __future__ import annotations

import json
import os
import sys
import time

BASELINE_SAMPLES_PER_SEC = 20_000 / 2_400.0  # 8.33, see docstring

BATCH = 32  # reference batch size (server_IID_IMDB.py:96-99)
SEQ = 128
ROUNDS = int(os.environ.get("BCFL_BENCH_ROUNDS", "32"))  # fed rounds / dispatch
STEPS = int(os.environ.get("BCFL_BENCH_STEPS", "8"))  # local batches / round
ITERS = int(os.environ.get("BCFL_BENCH_ITERS", "2"))  # timed dispatches
MODE = os.environ.get("BCFL_BENCH_MODE", "server")  # server | serverless
# dist execution mode: "leader" (per-component FedBuff funnel) or
# "gossip" (leaderless epidemic dispatch); validated in main() like MODE
DIST_DISPATCH = os.environ.get("BCFL_BENCH_DIST_DISPATCH", "leader")
# update-exchange codec compiled into the timed program (COMPRESSION.md)
COMPRESS = os.environ.get("BCFL_BENCH_COMPRESS", "none")
# codec kernel impl axis (PERF.md "Custom kernels"): auto | xla | pallas,
# compiled into the timed program via CompressionConfig.kernel_impl and
# stamped as codec_impl
CODEC_IMPL = os.environ.get("BCFL_BENCH_CODEC_IMPL", "auto")
# adapter-exchange axis: rank 0 = full-model fine-tune (the default row)
LORA_RANK_RAW = os.environ.get("BCFL_BENCH_LORA_RANK", "0")
# opt-in event telemetry (OBSERVABILITY.md): a directory here makes the
# bench stream run/phase events (bcfl_tpu.telemetry) into
# events_bench.jsonl there, and every JSON line stamps `event_stream`
# with the stream path — or "disabled", so a line's observability story
# is explicit either way. Off hot path: nothing is emitted inside the
# timed loop.
TELEMETRY_DIR = os.environ.get("BCFL_BENCH_TELEMETRY_DIR")
# the one way onto a non-TPU backend: an explicit request
PLATFORM = os.environ.get("BCFL_BENCH_PLATFORM")


def _emit(obj):
    print(json.dumps(obj), flush=True)


def _metric_name():
    if MODE == "dist":
        # one metric per dispatch mode: the leaderless row must not
        # overwrite the leadered baseline it is compared against
        if DIST_DISPATCH == "gossip":
            return "dist_fed_gossip_samples_per_sec"
        return "dist_fed_async_samples_per_sec"
    tag = "serverless_" if MODE == "serverless" else ""
    return f"bert-base_fed_{tag}finetune_samples_per_sec_per_chip"


def _compress_cfg():
    """CompressionConfig for BCFL_BENCH_COMPRESS, or None at 'none'."""
    if COMPRESS == "none":
        return None
    from bcfl_tpu.compression import CompressionConfig

    return CompressionConfig(kind=COMPRESS, kernel_impl=CODEC_IMPL)


def _codec_encode_ms(comp, shapes, num_clients: int) -> float:
    """Sub-timing of the codec encode alone — the per-round hot loop the
    Pallas kernels target (ops/pallas_codec.py). One jitted ``encode_tree``
    over a [C, ...] stacked random delta shaped like the exchanged tree
    (``shapes``: the round program has donated the tree itself away),
    warmed outside the timed window."""
    import jax
    import jax.numpy as jnp

    from bcfl_tpu.compression import codec_key, encode_tree
    from bcfl_tpu.core.fence import fence

    leaves, treedef = jax.tree.flatten(shapes)

    def make(key):
        return treedef.unflatten([
            jax.random.normal(k, (num_clients,) + leaf.shape, jnp.float32)
            for k, leaf in zip(jax.random.split(key, len(leaves)), leaves)])

    delta = jax.jit(make)(jax.random.key(7))
    keys = jax.random.split(jax.random.key(11), num_clients)
    enc = jax.jit(lambda d, kk: encode_tree(comp, d, codec_key(kk)))
    fence(enc(delta, keys))  # compile + one warm dispatch
    iters = 3
    t0 = time.perf_counter()
    payload = None
    for _ in range(iters):
        payload = enc(delta, keys)
    fence(payload)
    return (time.perf_counter() - t0) / iters * 1000.0


def _event_stream() -> str:
    """The JSON-line `event_stream` stamp: the telemetry stream path when
    BCFL_BENCH_TELEMETRY_DIR is set, else the explicit "disabled"."""
    return (os.path.join(TELEMETRY_DIR, "events_bench.jsonl")
            if TELEMETRY_DIR else "disabled")


def _require_platform(platform: str) -> bool:
    """Refuse any backend but a TPU unless the CPU was asked for by name;
    returns whether the row is plumbing only."""
    if platform == "tpu":
        return False
    if PLATFORM == platform:
        return True
    raise SystemExit(
        f"bench: the backend is {platform!r}, not a TPU. A measurement needs "
        "the chip; set BCFL_BENCH_PLATFORM=cpu to ask for a plumbing-only "
        "CPU row")


def _dist_bench(lora_rank: int):
    """The runtime='dist' BENCH row: a real multi-peer federation timed end
    to end (spawn -> target version count -> reports), reported as fleet
    samples/sec. This process initializes no jax backend before the peers
    have exited: on a TPU each peer owns one chip."""
    import shutil
    import tempfile

    from bcfl_tpu.compression import CompressionConfig
    from bcfl_tpu.config import DistConfig, FedConfig, LedgerConfig, \
        PartitionConfig
    from bcfl_tpu.dist.harness import resolved_platform, run_dist

    early = resolved_platform(PLATFORM)
    if early is not None:
        _require_platform(early)
    peers = int(os.environ.get("BCFL_BENCH_PEERS", "3"))
    versions = int(os.environ.get("BCFL_BENCH_DIST_ROUNDS", "6"))
    model = os.environ.get("BCFL_BENCH_DIST_MODEL", "tiny-bert")
    clients_per_peer = int(os.environ.get("BCFL_BENCH_DIST_CLIENTS", "2"))
    pipeline = os.environ.get("BCFL_BENCH_DIST_PIPELINE", "1") != "0"
    batch, seq, local_batches = 4, 16, 2
    deadline = float(os.environ.get("BCFL_BENCH_DIST_DEADLINE_S", "420"))
    cfg = FedConfig(
        name="bench_dist", runtime="dist", mode="server", sync="async",
        model=model, dataset="synthetic",
        num_clients=peers * clients_per_peer, num_rounds=versions,
        seq_len=seq, batch_size=batch, max_local_batches=local_batches,
        eval_every=0, seed=42, lora_rank=lora_rank,
        partition=PartitionConfig(kind="iid", iid_samples=8),
        ledger=LedgerConfig(enabled=True),
        compression=CompressionConfig(kind=COMPRESS,
                                      kernel_impl=CODEC_IMPL),
        # dispatch="gossip" rides the same knobs; the fanout is clamped
        # below the fleet size (the config rejects fanout >= peers)
        dist=DistConfig(peers=peers, peer_deadline_s=deadline,
                        pipeline=pipeline, dispatch=DIST_DISPATCH,
                        gossip_fanout=max(1, min(2, peers - 1))),
    )
    run_dir = tempfile.mkdtemp(prefix="bcfl_bench_dist_")
    t0 = time.perf_counter()
    result = run_dist(cfg, run_dir, deadline_s=deadline + 60.0,
                      platform=PLATFORM)
    dt = time.perf_counter() - t0
    reports = result["reports"]
    if not result["ok"] or len(reports) != peers:
        raise RuntimeError(
            f"dist bench run failed: rcs={result['returncodes']} "
            f"reports={sorted(reports)} (logs under {run_dir})")
    if result["supervisor_backend_initialized"]:
        raise RuntimeError("the dist bench parent initialized a jax backend "
                           "while its peers needed the chips")
    devices = [r["device"] for _, r in sorted(reports.items())]
    plumbing = _require_platform(devices[0]["platform"])
    # fleet throughput: every peer's local rounds each fine-tune its
    # whole client slice for local_batches batches
    total_rounds = sum(r["local_rounds"] for r in reports.values())
    samples = total_rounds * clients_per_peer * local_batches * batch
    streams = result.get("event_streams") or []
    keep = os.environ.get("BCFL_BENCH_DIST_KEEP_RUN") == "1"
    out = {
        "metric": _metric_name(),
        "value": round(samples / dt, 2),
        "unit": "samples/sec (fleet)",
        "vs_baseline": round(samples / dt / BASELINE_SAMPLES_PER_SEC, 2),
        "platform": devices[0]["platform"],
        "device": devices[0]["device_kind"],
        "device_count": len(devices),
        "peer_chips": [d["visible_chip"] for d in devices],
        "plumbing_only": plumbing,
        # the peers streamed telemetry into the run dir; the path only
        # outlives this row under KEEP_RUN (else it is cleaned up with
        # the run and stamped as such — never a dangling path)
        "event_stream": (os.path.dirname(streams[0]) if streams and keep
                         else ("discarded (BCFL_BENCH_DIST_KEEP_RUN=1 "
                               "retains)" if streams else "disabled")),
        "peers": peers,
        "model": model,
        "pipeline": pipeline,
        "dispatch": DIST_DISPATCH,
        "compress": COMPRESS,
        "codec_impl": CODEC_IMPL,
        "target_versions": versions,
        "final_versions": {str(p): r.get("final_version")
                           for p, r in reports.items()},
        "local_rounds_total": int(total_rounds),
        "wall_s": round(dt, 2),
    }
    if lora_rank > 0:
        # adapter accounting, after the peers have exited: eval_shape traces
        # init + adapter construction on abstract arrays, and
        # payload_nbytes is metadata-only
        import jax
        import jax.numpy as jnp

        from bcfl_tpu.compression import payload_nbytes
        from bcfl_tpu.models import build, lora as lora_lib, lora_targets

        m = build(model, num_labels=2)
        ids = jnp.ones((2, seq), jnp.int32)
        pshapes = jax.eval_shape(
            lambda k: m.init(k, ids, ids)["params"], jax.random.key(0))
        ashapes = jax.eval_shape(
            lambda p: lora_lib.init_lora(jax.random.key(1), p, lora_rank,
                                         targets=lora_targets(model)),
            pshapes)
        out["lora_rank"] = lora_rank
        out["adapter_params"] = int(sum(
            x.size for x in jax.tree.leaves(ashapes)))
        out["bytes_on_wire_per_round"] = int(
            payload_nbytes(_compress_cfg(), ashapes) * cfg.num_clients)
    if keep:
        out["run_dir"] = run_dir
    else:
        shutil.rmtree(run_dir, ignore_errors=True)
    return out


def main():
    from bcfl_tpu.compression import KERNEL_IMPLS, KINDS
    from bcfl_tpu.core.hostenv import compile_cache, device_peak_flops

    if MODE not in ("server", "serverless", "dist"):
        # fail fast: a typo'd mode silently timing the wrong program would
        # be a chip run of worthless evidence
        raise SystemExit(f"unknown BCFL_BENCH_MODE {MODE!r}; expected "
                         "'server', 'serverless', or 'dist'")
    if COMPRESS not in KINDS:
        raise SystemExit(f"unknown BCFL_BENCH_COMPRESS {COMPRESS!r}; "
                         f"expected one of {KINDS}")
    if CODEC_IMPL not in KERNEL_IMPLS:
        raise SystemExit(f"unknown BCFL_BENCH_CODEC_IMPL {CODEC_IMPL!r}; "
                         f"expected one of {KERNEL_IMPLS}")
    if DIST_DISPATCH not in ("leader", "gossip"):
        raise SystemExit("unknown BCFL_BENCH_DIST_DISPATCH "
                         f"{DIST_DISPATCH!r}; expected 'leader' or 'gossip'")
    try:
        lora_rank = int(LORA_RANK_RAW or "0")
        if lora_rank < 0:
            raise ValueError
    except ValueError:
        raise SystemExit(f"bad BCFL_BENCH_LORA_RANK {LORA_RANK_RAW!r}; "
                         "expected a non-negative integer")
    compile_cache()
    if MODE == "dist":
        _emit(_dist_bench(lora_rank))
        return

    import jax

    if PLATFORM:
        jax.config.update("jax_platforms", PLATFORM)
    # opt-in PRNG impl (e.g. BCFL_BENCH_PRNG=rbg): dropout RNG is +38% of
    # step time under threefry in the earlier recording (PERF.md).
    # Deliberately NOT the default — the row stays on the product's
    # default stream
    prng = os.environ.get("BCFL_BENCH_PRNG")
    if prng:
        jax.config.update("jax_default_prng_impl", prng)
    import jax.numpy as jnp

    from bcfl_tpu.core.fence import fence
    from bcfl_tpu.core.mesh import client_mesh
    from bcfl_tpu.fed.client_step import build_programs
    from bcfl_tpu.fed.synthetic import synthetic_round_inputs
    from bcfl_tpu.models import build

    devices = jax.devices()
    n_dev = len(devices)
    platform, kind = devices[0].platform, devices[0].device_kind
    plumbing = _require_platform(platform)
    peak = None if plumbing else device_peak_flops(kind)

    if TELEMETRY_DIR:
        from bcfl_tpu import telemetry

        telemetry.install(telemetry.EventWriter(
            _event_stream(), peer=None, run="bench"))
        telemetry.emit("run.start", role="bench", mode=MODE,
                       rounds=ROUNDS, steps=STEPS, iters=ITERS)

    num_clients = n_dev  # 1 client per chip (BASELINE.json north star)
    mesh = client_mesh(num_clients)
    model = build("bert-base", num_labels=2)

    ids0 = jnp.ones((2, SEQ), jnp.int32)
    # jitted init: unjitted flax init dispatches hundreds of host ops
    params = jax.jit(
        lambda k: model.init(k, ids0, ids0)["params"])(jax.random.key(0))
    # place params in the round program's steady-state (replicated)
    # sharding BEFORE the first call: a single-device-committed input
    # would compile once for that layout and then AGAIN when the chained
    # carry comes back with the program's out_shardings — and that second
    # compile lands inside the timed loop (results/dispatch_bisect.json)
    params = jax.device_put(params, mesh.replicated())
    n_params = sum(x.size for x in jax.tree.leaves(params))
    comp = _compress_cfg()
    progs = build_programs(model, mesh, donate=True, compression=comp)

    # adapter-exchange axis: the adapter tree becomes the trainable /
    # exchanged carry and the full params become the frozen base (arg 1
    # of every round program — never donated, so one replicated copy
    # serves the whole block)
    frozen = None
    trainable0 = params
    adapter_params = None
    if lora_rank > 0:
        from bcfl_tpu.models import lora as lora_lib, lora_targets

        trainable0 = jax.jit(lambda p: lora_lib.init_lora(
            jax.random.key(1), p, lora_rank,
            targets=lora_targets("bert-base")))(params)
        trainable0 = jax.device_put(trainable0, mesh.replicated())
        frozen = params
        adapter_params = sum(x.size for x in jax.tree.leaves(trainable0))
    # shapes only: the donating round program deletes trainable0's buffers
    trainable_shapes = jax.eval_shape(lambda t: t, trainable0)

    batches, weights, rngs = synthetic_round_inputs(
        mesh, steps=STEPS, batch=BATCH, seq=SEQ, vocab_size=30_000)
    # stack a round axis: [R, C, ...] (same data every round — this is a
    # throughput bench, not a learning run)
    rbatches = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (ROUNDS,) + x.shape), batches)
    rweights = jnp.broadcast_to(weights[None], (ROUNDS,) + weights.shape)
    rrngs = jnp.broadcast_to(rngs[None], (ROUNDS,) + rngs.shape)

    # error-feedback residual of the compressed carry, built BEFORE the
    # first donating call hands trainable0 to the round program
    ef = progs.ef_init(trainable0) if comp is not None else None
    if MODE == "serverless":
        # per-client stacked params carried across fused gossip rounds
        carry = jax.jit(
            lambda p: jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (num_clients,) + x.shape), p),
            out_shardings=mesh.client_sharding())(trainable0)
        run_block = lambda c: progs.gossip_rounds(  # noqa: E731
            c, frozen, rbatches, rweights, rrngs)[0]
    else:
        carry = trainable0
        run_block = lambda c: progs.server_rounds(  # noqa: E731
            c, frozen, rbatches, rweights, rrngs)[0]
    if comp is not None:
        # compressed round programs carry (params, EF residual); the
        # run_block's [0] then chains the whole tuple. The residual
        # lives over the TRAINABLE tree — adapter-shaped under LoRA
        carry = (carry, ef)

    # compile + TWO warmup dispatches: even with the input pre-placed, any
    # residual input-sharding/layout drift between call 1 and call 2 (e.g.
    # donated buffers) must trigger its recompile HERE, not inside the
    # timed loop
    t0 = time.perf_counter()
    carry = run_block(carry)
    fence(carry)
    compile_s = time.perf_counter() - t0
    carry = run_block(carry)
    fence(carry)

    trace_dir = os.environ.get("BCFL_BENCH_TRACE")
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        carry = run_block(carry)
    fence(carry)
    dt = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()

    if TELEMETRY_DIR:
        from bcfl_tpu import telemetry

        # one span event for the whole timed block — emitted AFTER the
        # completion fence, so nothing rides inside the measurement
        telemetry.emit("phase", name="bench_measure", wall_s=dt,
                       iters=ITERS)
        telemetry.emit("run.end", status="ok")
        telemetry.uninstall()
    samples = ITERS * ROUNDS * num_clients * STEPS * BATCH
    sps_chip = samples / dt / n_dev
    flops = 6.0 * n_params * samples * SEQ
    out = {
        "metric": _metric_name(),
        "value": round(sps_chip, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps_chip / BASELINE_SAMPLES_PER_SEC, 2),
        "platform": platform,
        "device": kind,
        "device_count": n_dev,
        "plumbing_only": plumbing,
        "event_stream": _event_stream(),
        "params_m": round(n_params / 1e6, 1),
        "steps_per_dispatch": ROUNDS * STEPS,
        "first_dispatch_s": round(compile_s, 2),
        "wall_s": round(dt, 2),
    }
    if prng:
        out["prng"] = prng
    if (comp is not None or "BCFL_BENCH_COMPRESS" in os.environ
            or lora_rank > 0):
        # bytes-on-wire axis (COMPRESSION.md): one shipped update per
        # client per round, raw vs through the codec (an explicit
        # compress=none run still records its raw baseline row). Under
        # the LoRA axis the exchanged unit is the adapter tree, so the
        # payload is adapter-sized and the codec stacks on top
        from bcfl_tpu.compression import payload_nbytes

        raw_b = payload_nbytes(None, trainable_shapes) * num_clients
        wire_b = payload_nbytes(comp, trainable_shapes) * num_clients
        out["compress"] = COMPRESS
        out["codec_impl"] = CODEC_IMPL
        out["bytes_raw_per_round"] = int(raw_b)
        out["bytes_on_wire_per_round"] = int(wire_b)
        out["compression_ratio"] = round(raw_b / max(wire_b, 1), 2)
        if comp is not None:
            # encode-only sub-wall: the row the kernel registry's
            # XLA-vs-Pallas comparison reads on silicon
            out["codec_encode_ms"] = round(
                _codec_encode_ms(comp, trainable_shapes, num_clients), 3)
    if lora_rank > 0:
        out["lora_rank"] = lora_rank
        out["adapter_params"] = int(adapter_params)
    if peak is not None:
        # a rate above peak silicon is not a measurement, it is a timing
        # that did not wait for the device
        implied_flops = flops / dt / n_dev
        if implied_flops > 1.2 * peak:
            raise RuntimeError(
                f"implausible result (implied {implied_flops / 1e12:.0f} "
                f"TFLOP/s/chip > the {kind} peak of {peak / 1e12:.0f}): "
                "the timed region did not wait for device execution")
        if lora_rank == 0:
            out["mfu_pct"] = round(100.0 * flops / dt / (peak * n_dev), 2)
    _emit(out)


if __name__ == "__main__":
    main()
