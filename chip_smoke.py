#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once on a TPU — a few
federated rounds of BERT-base at its published widths through
``bcfl_tpu.entrypoints.run``, in server and in serverless mode, with the
ledger and fused dispatch on in both and the ``int8+topk`` codec in the
server leg — checks what comes out, compiles every Pallas kernel against
its XLA reference, and (with two or more chips) runs a two-peer dist fleet
with one chip per process. The last line of stdout is one JSON object with
exactly these keys, the device as jax reports it: ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": ...}}``. The full report
(versions, compile cache, native cores, and per leg status, wall, compile
seconds and checks) is the line before it and lands in
``chiprun_out/chip_smoke.json``. Any failed leg makes ``ok`` false and the
exit code non-zero.

Nothing but a TPU is accepted: on any other backend the first act —
looking at ``jax.devices()`` — ends the run with a non-zero exit, before
any work. ``--plumbing`` is the one explicit switch that admits a CPU, with
tiny-bert and interpreted kernels, so the same file runs in a sandbox (and
under tier-1) first; it stamps ``"plumbing_only": true`` on the report.

One process owns a chip, so the file is two processes deep: this
orchestrator never imports jax; legs device/main_server/main_serverless/
kernels run in ONE child that holds every chip, and only after it has
exited does the dist leg start the CLI, whose peers each own one chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")
CORE_TIMEOUT_S = 1000
DIST_TIMEOUT_S = 420


# --------------------------------------------------------------- core child


class _Legs:
    """Runs legs in order; a failed leg is recorded with its traceback and
    the run goes on (a chip call is too dear to stop at the first failure),
    but ``ok`` is then false and the exit code non-zero."""

    def __init__(self, cache_dir: str):
        import jax

        self.cache_dir = cache_dir
        self.legs: dict = {}
        self._compile_s = 0.0
        # backend compile wall, cache retrieval included: what collapses
        # when the persistent cache is warm
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_s += secs

    def _cache_entries(self) -> int:
        try:
            return len(os.listdir(self.cache_dir))
        except FileNotFoundError:
            return 0

    def run(self, name: str, fn) -> None:
        import traceback

        print(f"[chip_smoke] leg {name} ...", flush=True)
        t0, c0, n0 = time.time(), self._compile_s, self._cache_entries()
        leg = {"status": "failed"}
        try:
            leg["checks"] = fn()
            leg["status"] = "ok"
        except Exception:  # noqa: BLE001 — recorded; the exit code says so
            leg["error"] = traceback.format_exc()[-4000:]
            print(leg["error"], file=sys.stderr, flush=True)
        leg["wall_s"] = round(time.time() - t0, 2)
        leg["compile_s"] = round(self._compile_s - c0, 2)
        leg["cache_entries_added"] = self._cache_entries() - n0
        self.legs[name] = leg
        print(f"[chip_smoke] leg {name}: {leg['status']} "
              f"wall={leg['wall_s']}s compile={leg['compile_s']}s "
              f"cache+={leg['cache_entries_added']}", flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _leg_device(devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    a = rng.standard_normal((512, 512)).astype(np.float32)
    b = rng.standard_normal((512, 512)).astype(np.float32)
    got = np.asarray(jax.jit(
        lambda x, y: jnp.dot(x, y, precision="highest"))(a, b))
    err = float(np.abs(got - a @ b).max())
    _check(err < 1e-2, f"jitted matmul off by {err}")
    # every visible device answers, not only the first
    for d in devices:
        x = jax.device_put(jnp.arange(8, dtype=jnp.int32), d)
        _check(int((x + 1).sum()) == 36, f"device {d.id} readback mismatch")
    return {"matmul_max_abs_err": err,
            "device_ids": [int(d.id) for d in devices],
            "coords": [list(getattr(d, "coords", ())) for d in devices]}


def _main_cfg(mode: str, chips: int, plumbing: bool):
    from bcfl_tpu.compression import CompressionConfig
    from bcfl_tpu.config import FedConfig, LedgerConfig, PartitionConfig

    # Full width, never shrunk: bert-base with its published 30522-token
    # vocabulary (FedConfig.vocab_size sizes the hash tokenizer AND the
    # embedding table). Rounds and local batches are what is cut to fit the
    # time limit. Two clients on one chip, so the aggregation is not the
    # identity; one client per chip beyond that, so the collective really
    # crosses chips.
    size = (dict(model="tiny-bert", seq_len=16, batch_size=4,
                 partition=PartitionConfig(kind="iid", iid_samples=16),
                 max_eval_batches=4)
            if plumbing else
            dict(model="bert-base", vocab_size=30522, seq_len=128,
                 batch_size=32,
                 partition=PartitionConfig(kind="iid", iid_samples=128),
                 max_eval_batches=8))
    # The codec rides the server leg only: its top-k sorts at BERT-base
    # widths are the dearest compile of the run, and paying it twice would
    # leave little of the time limit (the compressed gossip programs also
    # ran green on the chip once, PERF.md "Bring-up").
    return FedConfig(
        name=f"chip_smoke_{mode}", dataset="synthetic", mode=mode,
        num_clients=max(2, chips), num_rounds=4, rounds_per_dispatch=2,
        eval_every=2, max_local_batches=4, learning_rate=1e-4, donate=True,
        seed=42, ledger=LedgerConfig(enabled=True),
        compression=CompressionConfig(
            kind="int8+topk" if mode == "server" else "none"), **size)


def _leg_main(mode: str, devices, plumbing: bool):
    import jax
    import numpy as np

    from bcfl_tpu.compression import kernel_plan
    from bcfl_tpu.entrypoints import run
    from bcfl_tpu.ops import registry

    chips = len(devices)
    platform = devices[0].platform
    cfg = _main_cfg(mode, chips, plumbing)
    result = run(cfg)
    rounds = result.metrics.rounds
    losses = [r.train_loss for r in rounds]
    _check(len(rounds) == cfg.num_rounds, f"ran {len(rounds)} rounds")
    _check(all(math.isfinite(x) for x in losses), f"train loss {losses}")
    _check(losses[-1] < losses[0], f"train loss did not fall: {losses}")
    _check(any(r.fused for r in rounds), "no fused dispatch ran")
    evals = [r.global_acc for r in rounds if r.global_acc is not None]
    _check(len(evals) == 2 and all(math.isfinite(x) for x in evals),
           f"evaluation did not run twice: {evals}")

    # placement: the result lives on the accelerator, the clients mesh
    # spans every visible chip, and every chip holds a shard of the
    # per-client arrays (code that never ran on more than one device may
    # put everything on the first)
    leaves = jax.tree.leaves((result.trainable, result.params))
    _check(all(d.platform == platform for x in leaves for d in x.devices()),
           "a result leaf left the accelerator")
    mesh = leaves[0].sharding.mesh
    _check(mesh.devices.size == chips and dict(mesh.shape) == {
        "clients": chips}, f"mesh {dict(mesh.shape)} over {chips} chips")
    stacked = jax.tree.leaves((result.stacked, result.ef_residual))
    _check(bool(stacked), "no per-client state came back")
    shard_ids = set()
    for x in stacked:
        _check(x.shape[0] == cfg.num_clients, f"stacked leaf {x.shape}")
        ids = {s.device.id for s in x.addressable_shards}
        _check(ids == {d.id for d in devices},
               f"stacked leaf on devices {sorted(ids)} only")
        rows = {s.data.shape[0] for s in x.addressable_shards}
        _check(rows == {cfg.num_clients // chips}, f"shard rows {rows}")
        shard_ids |= ids
    finite = all(bool(np.isfinite(np.asarray(x)).all())
                 for x in jax.tree.leaves(result.trainable))
    _check(finite, "non-finite parameter in the result")

    # ledger: the chain verifies and every update authenticated
    _check(result.metrics.ledger.get("chain_ok") == 1.0,
           f"ledger {result.metrics.ledger}")
    _check(all(r.auth and all(a == 1.0 for a in r.auth) for r in rounds),
           f"auth {[r.auth for r in rounds]}")

    _check(registry.interpret_mode() is plumbing,
           f"interpret_mode() is {registry.interpret_mode()}")
    codec = {}
    if cfg.compression.enabled:
        # codec: fewer bytes on the wire, and which impl served which group
        ratio = result.metrics.comms["compression_ratio"]
        _check(ratio > 1.0, f"compression ratio {ratio}")
        plan = kernel_plan(cfg.compression, result.trainable,
                           cfg.num_clients)
        print(f"[chip_smoke] codec impl per leaf group ({mode}): "
              f"{json.dumps(plan)}", flush=True)
        codec = {"compression_ratio": round(ratio, 2), "codec_impl": plan}
    n_params = sum(int(x.size) for x in jax.tree.leaves(result.params))
    if not plumbing:
        _check(105e6 < n_params < 115e6, f"{n_params} parameters")
    return {
        "model": cfg.model, "params": n_params,
        "clients": cfg.num_clients, "train_loss": losses,
        "global_acc": evals, "mesh": dict(mesh.shape),
        "stacked_shard_device_ids": sorted(shard_ids),
        "chain_ok": 1.0, "auth_all_ok": True, **codec,
        "interpret_mode": registry.interpret_mode(),
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices],
    }


def _bits_equal(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def _leg_kernels(plumbing: bool):
    """Every registry op with a Pallas impl, compiled (interpreted only
    under --plumbing), at a shape it is declared to be paid at, against its
    XLA reference under ONE jit, to its declared parity."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bcfl_tpu.ops import flash, pallas_codec, registry

    checks = {"interpret_mode": registry.interpret_mode()}
    _check(registry.interpret_mode() is plumbing, str(checks))
    # auto = pallas where one TPU chip is visible; with more, the GSPMD
    # round programs cannot hold a Mosaic kernel and auto serves the
    # references (registry.pallas_by_default)
    want = "pallas" if not plumbing and jax.device_count() == 1 else "xla"
    for name in ("flash_attention", "int8_quantize", "topk_select"):
        _, impl = registry.resolve(name, "auto")
        checks[f"auto:{name}"] = impl
        _check(impl == want, f"auto selects {impl} for {name}, not {want}")

    def shape(op, label):
        (row,) = [r for r in op.bench_shapes if r["label"] == label]
        return row

    # -- int8_quantize: bit-identical, stochastic and deterministic
    key = jax.random.key(0)
    for label in ("bert-mlp-768x3072", "bert-vec-768"):
        row = shape(pallas_codec.INT8_QUANTIZE, label)
        C, chunk = (2, 16) if plumbing else (row["C"], row["chunk"])
        M = 3 if plumbing else -(-row["N"] // chunk)
        g = jax.random.normal(key, (C, M, chunk), jnp.float32)
        g = g.at[0, 0].set(0.0)  # an all-zero chunk: the 1e-30 scale floor
        u = jax.random.uniform(jax.random.fold_in(key, 1), g.shape)
        for stochastic in (True, False):
            ref, got = jax.jit(lambda g, u, s=stochastic: (
                pallas_codec._int8_quantize_xla(g, u if s else None,
                                                stochastic=s),
                pallas_codec._int8_quantize_pallas(g, u if s else None,
                                                   stochastic=s)))(g, u)
            ok = all(_bits_equal(a, b) for a, b in zip(ref, got))
            tag = f"int8_quantize/{label}/{'stoch' if stochastic else 'det'}"
            checks[tag] = "bit-identical" if ok else "MISMATCH"
            _check(ok, tag)

    # -- topk_select: bit-identical, at the adapter width and at the widest
    # row the static predicate admits
    widest = pallas_codec.TOPK_VMEM_BUDGET_BYTES // (
        8 * 4 * pallas_codec._TOPK_LIVE_BUFFERS)
    row = shape(pallas_codec.TOPK_SELECT, "lora-r8-6144")
    _check(not pallas_codec.topk_supported(
        jax.ShapeDtypeStruct((8, widest + 1), jnp.float32), k=1),
        "the predicate admits a wider row than probed")
    for label, (R, N) in (("lora-r8-6144", (row["R"], row["N"])),
                          (f"widest-{widest}", (8, widest))):
        if plumbing:
            R, N = 8, N // 64
        k = max(1, math.ceil(0.05 * N))
        x = jax.random.normal(jax.random.fold_in(key, 2), (R, N), jnp.float32)
        # magnitude ties and signed zeros: the tie-break and the
        # sign-preserving select are part of the contract
        x = x.at[0, :4].set(jnp.asarray([0.5, 0.5, -0.5, 0.0]))
        x = x.at[1, :2].set(jnp.asarray([-0.0, 0.0]))
        _check(pallas_codec.topk_supported(x, k=k), f"{label} not admitted")
        ref, got = jax.jit(lambda x, k=k: (
            pallas_codec._topk_select_xla(x, k=k),
            pallas_codec._topk_select_pallas(x, k=k)))(x)
        ok = all(_bits_equal(a, b) for a, b in zip(ref, got))
        checks[f"topk_select/{label}/k{k}"] = (
            "bit-identical" if ok else "MISMATCH")
        _check(ok, f"topk_select/{label}")

    # -- flash_attention: forward and the WHOLE backward (dq, dk, dv,
    # dbias), bf16, causal and padded. Declared parity allclose:2e-2, read
    # against the reference's own magnitude (bf16 gradients of size ~4 are
    # one bf16 ulp = 3e-2 apart)
    row = shape(flash.FLASH_ATTENTION, "bert-base-B4-S512")
    B, H, S, D = (2, 2, 128, 8) if plumbing else (
        row["B"], row["H"], row["S"], row["D"])
    q, k, v = (jax.random.normal(jax.random.fold_in(key, 10 + i),
                                 (B, H, S, D), jnp.bfloat16)
               for i in range(3))
    w = jax.random.normal(jax.random.fold_in(key, 13), q.shape, jnp.float32)
    lens = jnp.asarray([S, (3 * S) // 4, S // 3, S // 7][:B])
    bias = jnp.where(jnp.arange(S)[None, :] < lens[:, None], 0.0,
                     -1e9).astype(jnp.float32)
    for causal in (False, True):
        def both(q, k, v, bias, causal=causal):
            def outs(fn):
                def loss(*a):
                    out = fn(*a, causal=causal)
                    return (out.astype(jnp.float32) * w).sum(), out
                grads, out = jax.grad(loss, argnums=(0, 1, 2, 3),
                                      has_aux=True)(q, k, v, bias)
                return (out,) + grads
            return (outs(flash.flash_attention_xla),
                    outs(flash.flash_attention_pallas))
        ref, got = jax.jit(both)(q, k, v, bias)
        for part, a, b in zip(("out", "dq", "dk", "dv", "dbias"), ref, got):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            err, scale = float(np.abs(a - b).max()), float(np.abs(a).max())
            tag = f"flash_attention/causal={causal}/padded/{part}"
            checks[tag] = {"max_abs_err": round(err, 5),
                           "ref_max": round(scale, 3)}
            _check(np.isfinite(b).all() and err <= 2e-2 * max(1.0, scale),
                   f"{tag}: {checks[tag]}")
    return checks


def core_main(plumbing: bool, report_path: str) -> int:
    """Legs device, main_server, main_serverless and kernels, in this one
    process. The first act is the device check."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    if d0.platform != "tpu" and not plumbing:
        print(f"chip_smoke: jax reports {device}, not a TPU; nothing was "
              "run (--plumbing admits a CPU, and says so in its report)",
              file=sys.stderr)
        return 2

    import jaxlib

    from bcfl_tpu.core.hostenv import compile_cache, device_peak_flops
    from bcfl_tpu.native.build import load_ledger_lib, load_tokenizer_lib

    if not plumbing:
        device_peak_flops(d0.device_kind)  # an unknown kind is an error
    cache_dir, from_env = compile_cache()
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    report = {
        "device": device, "plumbing_only": plumbing,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version,
                     "python": sys.version.split()[0]},
        "compile_cache": {"dir": cache_dir, "from_env": from_env},
        "native": {name: "built" if lib is not None else "python fallback"
                   for name, lib in (("ledger", load_ledger_lib()),
                                     ("tokenizer", load_tokenizer_lib()))},
    }
    print(f"[chip_smoke] {json.dumps(report)}", flush=True)
    legs = _Legs(cache_dir)
    legs.run("device", lambda: _leg_device(devices))
    legs.run("main_server", lambda: _leg_main("server", devices, plumbing))
    legs.run("main_serverless",
             lambda: _leg_main("serverless", devices, plumbing))
    legs.run("kernels", lambda: _leg_kernels(plumbing))
    report["legs"] = legs.legs
    with open(report_path, "w") as f:
        json.dump(report, f)
    return 0 if all(v["status"] == "ok" for v in legs.legs.values()) else 1


# --------------------------------------------------------------- dist leg


def dist_leg(device: dict, plumbing: bool) -> dict:
    """Two peers through the CLI, each on a chip of its own; the CLI parent
    (and this process) initialize no jax backend. tiny-bert: the leg proves
    chip ownership, width is proven by the main legs."""
    rounds = 3
    cmd = [sys.executable, "-m", "bcfl_tpu.entrypoints", "--preset", "smoke",
           "--runtime", "dist", "--peers", "2", "--clients", "4",
           "--rounds", str(rounds), "--model", "tiny-bert", "--seq-len",
           "16", "--batch-size", "4", "--ledger", "--compress", "int8",
           "--dist-deadline", "300"]
    if plumbing:
        cmd += ["--platform", device["platform"]]
    t0 = time.time()
    try:
        out = subprocess.run(cmd, cwd=HERE, capture_output=True, text=True,
                             timeout=DIST_TIMEOUT_S)
    except subprocess.TimeoutExpired as e:
        return {"status": "failed", "wall_s": round(time.time() - t0, 2),
                "error": f"dist CLI still running after {e.timeout}s"}
    leg = {"status": "failed", "wall_s": round(time.time() - t0, 2)}
    text = out.stdout
    try:
        summary = json.loads(text[text.index("{"):])
    except ValueError:
        leg["error"] = (f"rc={out.returncode}, no JSON summary: "
                        f"{text[-1500:]} {out.stderr[-2500:]}")
        return leg
    peers = {}
    for p in ("0", "1"):
        path = os.path.join(summary["run_dir"], f"report_peer{p}.json")
        try:
            with open(path) as f:
                peers[p] = json.load(f).get("device")
        except OSError:
            peers[p] = None
    leg["checks"] = checks = {
        "returncode": out.returncode,
        "ok": summary.get("ok"),
        "invariants_ok": summary.get("invariants_ok"),
        "final_versions": summary.get("final_versions"),
        "supervisor_backend_initialized":
            summary.get("supervisor_backend_initialized"),
        "peer_devices": peers,
    }
    chips = [d and d["visible_chip"] for d in peers.values()]
    good = (
        out.returncode == 0 and checks["ok"] is True
        and checks["invariants_ok"] is True
        and checks["supervisor_backend_initialized"] is False
        and all(v is not None and v >= rounds
                for v in (checks["final_versions"] or {"": None}).values())
        and all(d is not None and d["platform"] == device["platform"]
                for d in peers.values())
        and (plumbing or (None not in chips and len(set(chips)) == 2)))
    if good:
        leg["status"] = "ok"
    else:
        leg["error"] = f"dist leg checks failed: {out.stderr[-2500:]}"
    return leg


# ------------------------------------------------------------ orchestrator


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plumbing", action="store_true",
                    help="admit a CPU backend: tiny-bert, interpreted "
                         "kernels, report stamped plumbing_only")
    ap.add_argument("--leg", choices=["core"], help=argparse.SUPPRESS)
    ap.add_argument("--report", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.leg == "core":
        return core_main(args.plumbing, args.report)

    os.makedirs(OUT_DIR, exist_ok=True)
    core_path = os.path.join(OUT_DIR, "chip_smoke_core.json")
    if os.path.exists(core_path):
        os.remove(core_path)
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", "core",
           "--report", core_path] + (["--plumbing"] if args.plumbing else [])
    try:
        rc = subprocess.run(cmd, cwd=HERE, timeout=CORE_TIMEOUT_S).returncode
    except subprocess.TimeoutExpired:
        print(f"chip_smoke: legs still running after {CORE_TIMEOUT_S}s",
              file=sys.stderr)
        return 1
    if not os.path.exists(core_path):
        # no accelerator, or no repo beside this file: no result is printed
        return rc or 1
    with open(core_path) as f:
        report = json.load(f)
    os.remove(core_path)
    if report["device"]["count"] >= 2:
        print("[chip_smoke] leg dist ...", flush=True)
        report["legs"]["dist"] = dist_leg(report["device"], args.plumbing)
    else:
        report["legs"]["dist"] = {"status": "skipped: one chip"}
    report = {"ok": all(
        leg["status"] == "ok" or leg["status"].startswith("skipped")
        for leg in report["legs"].values()), **report}
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)
    for name, leg in report["legs"].items():
        if "error" in leg:
            print(f"[chip_smoke] leg {name} FAILED:\n{leg['error']}",
                  file=sys.stderr, flush=True)
    print(f"[chip_smoke] report: {json.dumps(report)}", flush=True)
    # the result line: these keys and no others
    print(json.dumps({"ok": report["ok"], "device": report["device"]}),
          flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
