"""``run(config)`` — the single entrypoint replacing all 11 reference scripts.

Prints the reference's metric set at the end (CPU overhead %, memory GB,
latency minutes, model size GB, per-round local/global accuracy — the prints
at ``serverless_NonIID_IMDB.py:320-334`` and ``server_IID_IMDB.py:221-233``),
plus the info-passing-time and ledger accounting the notebooks model offline.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional

from bcfl_tpu.config import FedConfig
from bcfl_tpu.fed.engine import FedEngine, RunResult


def run(cfg: FedConfig, resume: bool = False, verbose: bool = True,
        fused_tamper=None) -> RunResult:
    """``fused_tamper``: optional ``(round) -> [num_clients] float scales or
    None`` — in-graph transport corruption for fused dispatches (the BC-FL
    tamper-resistance demo; see ``FedEngine.__init__``)."""
    if verbose:
        print("\n".join(_header(cfg)), flush=True)
    engine = FedEngine(cfg, fused_tamper=fused_tamper)
    if verbose:
        print(device_line(engine.mesh), flush=True)
    result = engine.run(resume=resume,
                        on_round=_print_round if verbose else None)
    if verbose:
        print(format_report(cfg, result, rounds=False, header=False))
    return result


def _header(cfg: FedConfig) -> list:
    clients = f"clients={cfg.num_clients}"
    if cfg.registry_size:
        # cohort mode (SCALING.md): the stacked axis is the sampled cohort
        clients = (f"registry={cfg.registry_size} "
                   f"cohort={cfg.sample_clients or cfg.num_clients}/round")
    return [
        f"== {cfg.name} ==",
        f"mode={cfg.mode} sync={cfg.sync} {clients} "
        f"rounds={cfg.num_rounds} model={cfg.model} dataset={cfg.dataset}",
    ]


def device_line(mesh) -> str:
    """``devices=<used>/<visible> x <device_kind> (<platform>)`` plus the
    mesh shape. ``client_mesh`` takes the largest divisor of the client
    count that fits, so 10 clients on 4 chips use 2 of them: printed in
    every run header, under-use is never silent."""
    import jax

    devs = mesh.mesh.devices
    shape = ", ".join(f"{k}={v}" for k, v in mesh.mesh.shape.items())
    return (f"devices={devs.size}/{jax.device_count()} x "
            f"{devs.flat[0].device_kind} ({devs.flat[0].platform}) "
            f"mesh=({shape}) clients_per_device={mesh.per_device}")


def _round_line(r) -> str:
    acc = f" global_acc={r.global_acc:.4f}" if r.global_acc is not None else ""
    anom = f" anomalies={r.anomalies}" if r.anomalies else ""
    # surface ledger rejections: a tampered/corrupted update failing auth is
    # the BC-FL flow's observable outcome and must not be silent
    rejected = ([i for i, a in enumerate(r.auth) if a == 0.0]
                if r.auth else [])
    rej = f" auth_failed={rejected}" if rejected else ""
    # chaos-harness observability (bcfl_tpu.faults): injected dropout and an
    # all-eliminated (model-kept) round must be visible in the stream
    drop = f" dropped={r.dropped}" if r.dropped else ""
    deg = " DEGRADED" if r.degraded else ""
    # peer-lifecycle observability (ROBUSTNESS.md §6): partition spans,
    # heals, churn absences, and quarantined/probation peers in the stream
    part = ""
    if r.partition is not None:
        comps = sorted(set(p for p in r.partition if p >= 0))
        part = f" PARTITIONED x{len(comps)}"
    if r.healed:
        part += " HEALED"
    gone = ([i for i, a in enumerate(r.churn_alive) if a == 0.0]
            if r.churn_alive else [])
    churn = f" churned_out={gone}" if gone else ""
    rep = ""
    if r.reputation_state is not None:
        q = [i for i, s in enumerate(r.reputation_state)
             if s == "quarantined"]
        p = [i for i, s in enumerate(r.reputation_state) if s == "probation"]
        if q:
            rep += f" quarantined={q}"
        if p:
            rep += f" probation={p}"
    return (f"round {r.round:3d}: train_loss={r.train_loss:.4f} "
            f"train_acc={r.train_acc:.4f}{acc}{anom}{rej}{drop}{part}"
            f"{churn}{rep}{deg} wall={r.wall_s:.2f}s")


def _print_round(r) -> None:
    print(_round_line(r), flush=True)


def format_report(cfg: FedConfig, result: RunResult, rounds: bool = True,
                  header: bool = True) -> str:
    """rounds=False / header=False omit the per-round lines / header (already
    streamed live by run(verbose=True) via the engine's on_round callback)."""
    m = result.metrics
    lines = _header(cfg) if header else []
    if rounds:
        lines.extend(_round_line(r) for r in m.rounds)
    # reference metric names (server_IID_IMDB.py:221-233, with the reversed
    # before/after memory naming fixed — SURVEY.md C11)
    lines.append(m.summary())
    if m.rounds and m.rounds[-1].info_passing_sync_s is not None:
        r = m.rounds[-1]
        lines.append(
            f"info passing time: sync={r.info_passing_sync_s:.3f}s "
            f"async={r.info_passing_async_s:.3f}s"
        )
    if m.ledger:
        lines.append("ledger: " + json.dumps(m.ledger))
    accs = m.global_accuracies
    lines.append(f"global_accuracies: {[round(a, 4) for a in accs]}")
    return "\n".join(lines)


def run_sweep(
    cfg: FedConfig,
    client_counts: Optional[List[int]] = None,
    resume: bool = False,
    verbose: bool = True,
    out_dir: Optional[str] = "results",
) -> Dict[int, RunResult]:
    """The reference's worker sweep (``for NUM_CLIENTS in [5,10,20]``,
    ``serverless_cancer_biobert_allclients.py:41``) over one config. Each
    client count checkpoints into its own subdirectory. ``out_dir`` gets
    the reference notebooks' sweep figure set (latency/accuracy/memory by
    client count — cells 15/18/21) plus ``<name>_sweep.json``; None skips
    recording."""
    import json
    import os

    from bcfl_tpu.entrypoints.presets import SWEEP_CLIENTS
    from bcfl_tpu.viz import sweep_report

    out: Dict[int, RunResult] = {}
    for n in client_counts or SWEEP_CLIENTS:
        ckpt = (os.path.join(cfg.checkpoint_dir, f"c{n}")
                if cfg.checkpoint_dir else None)
        out[n] = run(
            cfg.replace(name=f"{cfg.name}_c{n}", num_clients=n,
                        checkpoint_dir=ckpt),
            resume=resume, verbose=verbose)
    if out_dir:
        paths = sweep_report(out, out_dir, name=f"{cfg.name}_sweep")
        record = {
            str(n): {
                "final_acc": (r.metrics.global_accuracies[-1]
                              if r.metrics.global_accuracies else None),
                "latency_min": sum(x.wall_s for x in r.metrics.rounds) / 60.0,
                "memory_gb": r.metrics.resources.get("memory_gb"),
            } for n, r in out.items()
        }
        jpath = os.path.join(out_dir, f"{cfg.name}_sweep.json")
        with open(jpath, "w") as f:
            json.dump({"model": cfg.model, "dataset": cfg.dataset,
                       "rounds": cfg.num_rounds, "mode": cfg.mode,
                       "counts": sorted(out), "runs": record}, f, indent=2)
        if verbose:
            print(f"sweep artifacts: {jpath} + {len(paths)} figures",
                  flush=True)
    return out
