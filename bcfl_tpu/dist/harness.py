"""Spawn/supervise/reap the peer processes (RUNTIME.md §7).

The supervisor side of the dist runtime: write the config JSON, pick free
ports, spawn one ``python -m bcfl_tpu.dist`` subprocess per peer, enforce a
hard wall deadline, and REAP stragglers — a hung peer fails the run, it
never wedges it. Every spawned process is tracked in a module-level
registry with an ``atexit`` hook (and the test conftest calls
:func:`reap_all` at session teardown), so an interrupted supervisor cannot
leave orphan peers burning CPU behind a CI job.

One process owns a chip. The supervisor therefore never initializes a jax
backend, and on platform ``tpu`` every peer is pinned to one chip of its
own through the chip-visibility environment libtpu reads
(:func:`_peer_env`); more peers than chips is refused at launch.
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

# every live peer Popen, registered at spawn and discarded at reap — the
# orphan-reaper registry (tests/conftest.py drains it at session teardown)
_LIVE: set = set()


def reap_all() -> int:
    """SIGKILL every still-running registered peer; returns how many."""
    killed = 0
    for proc in list(_LIVE):
        if proc.poll() is None:
            try:
                proc.kill()
                proc.wait(timeout=10)
                killed += 1
            except OSError:
                pass
        _LIVE.discard(proc)
    return killed


atexit.register(reap_all)


def free_ports(n: int, host: str = "127.0.0.1") -> List[int]:
    """``n`` distinct currently-free TCP ports (bound-then-released)."""
    socks, ports = [], []
    try:
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.bind((host, 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


def resolved_platform(platform: Optional[str]) -> Optional[str]:
    """The platform the peers will run on, as far as the supervisor can
    tell without touching jax: the explicit request, else the first entry
    of ``JAX_PLATFORMS``; None when neither says."""
    if platform:
        return platform
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    return first or None


def _backend_initialized() -> bool:
    """Has THIS process initialized a jax backend? False without importing
    jax when nothing else has."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bool(bridge is not None and bridge.backends_are_initialized())


def probe_devices() -> tuple:
    """``(platform, device_count)`` as a fresh interpreter's jax reports
    them. Runs in a child that exits before any peer starts, so the
    supervisor itself never holds a chip."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
        capture_output=True, text=True, timeout=300)
    if out.returncode != 0:
        raise RuntimeError(
            f"device probe failed (rc={out.returncode}): "
            f"{out.stderr.strip()[-800:]}")
    platform, count = out.stdout.split()[-2:]
    return platform, int(count)


def _peer_env(platform: Optional[str],
              chip: Optional[int] = None) -> Dict[str, str]:
    env = dict(os.environ)
    # the peers build their own single-host meshes: the test conftest's
    # 8-virtual-device XLA flag must not leak in (it would 8x every compile
    # for a 2-client slice)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        env["XLA_FLAGS"] = " ".join(
            f for f in flags.split()
            if "xla_force_host_platform_device_count" not in f)
    if platform:
        env["JAX_PLATFORMS"] = platform
    if chip is not None:
        # one chip per process (libtpu 0.0.34, checked on a 2x2 v5e host —
        # PERF.md "Bring-up"): the process sees exactly this chip, as a
        # 1x1x1 topology of its own, under device id 0
        env["TPU_VISIBLE_CHIPS"] = str(chip)
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def spawn_peer(cfg_path: str, peer_id: int, ports: List[int], run_dir: str,
               resume: bool = False, bootstrap: bool = False,
               platform: Optional[str] = None,
               repo_root: Optional[str] = None,
               chip: Optional[int] = None) -> subprocess.Popen:
    log_path = os.path.join(run_dir, f"peer{peer_id}.log")
    cmd = [sys.executable, "-m", "bcfl_tpu.dist",
           "--config", cfg_path, "--peer-id", str(peer_id),
           "--ports", ",".join(str(p) for p in ports),
           "--run-dir", run_dir]
    if resume:
        cmd.append("--resume")
    if bootstrap:
        cmd.append("--bootstrap")
    if platform:
        cmd.extend(["--platform", platform])
    log = open(log_path, "ab")
    proc = subprocess.Popen(
        cmd, stdout=log, stderr=subprocess.STDOUT,
        env=_peer_env(platform, chip), cwd=repo_root or os.getcwd())
    proc._bcfl_log = log  # keep the handle; closed at reap/collect
    _LIVE.add(proc)
    return proc


def run_dist(cfg, run_dir: str, deadline_s: Optional[float] = None,
             platform: Optional[str] = None,
             kill_peer: Optional[int] = None,
             kill_after_version: int = 1,
             restart_delay_s: float = 2.0,
             restart_killed: bool = True,
             churn: Optional[Dict] = None,
             limp: Optional[Dict] = None,
             tpu_chips: Optional[int] = None) -> Dict:
    """Run one full dist federation: spawn ``cfg.dist.peers`` peer
    processes, supervise them under a hard deadline, optionally SIGKILL
    ``kill_peer`` mid-run once its checkpoint has reached
    ``kill_after_version`` and restart it with ``--resume`` (the
    crash/rejoin leg), and collect the per-peer reports.

    ``restart_killed=False`` leaves the killed peer dead — the quorum-
    degradation leg (``scripts/dist_chaos.py``): the survivors' failure
    detectors must mark it DOWN and the leader must complete the run on
    the reachable quorum instead of stalling. The overall ``ok`` is False
    by construction there (the corpse's returncode and missing report);
    that leg's caller grades the survivors' reports instead.

    ``churn`` drives REPEATED supervised kill/rejoin cycles of one peer —
    the long-soak churn lane (scripts/dist_soak.py). RUNTIME_CAPS rejects
    ``faults.churns`` on the dist runtime by design: peer-level churn IS
    the crash/rejoin path, and this is it, exercised in a loop. A dict
    ``{"peer", "cycles", "period_s", "downtime_s", "stop_after_s"}``:
    every ``period_s`` seconds (measured from the peer's last restart),
    while a checkpoint exists for it, the leader is still alive, and
    fewer than ``cycles`` kills have fired (and, when ``stop_after_s`` is
    set, only inside that window — the last rejoin must land well before
    the leader finalizes, or the orphan re-joins a dead mesh), the peer
    is SIGKILLed, left down ``downtime_s``, and restarted with
    ``--resume``. Cycle records land under ``result["churn"]``.

    Two optional churn keys drive the storage-chaos variant
    (scripts/dist_soak.py --storage, ROBUSTNESS.md §10): ``"damage"`` —
    a list of damage class names (checkpoint.STORAGE_CLASSES) applied to
    the downed peer's checkpoint directory WHILE IT IS DOWN, cycled one
    class per kill (supervisor-side injection: deterministic coverage of
    every listed class, complementing the in-process seeded lane 8) —
    and ``"bootstrap"`` — restart the peer with ``--resume --bootstrap``
    so a scrub that finds nothing usable repairs over STATE_SYNC instead
    of exiting with ResumeError.EXIT_CODE.

    ``limp`` drives supervised SIGSTOP/SIGCONT pause cycles of one peer —
    the gray-failure limp lane (ROBUSTNESS.md §11): unlike a SIGKILL the
    peer never dies and never resumes from checkpoint, it just goes
    SILENT for ``pause_s`` seconds and then continues exactly where it
    was — the canonical limping-process signature (GC stall, CPU
    starvation, a VM freeze) that fixed-timeout detectors flap on. A
    dict ``{"peer", "pause_s", "period_s", "cycles", "stop_after_s"}``:
    every ``period_s`` seconds, while fewer than ``cycles`` pauses have
    fired, peer 0 and the target are still alive, and (when
    ``stop_after_s`` is set) only inside that window, the peer is
    SIGSTOPped, left frozen ``pause_s``, and SIGCONTed. Cycle records
    land under ``result["limp"]``. Composes freely with ``churn`` as
    long as they target different peers.

    On platform ``tpu`` peer ``p`` is pinned to chip ``p`` and a fleet
    larger than the host's chip count raises ``ValueError`` before
    anything is spawned — never a CPU fallback for the losers, never a
    hang until the peer deadline. ``tpu_chips`` injects the chip count;
    left None it comes from :func:`probe_devices`.

    Returns ``{"ok", "returncodes", "reports", "run_dir", ...}``; raises
    nothing on peer failure — the caller inspects the result (and the logs
    under ``run_dir``)."""
    from bcfl_tpu.dist.launch import cfg_to_json

    n = cfg.dist.peers
    plat = resolved_platform(platform)
    if tpu_chips is None and plat in (None, "tpu"):
        plat, count = probe_devices()
        tpu_chips = count if plat == "tpu" else None
    pin = plat == "tpu"
    if pin and n > tpu_chips:
        raise ValueError(
            f"{n} dist peers need {n} TPU chips (one process owns a chip), "
            f"but this host has {tpu_chips}: lower dist.peers or run the "
            f"peers on platform='cpu'")
    if pin and _backend_initialized():
        raise RuntimeError(
            "this process has initialized a jax backend and so holds the "
            "TPU chips its dist peers need (one process owns a chip): "
            "launch the fleet from a process that has not touched jax")
    os.makedirs(run_dir, exist_ok=True)
    ports = ([cfg.dist.base_port + i for i in range(n)]
             if cfg.dist.base_port else free_ports(n, cfg.dist.host))
    cfg_path = os.path.join(run_dir, "config.json")
    with open(cfg_path, "w") as f:
        f.write(cfg_to_json(cfg))
    deadline_s = deadline_s or (cfg.dist.peer_deadline_s + 60.0)

    def spawn(p, **kw):
        return spawn_peer(cfg_path, p, ports, run_dir, platform=platform,
                          chip=p if pin else None, **kw)

    procs = {p: spawn(p) for p in range(n)}
    rcs: Dict[int, Optional[int]] = {p: None for p in range(n)}
    killed_restarted = False
    kill_record = None
    churn_records: List[Dict] = []
    limp_records: List[Dict] = []
    t0 = time.time()
    churn_next = (t0 + float(churn.get("period_s", 45.0))
                  if churn else None)
    limp_next = (t0 + float(limp.get("period_s", 20.0))
                 if limp else None)
    while time.time() - t0 < deadline_s:
        for p, proc in list(procs.items()):
            rc = proc.poll()
            if rc is not None and rcs[p] is None:
                rcs[p] = rc
                _LIVE.discard(proc)
                getattr(proc, "_bcfl_log", None) and proc._bcfl_log.close()
        if (kill_peer is not None and not killed_restarted
                and rcs.get(kill_peer) is None):
            ckpt = os.path.join(run_dir, f"ckpt_peer{kill_peer}",
                                f"round_{kill_after_version:06d}")
            if os.path.isdir(ckpt):
                proc = procs[kill_peer]
                proc.send_signal(signal.SIGKILL)
                proc.wait(timeout=30)
                _LIVE.discard(proc)
                getattr(proc, "_bcfl_log", None) and proc._bcfl_log.close()
                kill_record = {"peer": kill_peer,
                               "killed_at_s": time.time() - t0,
                               "checkpoint_seen": ckpt,
                               "restarted": restart_killed}
                if restart_killed:
                    time.sleep(restart_delay_s)
                    procs[kill_peer] = spawn(kill_peer, resume=True)
                    rcs[kill_peer] = None
                else:
                    rcs[kill_peer] = proc.returncode
                killed_restarted = True
        if (churn_next is not None and time.time() >= churn_next
                and len(churn_records) < int(churn.get("cycles", 3))
                and rcs.get(0) is None
                and rcs.get(int(churn["peer"])) is None):
            cp = int(churn["peer"])
            stop_after = churn.get("stop_after_s")
            if (stop_after is not None
                    and time.time() - t0 > float(stop_after)):
                churn_next = None   # window closed: no further cycles
            else:
                # checkpoint guard: only kill a peer that can resume
                ckdir = os.path.join(run_dir, f"ckpt_peer{cp}")
                # a round is only fair game once FULLY committed (tree dir
                # AND meta sidecar) — killing inside the commit window
                # would leave the damage lane nothing to damage
                if os.path.isdir(ckdir) and any(
                        name.startswith("round_")
                        and name.endswith(".meta.json")
                        and os.path.isdir(os.path.join(
                            ckdir, name[:-len(".meta.json")]))
                        for name in os.listdir(ckdir)):
                    proc = procs[cp]
                    proc.send_signal(signal.SIGKILL)
                    proc.wait(timeout=30)
                    _LIVE.discard(proc)
                    getattr(proc, "_bcfl_log", None) \
                        and proc._bcfl_log.close()
                    damage = None
                    classes = churn.get("damage")
                    if classes:
                        # storage-chaos churn: damage the corpse's durable
                        # state while it is down, one class per cycle in
                        # list order (deterministic coverage of every
                        # listed class across the soak)
                        from bcfl_tpu.checkpoint import apply_storage_fault
                        cls = classes[len(churn_records) % len(classes)]
                        frac = round(
                            ((len(churn_records) + 1) * 0.31) % 1.0, 3)
                        try:
                            damage = apply_storage_fault(
                                ckdir, {"cls": cls, "frac": frac,
                                        "delete_last": 1})
                        except (OSError, ValueError) as e:
                            damage = {"cls": cls, "error": str(e)}
                    time.sleep(float(churn.get("downtime_s", 2.0)))
                    procs[cp] = spawn(
                        cp, resume=True,
                        bootstrap=bool(churn.get("bootstrap")))
                    churn_records.append(
                        {"peer": cp, "cycle": len(churn_records) + 1,
                         "killed_at_s": round(time.time() - t0, 3),
                         **({"damage": damage} if damage else {})})
                    churn_next = (time.time()
                                  + float(churn.get("period_s", 45.0)))
        if (limp_next is not None and time.time() >= limp_next
                and len(limp_records) < int(limp.get("cycles", 3))
                and rcs.get(0) is None
                and rcs.get(int(limp["peer"])) is None):
            lp = int(limp["peer"])
            stop_after = limp.get("stop_after_s")
            if (stop_after is not None
                    and time.time() - t0 > float(stop_after)):
                limp_next = None   # window closed: no further pauses
            else:
                proc = procs[lp]
                pause_s = float(limp.get("pause_s", 3.0))
                try:
                    # freeze, not kill: the peer's sockets stay open and
                    # its kernel buffers keep accepting — peers talking to
                    # it see silence and backpressure, not a reset
                    proc.send_signal(signal.SIGSTOP)
                    time.sleep(pause_s)
                finally:
                    if proc.poll() is None:
                        proc.send_signal(signal.SIGCONT)
                limp_records.append(
                    {"peer": lp, "cycle": len(limp_records) + 1,
                     "paused_at_s": round(time.time() - t0 - pause_s, 3),
                     "pause_s": pause_s})
                limp_next = time.time() + float(limp.get("period_s", 20.0))
        if all(rc is not None for rc in rcs.values()):
            break
        time.sleep(0.25)
    else:
        # deadline: reap whoever is still running — they exit nonzero
        for p, proc in procs.items():
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
                rcs[p] = proc.returncode
                _LIVE.discard(proc)
                getattr(proc, "_bcfl_log", None) and proc._bcfl_log.close()

    reports = {}
    for p in range(n):
        path = os.path.join(run_dir, f"report_peer{p}.json")
        if os.path.exists(path):
            with open(path) as f:
                reports[p] = json.load(f)
    logs = {}
    for p in range(n):
        lp = os.path.join(run_dir, f"peer{p}.log")
        if os.path.exists(lp):
            with open(lp, errors="replace") as f:
                logs[p] = f.read()[-2000:]
    ok = (all(rc == 0 for rc in rcs.values())
          and all(reports.get(p, {}).get("status") == "ok"
                  for p in range(n)))
    # per-peer telemetry streams (OBSERVABILITY.md): collate with
    # bcfl_tpu.telemetry.collate / `bcfl-tpu trace`. Scanned via the
    # same resolver the peers write through, so the two can't drift
    from bcfl_tpu.telemetry import find_streams, resolve_stream_dir

    tele_dir = resolve_stream_dir(cfg.telemetry_dir, run_dir)

    return {
        "ok": ok,
        # one process owns a chip: a supervisor that had touched a backend
        # would have been holding every chip its peers needed
        "supervisor_backend_initialized": _backend_initialized(),
        "process_count": n,
        "returncodes": {str(p): rcs[p] for p in range(n)},
        "reports": reports,
        "log_tails": logs,
        "kill": kill_record,
        "churn": churn_records,
        "limp": limp_records,
        "run_dir": run_dir,
        "event_streams": (find_streams(tele_dir)
                          if tele_dir is not None else []),
        "wall_s": time.time() - t0,
    }
