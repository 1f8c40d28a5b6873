"""On-chip bisection of the fused-dispatch gap (PERF.md, round 4).

The recorded r04 bench ran the fused GSPMD ``server_rounds`` program at
~1.37 s/step while the identical local-train math under plain ``jit``
measures 35.7 ms/step on the same chip (PERF.md r03 table). This script
isolates WHERE the ~38x goes by timing a ladder of program forms that
differ by exactly one structural element each, in ONE process on the chip:

  A  plain     jit(local_train)                       — the 35.7 ms oracle
  B  fused     jit(scan_R(local_train))               — + round scan
  C  vmap1     jit(vmap_C=1(local_train))             — + client vmap
  D  stripped  jit(scan_R(vmap_C=1 + mean))           — + aggregation, NO
                                                         sharding anns
  E  gspmd     progs.server_rounds (donate=False)     — + constraints /
                                                         out_shardings
  F  donate    progs.server_rounds (donate=True)      — + buffer donation
                                                         (the bench config)

Every timed loop chains the output params into the next call's input, and
each row is appended to ``results/dispatch_bisect.json`` as soon as it is
measured so a failure mid-ladder keeps the completed evidence.

Usage: python scripts/dispatch_bisect.py [--quick] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.environ.get("BISECT_OUT") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "results", "dispatch_bisect.json")
STAGE_TIMEOUT_S = 1800.0


class _Watchdog:
    def __init__(self, timeout_s):
        self._timeout = timeout_s
        self._timer = None
        self.name = "start"

    def stage(self, name):
        self.name = name
        self.cancel()
        self._timer = threading.Timer(self._timeout, self._fire)
        self._timer.daemon = True
        self._timer.start()
        print(f"[stage] {name}", flush=True)

    def _fire(self):
        print(f"WATCHDOG: stage {self.name!r} wedged "
              f"(> {self._timeout:.0f}s); exiting", flush=True)
        os._exit(2)

    def cancel(self):
        if self._timer is not None:
            self._timer.cancel()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny-bert, tiny shapes (CPU plumbing check)")
    ap.add_argument("--platform", default=None)
    ap.add_argument("--iters", type=int, default=2)
    args = ap.parse_args(argv)

    wd = _Watchdog(STAGE_TIMEOUT_S)
    wd.stage("backend-init")

    import jax

    from bcfl_tpu.core.hostenv import compile_cache

    compile_cache()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp

    from jax import lax

    from bcfl_tpu.core.fence import fence

    from bcfl_tpu.core.mesh import client_mesh
    from bcfl_tpu.fed.client_step import (build_programs, make_local_train,
                                          make_loss_fn, make_optimizer)
    from bcfl_tpu.fed.synthetic import synthetic_round_inputs
    from bcfl_tpu.models import build

    model_name = "tiny-bert" if args.quick else "bert-base"
    STEPS = 2 if args.quick else 8
    ROUNDS = 2 if args.quick else 8
    BATCH = 4 if args.quick else 32
    SEQ = 32 if args.quick else 128
    ITERS = args.iters

    dev = jax.devices()[0]
    meta = {"device": dev.device_kind, "model": model_name, "steps": STEPS,
            "rounds": ROUNDS, "batch": BATCH, "seq": SEQ, "iters": ITERS}
    rows = []

    def record(name, steps_per_call, dt_per_call, note=""):
        row = {"variant": name, "steps_per_call": steps_per_call,
               "s_per_call": round(dt_per_call, 4),
               "ms_per_step": round(dt_per_call / steps_per_call * 1e3, 2),
               "note": note}
        rows.append(row)
        print(json.dumps(row), flush=True)
        with open(OUT, "w") as f:
            json.dump({"meta": meta, "rows": rows}, f, indent=1)

    wd.stage("build")
    model = build(model_name, num_labels=2)
    mesh = client_mesh(1)
    ids0 = jnp.ones((2, SEQ), jnp.int32)
    params = jax.jit(lambda k: model.init(k, ids0, ids0)["params"])(
        jax.random.key(0))
    fence(params)

    tx = make_optimizer("adamw", 5e-5)
    loss_fn = make_loss_fn(model)
    local_train = make_local_train(tx, loss_fn)

    # one client's batches for STEPS local steps
    batches, weights, rngs = synthetic_round_inputs(
        mesh, steps=STEPS, batch=BATCH, seq=SEQ, vocab_size=30_000)
    b1 = jax.tree.map(lambda x: x[0], batches)  # unstacked single client
    key = jax.random.key(7)
    # round-stacked inputs for the fused forms
    rbatches = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (ROUNDS,) + x.shape), batches)
    rweights = jnp.broadcast_to(weights[None], (ROUNDS,) + weights.shape)
    rrngs = jnp.broadcast_to(rngs[None], (ROUNDS,) + rngs.shape)
    rb1 = jax.tree.map(lambda x: x[:, 0], rbatches)  # [R, S, B, L]
    rr1 = rrngs[:, 0]  # [R, 2]

    def timeit(name, fn, carry, steps_per_call, note=""):
        """Warm (compile) TWICE, then time ITERS chained calls.

        Two warmups matter: the first call's input tree is single-device
        committed, but its output (the next call's input) carries the
        program's out_shardings — a DIFFERENT sharding, so call 2 is a
        fresh jit cache entry (a full recompile). Timing from call 3 on
        measures steady state. A 1-warmup loop times half a recompile —
        exactly the r04 bench's 87.5 s/dispatch artifact."""
        wd.stage(f"compile:{name}")
        t0 = time.perf_counter()
        carry = fn(carry)
        fence(carry)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        carry = fn(carry)
        fence(carry)
        compile2_s = time.perf_counter() - t0
        note = (note + f" compile2={compile2_s:.1f}s").strip()
        wd.stage(f"measure:{name}")
        t0 = time.perf_counter()
        for _ in range(ITERS):
            carry = fn(carry)
        fence(carry)
        dt = (time.perf_counter() - t0) / ITERS
        record(name, steps_per_call, dt,
               note=(note + f" compile={compile_s:.1f}s").strip())

    wrap = jax.random.wrap_key_data

    # ---- A: plain jit(local_train) — the oracle ----
    plain = jax.jit(local_train)
    timeit("A_plain_jit", lambda t: plain(t, None, b1, key)[0], params, STEPS)

    # ---- B: + round scan (no vmap, no mean) ----
    def fused_novmap(t):
        def one_round(t, xs):
            b, r = xs
            return local_train(t, None, b, wrap(r))

        return lax.scan(one_round, t, (rb1, rr1))[0]

    timeit("B_scan_rounds", jax.jit(fused_novmap), params, ROUNDS * STEPS)

    # ---- C: + client vmap (C=1), single round ----
    vm = jax.jit(jax.vmap(lambda t, b, r: local_train(t, None, b, wrap(r)),
                          in_axes=(0, 0, 0)))
    stacked = jax.tree.map(lambda x: x[None], params)
    timeit("C_vmap1", lambda s: vm(s, batches, rngs)[0], stacked, STEPS)

    # ---- D: scan + vmap + unweighted mean, NO sharding annotations ----
    def stripped(t):
        def one_round(t, xs):
            b, r = xs
            new_t, stats = jax.vmap(
                lambda bb, rr: local_train(t, None, bb, wrap(rr)))(b, r)
            return jax.tree.map(lambda x: x.mean(0), new_t), stats

        return lax.scan(one_round, t, (rbatches, rrngs))[0]

    timeit("D_stripped_fused", jax.jit(stripped), params, ROUNDS * STEPS)

    # ---- E: the real GSPMD server_rounds, donate OFF ----
    progs_nd = build_programs(model, mesh, donate=False)
    timeit("E_gspmd_rounds",
           lambda t: progs_nd.server_rounds(t, None, rbatches, rweights,
                                            rrngs)[0],
           params, ROUNDS * STEPS)

    # ---- F: the bench config — GSPMD server_rounds, donate ON ----
    progs_d = build_programs(model, mesh, donate=True)
    timeit("F_gspmd_donate",
           lambda t: progs_d.server_rounds(t, None, rbatches, rweights,
                                           rrngs)[0],
           params, ROUNDS * STEPS)

    wd.cancel()
    print("done ->", OUT, flush=True)


if __name__ == "__main__":
    main()
