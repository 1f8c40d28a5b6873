"""The benchmark's harness: one cell, one seed, one process.

It finds everything by name: the cell's file (benchmarks/workloads), its
configuration's file (benchmarks/configs), the configuration's model family
(benchmarks/families/<family>, named by the file's ``family`` key) and the
per-layer metrics' files (benchmarks/metrics, each naming its reader under
benchmarks/readers). A later PR adds a cell, a configuration, a metric or a
whole model family by adding such files and entries in BENCHMARK.json, and
edits nothing here. A family is a package that answers, for its models,
everything that depends on the model (benchmarks/families/__init__.py lists
the interface): the weights from the seed and their layout as the program's
``(trainable, frozen)`` trees, the plain reference of the first rounds and
its precisions' names, the required operations, and the ``FedConfig`` fields
and the job kind its configurations map to. Nothing in this file knows a
model: a new family brings ``families/<name>/`` (its ``__init__.py`` and
whatever it splits off), a configuration ``configs/<name>.json``, a cell
``workloads/<name>.json``, a metric ``metrics/<name>.json`` and its reader.

What it drives is the product's entry, ``FedEngine.run`` on an engine built
from a ``FedConfig`` as ``bcfl_tpu.entrypoints.run`` builds it. From the
seed the family makes the weights and benchmarks/traffic.py the round's
batches, and the harness hands both to the engine; the reference makes the
same arrays from the same generators and takes nothing from the program.

One engine object goes through three ``run`` calls: the first rounds (from
the seed; these are compared with the reference), a second warm dispatch,
and the measured window. With ``--trace 1`` the traced bracket is reduced,
by scope too, and the per-layer readers run while the trace is still on
disk. See PERF.md, Layers, for what each metric reads.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

EXIT_NO_DEVICE = 3
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def place_compile_cache():
    """JAX's persistent compile cache at a fixed path inside the checkout,
    whatever the environment names, so that only a cell's first run in a
    checkout compiles. Call before anything compiles. The program's entry
    points take the directory from the variable (``hostenv.compile_cache``
    sets none in code where it is set). Every program is kept, also those
    that compile in under a second, and the cache is never capped: under a
    cap smaller than one run's programs (the chip tool's own directory had
    one, PERF.md section 6) each run evicts what the next one needs and
    every run compiles anew."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from bcfl_tpu.core import hostenv

    hostenv.compile_cache()
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return CACHE_DIR


# ----------------------------------------------------------------- the files

def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name, plumbing=False):
    """The cell's file and its configuration's sizes. ``plumbing`` swaps in
    the tiny stand-ins that both files carry for the CPU rehearsal."""
    cell = load_json("workloads", name + ".json")
    sizes = load_json("configs", cell["config"] + ".json")
    if plumbing:
        sizes = dict(sizes, **sizes["plumbing"])
        cell = dict(cell)
        cell["traffic"] = dict(cell["traffic"], **cell["plumbing"]["traffic"])
        cell["limits"] = cell["plumbing"]["limits"]
    return cell, sizes


def load_reader(spec):
    """``readers/<file>.py:<function>`` -> the function."""
    path, fn = spec.split(":")
    full = os.path.join(HERE, path)
    mod_name = "bench_reader_" + path.replace("/", "_").replace(".", "_")
    if mod_name not in sys.modules:
        s = importlib.util.spec_from_file_location(mod_name, full)
        mod = importlib.util.module_from_spec(s)
        sys.modules[mod_name] = mod
        s.loader.exec_module(mod)
    return getattr(sys.modules[mod_name], fn)


def metrics_for(bench, cell_name, kind):
    """The metrics of ``kind`` (end_to_end / per_layer) that this cell
    reports: those with no ``workloads`` key and those that list it."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


# ------------------------------------------------------------ the FedConfig

def build_cfg(cell, sizes, seed, telemetry_dir):
    """``FedConfig`` from the two files and the seed. The fields that depend
    on the model (``model``, ``vocab_size``, ``num_labels``, ``task``, ...)
    are the family's ``program(sizes)``; the cell's ``fed`` object carries
    every field the cell sets; nested objects become the nested config
    classes by the type of the field's default."""
    from bcfl_tpu.config import FedConfig

    from benchmarks import families

    t, tr = cell["traffic"], sizes["training"]
    fields = dict(
        name=cell["name"], seed=int(seed),
        seq_len=t["seq"], batch_size=t["batch"], num_clients=t["clients"],
        max_local_batches=t["local_batches"],
        param_dtype=tr["param_dtype"], compute_dtype=tr["compute_dtype"],
        optimizer=tr["optimizer"], learning_rate=tr["learning_rate"],
        telemetry_dir=telemetry_dir,
    )
    fields.update(families.of(sizes).program(sizes))
    fields.update(cell["fed"])
    defaults = {f.name: f for f in dataclasses.fields(FedConfig)}
    for k, v in list(fields.items()):
        if k not in defaults:
            raise KeyError(f"cell {cell['name']}: FedConfig has no field {k!r}")
        if isinstance(v, dict):
            f = defaults[k]
            proto = (f.default_factory() if f.default_factory is not dataclasses.MISSING
                     else f.default)
            fields[k] = type(proto)(**v)
    return FedConfig(**fields)


# ----------------------------------------------------------------- a run

class Run:
    """State of one benchmark run, filled phase by phase."""

    def __init__(self, cell, sizes, seed, seconds, trace, plumbing, out_dir,
                 t_process_start):
        self.cell, self.sizes, self.seed = cell, sizes, int(seed)
        self.seconds, self.trace, self.plumbing = float(seconds), bool(trace), plumbing
        self.out_dir = out_dir
        self.t_process_start = t_process_start
        self.compile_events = 0
        self.counting = False


def _rec_dict(rec):
    d = dataclasses.asdict(rec)
    return {k: d[k] for k in ("round", "train_loss", "train_acc", "mask", "auth",
                               "degraded", "fused", "wall_s", "wall_chunk_s",
                               "anomalies", "reputation_state")}


def _leaves(tree):
    """``{path: (shape, dtype)}`` of a tree's leaves."""
    import jax

    return {jax.tree_util.keystr(k): (tuple(x.shape), str(x.dtype))
            for k, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def hand_over(engine, trainable, frozen, sizes):
    """The benchmark's weights in place of the engine's own initial draw.
    Both trees have to be the engine's own leaf for leaf, in shape and in
    type, and the model's parameters (the frozen base; for full fine-tuning
    the trained tree) in the ``training.param_dtype`` the configuration
    states: anything else raises, so a program whose trees or types change is
    refused at set-up and nothing is substituted. What is trained over a
    frozen base has the type the PROGRAM draws it in (models/lora.py: the
    base's), and the family writes it so. Each tree is placed as the engine
    places its own: the trained tree replicated, the frozen base once, with
    the sharding of the leaf it replaces."""
    import jax

    stated = sizes["training"]["param_dtype"]
    for what, mine, own in (("trainable", trainable, engine.trainable0),
                            ("frozen", frozen, engine.frozen)):
        if (mine is None) != (own is None):
            raise RuntimeError(f"the family hands over {'no' if mine is None else 'a'} "
                               f"{what} tree and the program has {'none' if own is None else 'one'}")
        if mine is None:
            continue
        want, have = _leaves(own), _leaves(mine)
        if want != have:
            odd = sorted(k for k in set(want) | set(have) if want.get(k) != have.get(k))
            raise RuntimeError(
                f"the program's {what} tree is not the configuration's: {len(odd)} leaves differ, "
                f"the first {[(k, want.get(k), have.get(k)) for k in odd[:3]]} (program, family)")
        if what == "frozen" or frozen is None:
            off = sorted({d for _, d in want.values()} - {stated})
            if off:
                raise RuntimeError(f"the program holds its parameters (the {what} tree) in "
                                   f"{off} where the configuration states {stated}")
    engine.trainable0 = engine.mesh.replicate(trainable)
    if frozen is not None:
        where = jax.tree.map(lambda x: x.sharding, engine.frozen)
        engine.frozen = None  # the engine's own draw goes before ours is placed
        engine.frozen = jax.device_put(frozen, where)


def setup_engine(run, prepare=None):
    """Weights and traffic from the seed, the engine, and both handed over.
    ``prepare(engine)`` is the tests' hook to break the timed path."""
    import jax
    import jax.numpy as jnp

    from bcfl_tpu.fed.engine import FedEngine

    from benchmarks import families, traffic

    cell, sizes = run.cell, run.sizes
    fam = families.of(sizes)
    tele = os.path.join(run.out_dir, "telemetry")
    shutil.rmtree(tele, ignore_errors=True)
    cfg = build_cfg(cell, sizes, run.seed, tele)
    run.cfg = cfg
    batches, n_ex = traffic.make(cell["traffic"], cfg.vocab_size, cfg.num_labels, run.seed,
                                 job=cfg.task)
    run.batches, run.n_ex = batches, n_ex
    flat = fam.make_weights(sizes, run.seed)
    trainable, frozen = fam.to_program(flat, sizes)
    engine = FedEngine(cfg)
    # the benchmark's weights in place of the engine's own; the benchmark's
    # batches as the round-static batch cache (iid partition without
    # resampling: the engine reuses one batch tree every round, this one)
    hand_over(engine, trainable, frozen, sizes)
    engine._static_batches = (
        engine.mesh.shard_clients(jax.tree.map(jnp.asarray, batches)),
        np.asarray(n_ex))
    del flat, trainable, frozen
    if prepare is not None:
        prepare(engine)
    run.engine = engine
    return engine


def _drive(run, num_rounds, on_round=None):
    """One ``FedEngine.run`` of ``num_rounds`` on the run's engine, fenced."""
    import jax

    engine = run.engine
    engine.cfg = run.cfg.replace(num_rounds=int(num_rounds))
    recs = []

    def cb(rec):
        recs.append(rec)
        if on_round is not None:
            on_round(rec)

    t0 = time.perf_counter()
    res = engine.run(on_round=cb)
    jax.block_until_ready(res.trainable)
    wall = time.perf_counter() - t0
    # the next run starts where this one ended (and, with donation on, from
    # the only live copy of the parameters)
    engine.trainable0 = res.trainable
    return res, recs, wall


def first_rounds(run):
    """The first rounds from the seed, through the window's own call: these
    are what the reference is compared with. Then a second warm dispatch
    (it catches a sharding-driven recompile) whose walls size the window."""
    import jax

    cell = run.cell
    n_check = cell["check"]["rounds"]
    res, recs, wall = _drive(run, n_check)
    run.k = cell["fed"].get("rounds_per_dispatch", 1) if recs[0].fused else 1
    run.first_records = [_rec_dict(r) for r in recs]
    run.after_first = jax.device_get(res.trainable)  # host copy: no device memory held
    run.first_wall = wall
    del res
    res, recs, wall = _drive(run, max(run.k, 2))
    run.warm_records = [_rec_dict(r) for r in recs]
    run.round_wall = float(np.mean([r.wall_s for r in recs]))
    run.warm_wall = wall
    del res


def window(run):
    """The measured window: one ``FedEngine.run`` sized from the warm rounds
    to last about ``--seconds`` in whole dispatches. With ``--trace 1`` the
    profiler brackets a few dispatches in its middle."""
    import jax

    k = run.k
    n_disp = max(2, int(round(run.seconds / (run.round_wall * k))))
    tr = run.cell["trace"]
    if run.trace:
        n_disp = max(n_disp, tr["skip_dispatches"] + tr["dispatches"] + 1)
    rounds = n_disp * k
    run.trace_dir = os.path.join(run.out_dir, "trace")
    shutil.rmtree(run.trace_dir, ignore_errors=True)
    state = {"disp": 0, "t_on": None, "t_off": None, "stop_cost": 0.0, "start_cost": 0.0}

    def on_round(rec):
        if not run.trace or (rec.round + 1) % k:
            return
        state["disp"] += 1
        if state["disp"] == tr["skip_dispatches"]:
            t = time.perf_counter()
            jax.profiler.start_trace(run.trace_dir)
            state["t_on"] = time.perf_counter()
            state["start_cost"] = state["t_on"] - t
        elif state["disp"] == tr["skip_dispatches"] + tr["dispatches"]:
            state["t_off"] = time.perf_counter()
            jax.profiler.stop_trace()
            state["stop_cost"] = time.perf_counter() - state["t_off"]

    run.counting = True
    wall0 = time.time()
    res, recs, wall = _drive(run, rounds, on_round)
    run.counting = False
    run.setup_s = wall0 - run.t_process_start
    run.window_wall = wall
    run.window_rounds = rounds
    run.window_records = [_rec_dict(r) for r in recs]
    run.phases = res.metrics.phases
    run.ledger_summary = res.metrics.ledger or {}
    run.chain_len = len(run.engine.ledger) if run.engine.ledger is not None else 0
    run.bracket = state
    # what the program holds its frozen base in once the window has run
    run.frozen_dtypes = (None if run.engine.frozen is None else
                         [str(x.dtype) for x in jax.tree.leaves(run.engine.frozen)])
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    run.memory_stats = {k: int(v) for k, v in stats[0].items() if isinstance(v, (int, float))}
    # the fullest chip's peak: live arrays (``peak_bytes_in_use``) and what
    # the loaded programs reserve for their temporaries
    # (``peak_bytes_reserved``). The backend keeps the two apart, and a
    # training step's memory is nearly all of the second kind.
    run.memory_live_bytes = int(max((s.get("peak_bytes_in_use", 0) for s in stats), default=0))
    run.memory_peak_bytes = int(max(
        (s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0) for s in stats),
        default=0))
    del res
    run.device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes,
                  "memory_live_peak_bytes": run.memory_live_bytes}


def release(run):
    """Free the program's state before the reference runs on the chip."""
    import jax

    from bcfl_tpu.fed import client_step

    run.engine = None
    client_step.clear_program_cache()
    gc.collect()
    jax.clear_caches()
    gc.collect()


def reference_check(run):
    """The family's reference over the first rounds, in float32 and once
    more in the stated precision, and the comparison. Returns the judged
    rows, ``correct`` and the notes. The harness holds no weights here: the
    family makes them from the seed and decides what is on the device at
    once."""
    from benchmarks import compare, families
    from benchmarks.reference import gate

    sizes = run.sizes
    fam = families.of(sizes)
    clients = run.cell["traffic"]["clients"]
    n = run.cell["check"]["rounds"]
    masks = [r["mask"] for r in run.first_records[:n]]
    t0 = time.perf_counter()
    ref = fam.reference(sizes, run.seed, run.batches, masks, run.n_ex)
    # the same rounds once more in the precision the configuration states
    stated = fam.reference(sizes, run.seed, run.batches, masks, run.n_ex,
                           precision=fam.precisions(sizes)[0])
    run.reference_s = time.perf_counter() - t0
    prog = fam.from_program(run.after_first, sizes)
    recs = run.first_records + run.warm_records + run.window_records
    values, notes = compare.numbers(
        [r["train_loss"] for r in run.first_records[:n]], ref["losses"], prog,
        ref["trained"], ref["start"], ref["grad_norms"], recs,
        bool(run.ledger_summary.get("chain_ok", 0.0) == 1.0), run.chain_len,
        clients, run.compile_events, stated=stated["trained"],
        expected_mask=gate.expected_mask(run.cell.get("gate"), clients, run.seed))
    if run.frozen_dtypes is not None:
        # a frozen base held in another type than the configuration states
        # costs other memory and is another model: leaves off it, limit 0.
        # ``hand_over`` refuses the engine's own draw in another type; this
        # reads the base as the window left it, so it also sees a program
        # that converts its base once it runs
        values["frozen_leaves_off_stated_dtype"] = float(sum(
            1 for d in run.frozen_dtypes if d != sizes["training"]["param_dtype"]))
    rows, ok = compare.judge(values, run.cell["limits"])
    run.ref_losses = [float(x) for x in ref["losses"]]
    return rows, ok, notes


def count_compiles(run):
    """Count every program lowered while the window runs (there should be
    none: set-up warms every shape the window uses)."""
    import jax

    def listener(name, _dur, **_kw):
        if run.counting and name.endswith("jaxpr_to_mlir_module_duration"):
            run.compile_events += 1

    jax.monitoring.register_event_duration_secs_listener(listener)


# --------------------------------------------------------- metrics and trace

def reduce_trace(run):
    """The traced bracket as numbers (None without a device trace): busy
    time a device, the first device's time by scope (``scopes``, ``op_names``:
    trace_reduce.scope_table) and its longest idle gaps named by the
    engine's innermost ``fed.*`` host span."""
    from benchmarks import trace_reduce as tr

    b = run.bracket
    if not run.trace or b["t_on"] is None or b["t_off"] is None:
        return None
    t0 = time.perf_counter()
    path = tr.find_xplane(run.trace_dir)
    raw = tr.load_xplane(path)
    log(f"[bench] trace {os.path.getsize(path) / 1e6:.1f} MB, "
        f"{sum(len(v) for v in raw['devices'].values())} device operations, "
        f"{len(raw['host'])} engine spans, read in {time.perf_counter() - t0:.1f}s")
    if not raw["devices"]:
        return None
    per_dev = {name: tr.reduce_device(ops) for name, ops in raw["devices"].items()}
    first = sorted(per_dev)[0]
    window_s = b["t_off"] - b["t_on"]
    rounds = run.cell["trace"]["dispatches"] * run.k
    out = {
        "window_s": window_s,
        "rounds": rounds,
        "devices": per_dev,
        "first_device": first,
        "busy_s": float(np.mean([d["busy_s"] for d in per_dev.values()])),
        "worst_idle_pct": 100.0 * (1.0 - min(d["busy_s"] for d in per_dev.values()) / window_s),
        # by scope and HLO name where the trace names scopes, else by HLO name
        "device_ops": tr.op_totals(raw["devices"][first], by_scope=raw["op_names"]),
        "idle_gaps": tr.name_gaps(tr.gaps(per_dev[first]["busy"]), tr.host_spans(raw["host"])),
        "scopes": None, "op_names": None,
    }
    if raw["op_names"]:
        out.update(tr.scope_table(tr.leaves(raw["devices"][first]), rounds))
        ranked = sorted(out["scopes"].items(), key=lambda kv: -kv[1])
        log(f"[bench] scopes, ms a round: {json.dumps({k: round(v, 3) for k, v in ranked})}; "
            f"together {sum(out['scopes'].values()):.3f} of the device's "
            f"{1e3 * per_dev[first]['busy_s'] / rounds:.3f} busy")
    else:
        out["scopes_error"] = (
            f"no device operation of the trace carries the {tr.OP_NAME_STAT!r} stat in its "
            "metadata: this libtpu names an operation's op_name otherwise, or the profiler "
            "wrote none (benchmarks/trace_reduce.py, OP_NAME_STAT)")
    return out


def context(run, trace):
    """What a per-layer reader may read."""
    from benchmarks import traffic, yardstick

    b = run.bracket
    bracket_s = 0.0
    bracket_rounds = 0
    if run.trace and b["t_on"] is not None and b["t_off"] is not None:
        bracket_s = (b["t_off"] - b["t_on"]) + b["start_cost"] + b["stop_cost"]
        bracket_rounds = run.cell["trace"]["dispatches"] * run.k
    t = run.cell["traffic"]
    tokens_round = traffic.tokens_per_round(t)
    steady = (tokens_round * (run.window_rounds - bracket_rounds)
              / (run.window_wall - bracket_s) / run.cell["chips"])
    return {
        "cell": run.cell, "sizes": run.sizes, "seq": t["seq"], "chips": run.cell["chips"],
        "records": run.window_records, "phases": run.phases,
        "rounds": run.window_rounds, "k": run.k,
        "tokens_per_s_per_chip": steady,
        "device_kind": run.device["kind"], "platform": run.device["platform"],
        "memory_peak_bytes": run.memory_peak_bytes,
        "trace": trace, "trace_dir": run.trace_dir, "yardstick": yardstick,
    }


def per_layer(run, bench, trace):
    """The cell's per-layer metrics, each from its reader. A reader that
    finds nothing to read returns None and the line leaves the metric out."""
    ctx = context(run, trace)
    metrics = {}
    for m in metrics_for(bench, run.cell["name"], "per_layer"):
        spec = load_json("metrics", m["name"] + ".json")
        value = load_reader(spec["reader"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run_cell(cell_name, seed, seconds, trace, plumbing=False, out_dir=None,
             t_process_start=None, prepare=None, bench=None):
    """A whole run but for the look for a chip; returns the result object
    (the last line of standard output) and prints the compared numbers
    beside their limits on standard error."""
    from benchmarks import traffic

    bench = bench or load_benchmark()
    cell, sizes = load_cell(cell_name, plumbing)
    out_dir = out_dir or os.path.join(ROOT, "bench_out", cell_name)
    os.makedirs(out_dir, exist_ok=True)
    run = Run(cell, sizes, seed, seconds, trace, plumbing, out_dir,
              t_process_start or time.time())
    count_compiles(run)
    t_a = time.time()
    setup_engine(run, prepare)
    t_b = time.time()
    first_rounds(run)
    t_c = time.time()
    run.stages = {"process_start_to_harness": t_a - run.t_process_start,
                  "weights_traffic_engine": t_b - t_a, "first_rounds": run.first_wall,
                  "second_warm_dispatch": run.warm_wall, "first_and_warm": t_c - t_b}
    window(run)
    log(f"[bench] set-up {run.setup_s:.1f}s {json.dumps({k: round(v, 1) for k, v in run.stages.items()})}; "
        f"window {run.window_rounds} rounds in {run.window_wall:.2f}s; "
        f"peak {run.memory_peak_bytes / 1e9:.2f} GB (live arrays {run.memory_live_bytes / 1e9:.2f})")
    release(run)
    tokens = traffic.tokens_per_round(cell["traffic"]) * run.window_rounds
    e2e = {"tokens_per_s_per_chip": tokens / run.window_wall / cell["chips"],
           "setup_s": run.setup_s}
    if trace:
        # the readers run while the trace is still on disk
        tr = reduce_trace(run)
        metrics = per_layer(run, bench, tr)
    else:
        tr = None
        metrics = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                   for m in metrics_for(bench, cell_name, "end_to_end")}
    shutil.rmtree(run.trace_dir, ignore_errors=True)
    rows, ok, notes = reference_check(run)

    records = run.window_records
    failed = sum(1 for r in records
                 if not np.isfinite(r["train_loss"]) or r["auth"] is None
                 or any(a != 1.0 for a in r["auth"]))
    if run.ledger_summary.get("chain_ok", 0.0) != 1.0:
        failed = len(records)
    device = dict(run.device)
    result = {"correct": bool(ok), "attempted": len(records), "failed": int(failed),
              "metrics": metrics, "device": device}
    if plumbing:
        result["plumbing_only"] = True
    if trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
        if tr["scopes"]:
            result["breakdown"]["scopes_ms_per_round"] = sorted(
                ([k, v] for k, v in tr["scopes"].items()), key=lambda kv: -kv[1])[:10]
    compared = {name: {"value": v, "limit": lim, "ok": good} for name, v, lim, good in rows}
    detail = {
        "workload": cell_name, "seed": run.seed, "trace": int(trace), "seconds": seconds,
        "compared": compared, "notes": notes, "reference_s": run.reference_s,
        "losses": {"program": [r["train_loss"] for r in run.first_records],
                   "reference": run.ref_losses},
        "masks": [r["mask"] for r in run.first_records],
        "round_walls_s": [r["wall_s"] for r in run.window_records],
        "window": {"rounds": run.window_rounds, "wall_s": run.window_wall, "k": run.k,
                   "round_wall_warm_s": run.round_wall, "first_wall_s": run.first_wall},
        "setup_stages_s": run.stages, "memory_stats": run.memory_stats,
        # the whole table; the result's line carries its ten largest rows
        "scopes_ms_per_round": tr["scopes"] if tr else None,
        "result": result,
    }
    with open(os.path.join(out_dir, f"run-{run.seed}-t{int(trace)}.json"), "w") as f:
        json.dump(detail, f, indent=1, default=str)
    log(f"[bench] reference {run.reference_s:.1f}s; worst leaf {notes['dparam_worst_leaf']}; "
        f"left out {notes['leaves_left_out']}")
    for name, v, lim, good in rows:
        log(f"[compared] {name} = {v} limit {lim} {'ok' if good else 'FAILED'}")
    result["compared"] = compared  # last in the line, as the contract asks
    return result
