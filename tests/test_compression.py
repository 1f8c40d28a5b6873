"""Communication-compression contracts (COMPRESSION.md).

Codec-level: round-trip error bounds per dtype, top-k exactness + error-
feedback residual algebra, bytes accounting, payload corruption semantics.
Engine-level: ``compress=none`` bit-identical to the uncompressed programs,
error-feedback convergence parity on the tiny model, codec params keying the
program cache (no silent cross-codec reuse), zero per-round retraces with
compression on, and the
chaos-matrix rows at ``int8+topk`` — ledger auth passes on clean compressed
rounds and fails on transport-corrupted compressed payloads, on both the
per-round and fused paths, plus bit-identical compressed crash/resume
(error-feedback state rides the checkpoint).

Marker ``compression``; the whole file is fast/`not slow`, so tier-1 runs it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bcfl_tpu.compression import (
    CompressionConfig,
    corrupt_payload,
    decode_tree,
    encode_tree,
    payload_nbytes,
    roundtrip,
    zero_residual,
)
from bcfl_tpu.config import FedConfig, LedgerConfig, PartitionConfig
from bcfl_tpu.faults import FaultPlan, SimulatedCrash
from bcfl_tpu.fed.client_step import build_programs
from bcfl_tpu.fed.engine import FedEngine

pytestmark = pytest.mark.compression

INT8_TOPK = CompressionConfig(kind="int8+topk", topk_frac=0.1)


def _tiny(**kw):
    base = dict(
        dataset="synthetic", model="tiny-bert", num_clients=4, num_rounds=3,
        seq_len=16, batch_size=4, max_local_batches=2, vocab_size=512,
        partition=PartitionConfig(kind="iid", iid_samples=8),
    )
    base.update(kw)
    return FedConfig(**base)


def _tree(seed=0):
    k = jax.random.key(seed)
    return {
        "w": jax.random.normal(k, (4, 37, 5)) * 3.0,
        "b": jax.random.normal(jax.random.fold_in(k, 1),
                               (4, 9)).astype(jnp.bfloat16),
    }


def _zeros_like_f32(tree):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


# ------------------------------------------------------------------- codecs


def test_config_validation():
    with pytest.raises(ValueError, match="kind"):
        CompressionConfig(kind="fp4")
    with pytest.raises(ValueError, match="chunk"):
        CompressionConfig(kind="int8", chunk=0)
    with pytest.raises(ValueError, match="topk_frac"):
        CompressionConfig(kind="topk", topk_frac=0.0)
    assert not CompressionConfig().enabled
    # faithful mode has no update exchange to compress — rejected loudly
    with pytest.raises(ValueError, match="faithful"):
        _tiny(mode="serverless", faithful=True, compression=INT8_TOPK)


@pytest.mark.parametrize("stochastic", [False, True])
def test_int8_roundtrip_error_bound_per_dtype(stochastic):
    """Per element the int8 error is bounded by the chunk's quantization
    quantum: max|x_chunk| / 127 (one quantum for stochastic rounding, half
    for deterministic), for float32 AND bfloat16 leaves (the codec casts to
    f32 first, so the bf16 leaf's bound uses its f32 view)."""
    comp = CompressionConfig(kind="int8", chunk=16, stochastic=stochastic)
    tree = _tree()
    payload = encode_tree(
        comp, jax.tree.map(lambda x: x.astype(jnp.float32), tree),
        jax.random.key(7))
    dec = decode_tree(comp, payload, tree)
    # per-leaf check with explicit chunk-local quanta
    for name in ("w", "b"):
        y = np.asarray(tree[name], np.float32).reshape(4, -1)
        d = np.asarray(dec[name], np.float32).reshape(4, -1)
        n = y.shape[1]
        pad = (-n) % comp.chunk
        yp = np.pad(y, ((0, 0), (0, pad)))
        quanta = (np.abs(yp.reshape(4, -1, comp.chunk)).max(-1)
                  / 127.0)[..., None]
        bound = (quanta if stochastic else quanta / 2.0) + 1e-7
        err = np.abs(np.pad(d, ((0, 0), (0, pad))).reshape(
            4, -1, comp.chunk) - yp.reshape(4, -1, comp.chunk))
        assert (err <= bound).all(), f"{name}: int8 error exceeds quantum"


def test_topk_exact_on_kept_and_error_feedback_residual():
    comp = CompressionConfig(kind="topk", topk_frac=0.25)
    tree = _tree(3)
    resid = _zeros_like_f32(tree)
    payload, dec, resid2 = roundtrip(comp, tree, resid, jax.random.key(0))
    for name in ("w", "b"):
        y = np.asarray(tree[name], np.float32).reshape(4, -1)
        d = np.asarray(dec[name], np.float32).reshape(4, -1)
        r = np.asarray(resid2[name], np.float32).reshape(4, -1)
        kept = d != 0.0
        # kept coordinates transmit EXACTLY; dropped mass is the residual
        np.testing.assert_array_equal(d[kept], y[kept])
        np.testing.assert_allclose(r, y - d, rtol=0, atol=0)
        k = payload[name]["v"].shape[1]
        assert kept.sum(axis=1).max() <= k
        # the kept set is the magnitude top-k: every dropped |value| is <=
        # the smallest kept |value| (per client)
        for c in range(4):
            if kept[c].any() and (~kept[c]).any():
                assert (np.abs(y[c][~kept[c]]).max()
                        <= np.abs(y[c][kept[c]]).min() + 1e-7)
    # error_feedback=False zeroes the carried state instead
    comp_no_ef = CompressionConfig(kind="topk", topk_frac=0.25,
                                   error_feedback=False)
    _, _, resid3 = roundtrip(comp_no_ef, tree, resid, jax.random.key(0))
    assert all(float(jnp.abs(x).max()) == 0.0
               for x in jax.tree.leaves(resid3))


def test_payload_bytes_accounting_and_corruption():
    tmpl = jax.tree.map(lambda x: x[0], _tree())  # unstacked template
    raw = payload_nbytes(None, tmpl)
    assert raw == 37 * 5 * 4 + 9 * 2  # f32 + bf16
    int8 = payload_nbytes(CompressionConfig(kind="int8", chunk=16), tmpl)
    assert int8 < raw / 2.5  # ~1 byte/elt + scales
    both = payload_nbytes(INT8_TOPK, tmpl)
    assert raw / both >= 4.0, "int8+topk must beat 4x on this template"
    # corruption: float parts move, int parts don't, zero row is identity
    comp = INT8_TOPK
    payload = encode_tree(
        comp, jax.tree.map(lambda x: x.astype(jnp.float32), _tree()),
        jax.random.key(0))
    clean = corrupt_payload(payload, jnp.zeros((4,)))
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(payload), jax.tree.leaves(clean)))
    hit = corrupt_payload(payload, jnp.array([0.0, 5.0, 0.0, 0.0]))
    for name, part in payload.items():
        assert np.array_equal(np.asarray(part["i"]),
                              np.asarray(hit[name]["i"]))  # ints untouched
        assert not np.array_equal(np.asarray(part["s"][1]),
                                  np.asarray(hit[name]["s"][1]))
        assert np.array_equal(np.asarray(part["s"][0]),
                              np.asarray(hit[name]["s"][0]))


# ------------------------------------------------------- fused encode path


@pytest.mark.parametrize("kind", ["int8", "topk", "int8+topk"])
@pytest.mark.parametrize("stochastic", [False, True])
def test_fused_encode_bit_identical_vs_unfused(kind, stochastic):
    """The fused encode (leaves grouped into one concatenated int8 chunk-
    grid quantize + one stacked top_k per distinct width) must produce
    BIT-identical payloads to the per-leaf reference encode — chunk
    groupings, per-leaf stochastic-rounding keys, and top-k row
    independence are all preserved, so ledger digests and checkpointed
    EF state cannot move."""
    from bcfl_tpu.compression.codecs import encode_tree_unfused

    comp = CompressionConfig(kind=kind, chunk=16, topk_frac=0.3,
                             stochastic=stochastic)
    # repeated shapes (the transformer case the grouping exists for) plus
    # odd widths, so every grouping branch is exercised
    k = jax.random.key(11)
    tree = {
        "l0": {"w": jax.random.normal(jax.random.fold_in(k, 1), (4, 37, 5)),
               "b": jax.random.normal(jax.random.fold_in(k, 2), (4, 9))},
        "l1": {"w": jax.random.normal(jax.random.fold_in(k, 3), (4, 37, 5)),
               "b": jax.random.normal(jax.random.fold_in(k, 4), (4, 9))},
        "head": jax.random.normal(jax.random.fold_in(k, 5), (4, 13)),
    }
    a = encode_tree_unfused(comp, tree, jax.random.key(7))
    b = encode_tree(comp, tree, jax.random.key(7))
    assert (jax.tree_util.tree_structure(a)
            == jax.tree_util.tree_structure(b))
    for (pa, xa), (pb, xb) in zip(
            jax.tree_util.tree_flatten_with_path(a)[0],
            jax.tree_util.tree_flatten_with_path(b)[0]):
        assert np.asarray(xa).dtype == np.asarray(xb).dtype, pa
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=str(pa))
    # and the fused payload decodes against the same template
    dec = decode_tree(comp, b, tree)
    assert (jax.tree_util.tree_structure(dec)
            == jax.tree_util.tree_structure(tree))


def test_fused_encode_collapses_per_leaf_dispatches():
    """The fusion is real, not a relabel: a tree with L leaves across D
    distinct flattened widths lowers to exactly D top_k ops (not L) and
    one int8 quantize pipeline (one concatenated max-reduce), where the
    per-leaf reference encode lowers one per leaf."""
    from bcfl_tpu.compression.codecs import encode_tree_unfused

    comp = CompressionConfig(kind="int8+topk", topk_frac=0.3)
    k = jax.random.key(0)
    tree = {f"l{i}": jax.random.normal(jax.random.fold_in(k, i), (2, 50))
            for i in range(4)}
    tree["odd"] = jax.random.normal(jax.random.fold_in(k, 9), (2, 31))

    def count(fn, prim):
        jaxpr = jax.make_jaxpr(fn)(tree, jax.random.key(0))
        return sum(1 for e in jaxpr.jaxpr.eqns if e.primitive.name == prim)

    fused = count(lambda t, kk: encode_tree(comp, t, kk), "top_k")
    unfused = count(lambda t, kk: encode_tree_unfused(comp, t, kk),
                    "top_k")
    assert unfused == 5  # one per leaf
    assert fused == 2    # one per distinct width (50, 31)


def test_fused_encode_zero_retraces_in_engine():
    """The grouped encode keeps every shape trace-time static: the dist-
    style split-phase async encoder (the seam the dist wire rides) traces
    once across rounds. (The in-graph fused-program pin is
    test_compressed_run_zero_retraces below.)"""
    from bcfl_tpu.core.mesh import client_mesh
    from bcfl_tpu.fed.client_step import build_programs
    from bcfl_tpu.models import build

    mesh = client_mesh(4)
    model = build("tiny-bert", num_labels=2, vocab_size=512)
    progs = build_programs(model, mesh, compression=INT8_TOPK)
    import jax.numpy as jnp

    tmpl = jax.jit(lambda key: model.init(
        key, jnp.ones((2, 16), jnp.int32),
        jnp.ones((2, 16), jnp.int32))["params"])(jax.random.key(0))
    stack = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (4,) + x.shape), tmpl)
    resid = progs.ef_init(tmpl)
    rngs = jax.random.key_data(jax.vmap(jax.random.key)(
        jnp.arange(4, dtype=jnp.uint32)))
    n0 = progs.encode_deltas_async._cache_size()
    for _ in range(3):
        _, resid = progs.encode_deltas_async(stack, stack, resid, rngs)
    assert progs.encode_deltas_async._cache_size() == n0 + 1


# ------------------------------------------------------- program cache keys


def test_program_cache_keys_on_codec_params():
    """Codec params are part of the program-cache key: equal configs share
    ONE program set, different codecs get distinct sets (silent cross-codec
    program reuse would ship the wrong wire format), and a disabled config
    normalizes onto the uncompressed entry — build_programs(compress=none)
    IS build_programs() (the acceptance pin for 'none is bit-identical')."""
    from bcfl_tpu.core.mesh import client_mesh
    from bcfl_tpu.models import build

    mesh = client_mesh(4)
    model = build("tiny-bert", num_labels=2, vocab_size=512)
    base = build_programs(model, mesh)
    none = build_programs(model, mesh,
                          compression=CompressionConfig(kind="none"))
    assert none is base
    a = build_programs(model, mesh, compression=INT8_TOPK)
    b = build_programs(model, mesh,
                       compression=CompressionConfig(kind="int8+topk",
                                                     topk_frac=0.1))
    assert a is b and a is not base
    c = build_programs(model, mesh,
                       compression=CompressionConfig(kind="int8+topk",
                                                     topk_frac=0.2))
    assert c is not a  # same kind, different param -> different programs
    d = build_programs(model, mesh, compression=CompressionConfig(
        kind="int8+topk", topk_frac=0.1, stochastic=False))
    assert d is not a


# ------------------------------------------------------------------- engine


def test_compress_none_engine_bit_identical():
    """A run with an explicit compress=none config must produce bit-identical
    final params to the default config — same program objects, same math."""
    a = FedEngine(_tiny(num_rounds=2)).run()
    b = FedEngine(_tiny(num_rounds=2,
                        compression=CompressionConfig(kind="none"))).run()
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert b.metrics.comms["compression_ratio"] == 1.0
    assert (b.metrics.rounds[0].bytes_on_wire
            == b.metrics.rounds[0].bytes_raw)


@pytest.mark.parametrize("mode", ["server", "serverless"])
def test_error_feedback_convergence_parity(mode):
    """int8+topk with error feedback must track the uncompressed loss
    trajectory on the tiny model: the codec drops 90% of coordinates per
    round, but the residual re-injects the dropped mass, so the final loss
    lands within tolerance of compress=none (and the wire carries >= 4x
    fewer bytes — the acceptance pair). Tolerance 0.08 ~ 2x the observed
    EF transient delay on this config (server 0.02 / serverless 0.04); a
    broken codec or residual (error accumulating instead of re-entering)
    diverges by 1e-1-to-NaN scale and still fails. The full convergence
    curve (compressed reaches the uncompressed final loss over a modestly
    longer round budget at ~9x fewer TOTAL bytes) is recorded by
    scripts/comm_overhead.py -> results/comm_overhead.json."""
    lr = 3e-4  # large enough that 4 rounds actually move the loss
    base = FedEngine(_tiny(mode=mode, num_rounds=4, learning_rate=lr)).run()
    comp = FedEngine(_tiny(mode=mode, num_rounds=4, learning_rate=lr,
                           compression=INT8_TOPK)).run()
    lb = base.metrics.rounds[-1].train_loss
    lc = comp.metrics.rounds[-1].train_loss
    assert np.isfinite(lc)
    assert abs(lb - lc) < 0.08, (lb, lc)
    r = comp.metrics.rounds[0]
    assert r.bytes_raw / r.bytes_on_wire >= 4.0
    assert comp.metrics.comms["compress"] == "int8+topk"
    # the comms model scales with actual payload bytes: compressed rounds
    # pass information faster than raw ones on the same graph
    assert (comp.metrics.rounds[-1].info_passing_sync_s
            < base.metrics.rounds[-1].info_passing_sync_s)


def test_compressed_run_zero_retraces():
    """Recompile guard for the codec params: a 3-round compressed run (with
    per-round resampled batches) must compile its hot round program exactly
    once — the EF-residual carry and codec stages are shape-static."""
    import os

    os.environ["BCFL_PROGRAM_CACHE"] = "0"
    try:
        eng = FedEngine(_tiny(
            compression=INT8_TOPK,
            partition=PartitionConfig(kind="iid", iid_samples=8,
                                      resample_each_round=True)))
        eng.run()
        assert eng.progs.server_round._cache_size() == 1
        eng2 = FedEngine(_tiny(
            mode="serverless", compression=INT8_TOPK,
            partition=PartitionConfig(kind="iid", iid_samples=8,
                                      resample_each_round=True)))
        eng2.run()
        assert eng2.progs.gossip_round._cache_size() == 1
    finally:
        os.environ.pop("BCFL_PROGRAM_CACHE", None)


# ------------------------------------------------- chaos matrix @ int8+topk


def test_chaos_ledger_auth_per_round_path():
    """Per-round split-phase path at int8+topk: clean compressed rounds pass
    chain auth; a FaultPlan-corrupted compressed payload fails it for
    exactly the corrupted clients and the round aggregates without them."""
    cfg = _tiny(mode="serverless", compression=INT8_TOPK,
                ledger=LedgerConfig(enabled=True),
                faults=FaultPlan(seed=4, corrupt_prob=0.5,
                                 corrupt_rounds=(1,)))
    res = FedEngine(cfg).run()
    assert res.metrics.rounds[0].auth == [1.0] * 4  # clean round passes
    assert res.metrics.rounds[2].auth == [1.0] * 4
    hit = [i for i, a in enumerate(res.metrics.rounds[1].auth) if a == 0.0]
    assert hit, "seeded corruption never fired"
    # the schedule says exactly these clients were corrupted
    scales = cfg.faults.transport_scales(1, 4)
    assert hit == [i for i in range(4) if scales[i] != 0.0]
    assert res.ledger.verify_chain() == -1
    for x in jax.tree.leaves(res.params):
        assert np.isfinite(np.asarray(x)).all()


def test_chaos_ledger_auth_fused_path():
    """Fused (rounds_per_dispatch) path at int8+topk: the in-graph payload
    fingerprints catch a fused-transport corruption, auth fails on the
    chain, and the clean rounds of the same dispatch still authenticate."""
    def tamper(rnd):
        return (np.array([0.0, 0.0, 1e6, 0.0], np.float32)
                if rnd == 1 else None)

    with pytest.warns(DeprecationWarning):
        eng = FedEngine(_tiny(compression=INT8_TOPK, rounds_per_dispatch=3,
                              eval_every=3,
                              ledger=LedgerConfig(enabled=True)),
                        fused_tamper=tamper)
    res = eng.run()
    assert res.metrics.rounds[0].auth == [1.0, 1.0, 1.0, 1.0]
    assert res.metrics.rounds[1].auth == [1.0, 1.0, 0.0, 1.0]
    assert res.metrics.rounds[2].auth == [1.0, 1.0, 1.0, 1.0]
    assert res.ledger.verify_chain() == -1


def test_async_compressed_round_semantics():
    """Buffered-async + compression: payloads are the delta exchange, only
    arrived clients merge, and each client's base is its OWN carry — so
    deltas stay incremental and no update mass applies twice (the residual
    re-delivers compression error only; see the _async_round note). Pins
    finiteness + that the run actually learns state per round."""
    res = FedEngine(_tiny(mode="serverless", sync="async", async_buffer=2,
                          num_rounds=4, compression=INT8_TOPK)).run()
    assert len(res.metrics.rounds) == 4
    for r in res.metrics.rounds:
        assert np.isfinite(r.train_loss)
        assert r.bytes_raw / r.bytes_on_wire >= 4.0
    for x in jax.tree.leaves(res.params):
        a = np.asarray(x)
        assert np.isfinite(a).all() and np.abs(a).max() < 1e3


def test_cli_compress_subflags_require_codec():
    from bcfl_tpu.entrypoints.__main__ import main as cli_main

    with pytest.raises(SystemExit, match="--compress"):
        cli_main(["--preset", "smoke", "--compress-topk", "0.02"])


def test_chaos_dropout_compressed_stays_finite():
    res = FedEngine(_tiny(compression=INT8_TOPK,
                          faults=FaultPlan(seed=2, dropout_prob=0.5))).run()
    assert any(r.dropped for r in res.metrics.rounds)
    for x in jax.tree.leaves(res.params):
        assert np.isfinite(np.asarray(x)).all()


def test_resume_rejects_wire_format_change(tmp_path):
    """The checkpoint records the codec identity: resuming a compressed run
    uncompressed (or under a different codec) would silently drop or
    misapply the carried error-feedback residual — refused loudly, same
    guard class as the prng-impl resume check."""
    kw = dict(checkpoint_dir=str(tmp_path / "a"), checkpoint_every=1,
              eval_every=0)
    FedEngine(_tiny(num_rounds=1, compression=INT8_TOPK, **kw)).run()
    with pytest.raises(ValueError, match="wire format"):
        FedEngine(_tiny(num_rounds=2, **kw)).run(resume=True)
    with pytest.raises(ValueError, match="wire format"):
        FedEngine(_tiny(num_rounds=2, compression=CompressionConfig(
            kind="topk", topk_frac=0.1), **kw)).run(resume=True)
    # a codec-IRRELEVANT field change must NOT refuse: pure topk never
    # consumes the int8 chunk size, so the wire format is unchanged
    kw2 = dict(checkpoint_dir=str(tmp_path / "b"), checkpoint_every=1,
               eval_every=0)
    topk = CompressionConfig(kind="topk", topk_frac=0.1, chunk=256)
    FedEngine(_tiny(num_rounds=1, compression=topk, **kw2)).run()
    res = FedEngine(_tiny(num_rounds=2, compression=CompressionConfig(
        kind="topk", topk_frac=0.1, chunk=64), **kw2)).run(resume=True)
    assert len(res.metrics.rounds) == 1  # resumed past round 0


def test_crash_resume_bit_identical_compressed(tmp_path):
    """Compressed crash/resume: the error-feedback residual rides the
    checkpoint, so crash at round 2 + resume reproduces the uninterrupted
    compressed run bit-for-bit."""
    kw = dict(compression=INT8_TOPK, num_rounds=4,
              checkpoint_every=1, eval_every=0)
    ref = FedEngine(_tiny(**kw)).run()
    cfg = _tiny(checkpoint_dir=str(tmp_path),
                faults=FaultPlan(crash_at_round=2), **kw)
    with pytest.raises(SimulatedCrash):
        FedEngine(cfg).run()
    res = FedEngine(cfg).run(resume=True)
    for a, b in zip(jax.tree.leaves(ref.params),
                    jax.tree.leaves(res.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
