"""The reference against the program at a tiny size on the CPU, both in
float32: same weights, same batches, same dropout draws give the same
losses and the same parameters. This pins the one thing the reference cannot
derive from the mathematics, the dropout draws (families/encoder/dropout.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families, harness, traffic
from benchmarks.families.encoder import train as ref_train
from benchmarks.families.encoder import weights

SEED = 3000000011


@pytest.mark.parametrize("cell_name", ["bert-base.fedavg-s128", "albert-base.fedavg-s128"])
def test_reference_follows_the_program_in_float32(cell_name, tmp_path):
    cell, sizes = harness.load_cell(cell_name, plumbing=True)
    sizes = dict(sizes, training=dict(sizes["training"], compute_dtype="float32"))
    run = harness.Run(cell, sizes, SEED, 0.0, False, True, str(tmp_path), 0.0)
    harness.setup_engine(run)
    res, recs, _ = harness._drive(run, cell["check"]["rounds"])
    assert recs[0].fused
    prog = weights.from_program(jax.device_get(res.trainable), sizes)
    start = weights.make(sizes, SEED)
    losses, ref, gnorm = ref_train.run_rounds(
        start, sizes, sizes["training"], jax.tree.map(jnp.asarray, run.batches), SEED,
        [r.mask for r in recs], run.n_ex)
    np.testing.assert_allclose([r.train_loss for r in recs], losses, rtol=2e-6)
    gmed = np.median([float(v) for v in gnorm.values()])
    for name in ref:
        if float(gnorm[name]) < 1e-3 * gmed:
            continue  # a key's bias: no gradient under softmax, round-off only
        d_ref = np.asarray(ref[name]) - np.asarray(start[name])
        d_prog = np.asarray(prog[name]) - np.asarray(start[name])
        assert np.linalg.norm(d_prog - d_ref) <= 5e-3 * np.linalg.norm(d_ref), name


def test_weights_are_the_seed_and_nothing_else():
    _, sizes = harness.load_cell("albert-base.fedavg-s128", plumbing=True)
    a, b, c = weights.make(sizes, 7), weights.make(sizes, 7), weights.make(sizes, 2 ** 31 + 5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["pool.w"], c["pool.w"])
    assert weights.count(sizes) == sum(int(np.prod(v.shape)) for v in a.values())
    back = weights.from_program(weights.to_program(a, sizes), sizes)
    assert all(np.array_equal(a[k], back[k]) for k in a)


def test_published_parameter_counts():
    for name, n in (("bert-base", 109_483_778), ("albert-base", 11_685_122)):
        assert weights.count(harness.load_json("configs", name + ".json")) == n


def test_traffic_has_the_same_sizes_for_every_seed():
    cell, sizes = harness.load_cell("bert-base.fedavg-s128")
    t = cell["traffic"]
    b1, n1 = traffic.make(t, sizes["vocab_size"], 2, 1)
    b2, n2 = traffic.make(t, sizes["vocab_size"], 2, 2 ** 31 + 11)
    l1, l2 = b1["mask"].sum(-1).ravel(), b2["mask"].sum(-1).ravel()
    assert sorted(l1) == sorted(l2) and not np.array_equal(l1, l2)
    assert not np.array_equal(b1["ids"], b2["ids"])
    assert b1["ids"].shape == (t["clients"], t["local_batches"], t["batch"], t["seq"])
    assert b1["ids"].max() < sizes["vocab_size"] and (n1 == t["local_batches"] * t["batch"]).all()
    rows = b1["ids"].reshape(-1, t["seq"])
    assert len({r.tobytes() for r in rows}) == len(rows)  # rows that all differ


def test_the_family_seam_gives_the_same_reference():
    """Through the family's interface (what the harness and calibrate.py
    call) the reference is the encoder's own ``run_rounds``, digit for digit."""
    cell, sizes = harness.load_cell("albert-base.fedavg-s128", plumbing=True)
    fam = families.of(sizes)
    t = cell["traffic"]
    batches, n_ex = traffic.make(t, sizes["vocab_size"], sizes["num_labels"], SEED)
    masks = [[1.0] * t["clients"]] * 2
    got = fam.reference(sizes, SEED, batches, masks, n_ex)
    start = weights.make(sizes, SEED)
    losses, ref, gnorm = ref_train.run_rounds(
        start, sizes, sizes["training"], jax.tree.map(jnp.asarray, batches), SEED, masks, n_ex)
    assert got["losses"] == [float(x) for x in losses]
    for name in ref:
        assert np.array_equal(got["trained"][name], np.asarray(ref[name]))
        assert np.array_equal(got["start"][name], np.asarray(start[name]))
        assert float(got["grad_norms"][name]) == float(gnorm[name])
    tree, frozen = fam.to_program(start, sizes)
    assert frozen is None and set(fam.from_program(tree, sizes)) == set(start)
