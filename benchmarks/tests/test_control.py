"""The control comes out as not correct: the reference with its matrix
operands one precision below the stated bfloat16 (an 8-bit float inside a
bfloat16 pipeline), put in the program's place, at a size a test run can
hold. The stated precision itself, put in the program's place, is correct.
On the chip at the cells' own sizes: PERF.md, section 2."""

import pytest

from benchmarks import compare, families, harness, traffic


def numbers(cell_name, seed, which):
    cell, sizes = harness.load_cell(cell_name, plumbing=True)
    t = cell["traffic"]
    fam = families.of(sizes)
    batches, n_ex = traffic.make(t, sizes["vocab_size"], sizes["num_labels"], seed)
    masks = [[1.0] * t["clients"]] * cell["check"]["rounds"]
    start = None

    def ref(p):
        nonlocal start
        r = fam.reference(sizes, seed, batches, masks, n_ex, precision=p)
        start = r["start"]
        return r["losses"], r["trained"], r["grad_norms"]

    ref_losses, ref_params, gnorm = ref(None)
    stated_p, control_p = fam.precisions(sizes)
    precision = {"stated": stated_p, "control": control_p}[which]
    stated = ref(stated_p)[1]
    losses, params, _ = (None, stated, None) if which == "stated" else ref(precision)
    losses = losses or ref_losses
    recs = [{"auth": [1.0], "train_loss": x, "mask": masks[0]} for x in losses]
    values, _ = compare.numbers(losses, ref_losses, params, ref_params, start,
                                gnorm, recs, True, len(recs) * t["clients"], t["clients"], 0,
                                stated=stated, expected_mask=masks[0])
    return compare.judge(values, cell["limits"])


@pytest.mark.parametrize("cell_name", ["bert-base.fedavg-s128", "albert-base.guarded-s128"])
@pytest.mark.parametrize("seed", [11, 12, 2147483659])
def test_control_is_not_correct(cell_name, seed):
    rows, ok = numbers(cell_name, seed, "control")
    assert not ok
    assert [name for name, _, _, good in rows if not good] == ["turn_vs_stated"]


@pytest.mark.parametrize("cell_name", ["bert-base.fedavg-s128"])
def test_stated_precision_is_correct(cell_name):
    rows, ok = numbers(cell_name, 11, "stated")
    assert ok, rows
