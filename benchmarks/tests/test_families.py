"""The model family is found by the name in the configuration's file, and
asked for everything that depends on the model."""

import pytest

from benchmarks import families, harness, yardstick


def test_family_is_found_by_name_and_has_the_whole_interface():
    for c in harness.load_benchmark()["configs"]:
        sizes = harness.load_json("configs", c["name"] + ".json")
        assert sizes["family"] in families.present()
        fam = families.of(sizes)
        assert fam is families.load(sizes["family"])
        assert all(callable(getattr(fam, f)) for f in families.INTERFACE)


def test_unknown_family_is_an_error_that_names_those_present():
    with pytest.raises(KeyError, match="encoder"):
        families.load("no-such-family")
    with pytest.raises(KeyError, match="no-such-family"):
        families.of({"name": "x", "family": "no-such-family"})
    with pytest.raises(KeyError, match="names no"):
        families.of({"name": "x"})


def test_a_package_that_lacks_part_of_the_interface_is_refused(tmp_path, monkeypatch):
    pkg = tmp_path / "half"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("def program(sizes):\n    return {}\n")
    monkeypatch.setattr(families, "HERE", str(tmp_path))
    monkeypatch.setattr(families, "__path__", [str(tmp_path)], raising=False)
    with pytest.raises(AttributeError, match="make_weights"):
        families.load("half")


def test_the_encoder_maps_its_configurations_keys_to_the_programs():
    _, sizes = harness.load_cell("albert-base.fedavg-s128")
    assert families.of(sizes).program(sizes) == {
        "model": "albert-base", "vocab_size": 30000, "num_labels": 2}
    cell, sizes = harness.load_cell("bert-base.fedavg-s128", plumbing=True)
    cfg = harness.build_cfg(cell, sizes, 7, None)
    assert (cfg.model, cfg.vocab_size, cfg.num_labels, cfg.task) == (
        "tiny-bert", 8192, 2, "classification")
    assert families.of(sizes).precisions(sizes) == ("bf16+act", "fp8+act")


def test_the_yardstick_asks_the_family():
    _, sizes = harness.load_cell("bert-base.fedavg-s128")
    fam = families.of(sizes)
    assert yardstick.mfu_pct(1e5, sizes, 128, "TPU v5 lite") == (
        100.0 * 1e5 * fam.train_flops_per_token(sizes, 128, None) / 197e12)


# ------------------------------------------------------------ the hand-over

class _Mesh:
    def replicate(self, tree):
        return tree


def _engine(trainable, frozen=None):
    import types

    return types.SimpleNamespace(trainable0=trainable, frozen=frozen, mesh=_Mesh())


def _tree(dtype, shape=(2, 3)):
    import jax.numpy as jnp

    return {"layer": {"kernel": jnp.ones(shape, dtype), "bias": jnp.zeros(shape[-1:], dtype)}}


def test_hand_over_places_the_familys_trees_where_they_are_the_programs():
    import jax.numpy as jnp

    sizes = {"training": {"param_dtype": "float32"}}
    e = _engine(_tree("float32"))
    mine = _tree("float32")
    mine["layer"]["kernel"] = mine["layer"]["kernel"] * 5
    harness.hand_over(e, mine, None, sizes)
    assert float(e.trainable0["layer"]["kernel"][0, 0]) == 5.0 and e.frozen is None
    # under a frozen base: the base in the stated type, what is trained in
    # the type the program draws it in
    sizes = {"training": {"param_dtype": "bfloat16"}}
    e = _engine({"q": {"a": jnp.ones((3, 2), "bfloat16")}}, _tree("bfloat16"))
    base = _tree("bfloat16")
    base["layer"]["bias"] = base["layer"]["bias"] + 1
    harness.hand_over(e, {"q": {"a": jnp.full((3, 2), 2, "bfloat16")}}, base, sizes)
    assert float(e.trainable0["q"]["a"][0, 0]) == 2.0 and float(e.frozen["layer"]["bias"][0]) == 1.0
    assert e.frozen["layer"]["kernel"].dtype == jnp.bfloat16


@pytest.mark.parametrize("case,match", [
    ("family_in_another_type", "not the configuration's"),
    ("family_in_another_shape", "not the configuration's"),
    ("program_off_the_stated_type", "where the configuration states float32"),
    ("frozen_base_off_the_stated_type", "where the configuration states bfloat16"),
    ("adapters_not_as_the_program_draws_them", "not the configuration's"),
    ("no_frozen_tree_from_the_family", "hands over no frozen tree"),
    ("a_frozen_tree_the_program_has_not", "hands over a frozen tree"),
])
def test_hand_over_raises_and_substitutes_nothing(case, match):
    """A program whose trees or types are not the configuration's is refused
    at set-up, as the parent refused it: the harness never places the
    family's type over the program's."""
    import jax.numpy as jnp

    f32, bf16 = {"training": {"param_dtype": "float32"}}, {"training": {"param_dtype": "bfloat16"}}
    adapters = lambda d: {"q": {"a": jnp.ones((3, 2), d)}}  # noqa: E731
    e, mine, frozen, sizes = {
        "family_in_another_type": (_engine(_tree("float32")), _tree("bfloat16"), None, f32),
        "family_in_another_shape": (_engine(_tree("float32")), _tree("float32", (2, 4)), None, f32),
        "program_off_the_stated_type": (_engine(_tree("bfloat16")), _tree("bfloat16"), None, f32),
        "frozen_base_off_the_stated_type": (
            _engine(adapters("float32"), _tree("float32")), adapters("float32"), _tree("float32"), bf16),
        "adapters_not_as_the_program_draws_them": (
            _engine(adapters("bfloat16"), _tree("bfloat16")), adapters("float32"), _tree("bfloat16"), bf16),
        "no_frozen_tree_from_the_family": (
            _engine(adapters("bfloat16"), _tree("bfloat16")), adapters("bfloat16"), None, bf16),
        "a_frozen_tree_the_program_has_not": (
            _engine(_tree("float32")), _tree("float32"), _tree("float32"), f32),
    }[case]
    own = (e.trainable0, e.frozen)
    with pytest.raises(RuntimeError, match=match):
        harness.hand_over(e, mine, frozen, sizes)
    assert e.trainable0 is own[0] and e.frozen is own[1]
