"""The encoder family's weights: made on the device in ONE jitted call from the
seed, float32 (the type the configuration states for parameters), in the
reference's flat naming (benchmarks/families/encoder/model.py). ``to_program``
lays the same arrays out as the program's parameter tree, so the program and
the reference start from the same numbers and neither takes the other's."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

WEIGHT_LANE = 0x5EED  # folded into the seed's key: a stream of its own


def _shapes(sizes):
    """``{name: (shape, kind)}`` with kind in normal / zeros / ones."""
    H, E, F = sizes["hidden_size"], sizes["embedding_size"], sizes["intermediate_size"]
    s = {
        "emb.word": ((sizes["vocab_size"], E), "normal"),
        "emb.pos": ((sizes["max_position_embeddings"], E), "normal"),
        "emb.type": ((sizes["type_vocab_size"], E), "normal"),
        "emb.ln.g": ((E,), "ones"), "emb.ln.b": ((E,), "zeros"),
    }
    if E != H:
        s["emb.proj.w"] = ((E, H), "normal")
        s["emb.proj.b"] = ((H,), "zeros")
    layers = 1 if sizes["share_layers"] else sizes["num_hidden_layers"]
    for i in range(layers):
        n = f"L{i}"
        for m in ("q", "k", "v", "o"):
            s[f"{n}.{m}.w"] = ((H, H), "normal")
            s[f"{n}.{m}.b"] = ((H,), "zeros")
        s[f"{n}.ln1.g"] = ((H,), "ones"); s[f"{n}.ln1.b"] = ((H,), "zeros")
        s[f"{n}.f1.w"] = ((H, F), "normal"); s[f"{n}.f1.b"] = ((F,), "zeros")
        s[f"{n}.f2.w"] = ((F, H), "normal"); s[f"{n}.f2.b"] = ((H,), "zeros")
        s[f"{n}.ln2.g"] = ((H,), "ones"); s[f"{n}.ln2.b"] = ((H,), "zeros")
    s["pool.w"] = ((H, H), "normal"); s["pool.b"] = ((H,), "zeros")
    s["cls.w"] = ((H, sizes["num_labels"]), "normal")
    s["cls.b"] = ((sizes["num_labels"],), "zeros")
    return s


def count(sizes):
    n = 0
    for shape, _ in _shapes(sizes).values():
        k = 1
        for d in shape:
            k *= d
        n += k
    return n


@functools.partial(jax.jit, static_argnames=("spec", "std"))
def _make(seed, spec, std):
    key = jax.random.fold_in(jax.random.key(seed), WEIGHT_LANE)
    out = {}
    for i, (name, shape, kind) in enumerate(spec):
        if kind == "normal":
            out[name] = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        elif kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jnp.zeros(shape, jnp.float32)
    return out


def make(sizes, seed):
    """Flat float32 parameter dict from ``seed`` (any int up to 2**32)."""
    spec = tuple((n, sh, k) for n, (sh, k) in _shapes(sizes).items())
    return _make(jnp.uint32(int(seed) % (2 ** 32)), spec, float(sizes["initializer_range"]))


# --- the program's layout ---------------------------------------------------
# flax names of bcfl_tpu/models/bert.py: DenseGeneral keeps heads apart
# ([H, heads, head_dim] / [heads, head_dim, H]); the arrays are the same.

def _layer_to_program(p, n, nh, hd):
    H = nh * hd
    att = {}
    for ours, theirs in (("q", "query"), ("k", "key"), ("v", "value")):
        att[theirs] = {"kernel": p[f"{n}.{ours}.w"].reshape(H, nh, hd),
                       "bias": p[f"{n}.{ours}.b"].reshape(nh, hd)}
    att["out"] = {"kernel": p[f"{n}.o.w"].reshape(nh, hd, H), "bias": p[f"{n}.o.b"]}
    return {
        "attention": att,
        "attention_norm": {"scale": p[f"{n}.ln1.g"], "bias": p[f"{n}.ln1.b"]},
        "mlp_in": {"kernel": p[f"{n}.f1.w"], "bias": p[f"{n}.f1.b"]},
        "mlp_out": {"kernel": p[f"{n}.f2.w"], "bias": p[f"{n}.f2.b"]},
        "mlp_norm": {"scale": p[f"{n}.ln2.g"], "bias": p[f"{n}.ln2.b"]},
    }


def to_program(p, sizes):
    nh = sizes["num_attention_heads"]
    hd = sizes["hidden_size"] // nh
    emb = {
        "word": {"embedding": p["emb.word"]},
        "position": {"embedding": p["emb.pos"]},
        "type": {"embedding": p["emb.type"]},
        "norm": {"scale": p["emb.ln.g"], "bias": p["emb.ln.b"]},
    }
    if "emb.proj.w" in p:
        emb["projection"] = {"kernel": p["emb.proj.w"], "bias": p["emb.proj.b"]}
    enc = {"embeddings": emb}
    if sizes["share_layers"]:
        enc["layer_shared"] = _layer_to_program(p, "L0", nh, hd)
    else:
        for i in range(sizes["num_hidden_layers"]):
            enc[f"layer_{i}"] = _layer_to_program(p, f"L{i}", nh, hd)
    return {
        "encoder": enc,
        "pooler": {"kernel": p["pool.w"], "bias": p["pool.b"]},
        "classifier": {"kernel": p["cls.w"], "bias": p["cls.b"]},
    }


def from_program(tree, sizes):
    """The program's tree back in the flat naming (reshapes only)."""
    H = sizes["hidden_size"]
    enc = tree["encoder"]
    e = enc["embeddings"]
    p = {"emb.word": e["word"]["embedding"], "emb.pos": e["position"]["embedding"],
         "emb.type": e["type"]["embedding"], "emb.ln.g": e["norm"]["scale"],
         "emb.ln.b": e["norm"]["bias"]}
    if "projection" in e:
        p["emb.proj.w"] = e["projection"]["kernel"]
        p["emb.proj.b"] = e["projection"]["bias"]
    names = (["layer_shared"] if sizes["share_layers"]
             else [f"layer_{i}" for i in range(sizes["num_hidden_layers"])])
    for i, ln in enumerate(names):
        L, n = enc[ln], f"L{i}"
        for ours, theirs in (("q", "query"), ("k", "key"), ("v", "value")):
            p[f"{n}.{ours}.w"] = L["attention"][theirs]["kernel"].reshape(H, H)
            p[f"{n}.{ours}.b"] = L["attention"][theirs]["bias"].reshape(H)
        p[f"{n}.o.w"] = L["attention"]["out"]["kernel"].reshape(H, H)
        p[f"{n}.o.b"] = L["attention"]["out"]["bias"]
        p[f"{n}.ln1.g"] = L["attention_norm"]["scale"]; p[f"{n}.ln1.b"] = L["attention_norm"]["bias"]
        p[f"{n}.f1.w"] = L["mlp_in"]["kernel"]; p[f"{n}.f1.b"] = L["mlp_in"]["bias"]
        p[f"{n}.f2.w"] = L["mlp_out"]["kernel"]; p[f"{n}.f2.b"] = L["mlp_out"]["bias"]
        p[f"{n}.ln2.g"] = L["mlp_norm"]["scale"]; p[f"{n}.ln2.b"] = L["mlp_norm"]["bias"]
    p["pool.w"] = tree["pooler"]["kernel"]; p["pool.b"] = tree["pooler"]["bias"]
    p["cls.w"] = tree["classifier"]["kernel"]; p["cls.b"] = tree["classifier"]["bias"]
    return p
