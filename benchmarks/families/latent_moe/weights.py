"""Weights from the seed for the latent-attention expert decoder, a layer at
a time, and their layout as the program's trees.

Flat naming. The base (frozen, in ``training.param_dtype``):
  emb [V, H]   norm [H]   lm [H, V]
  L<i>.n1 / L<i>.n2 [H]   L<i>.qn [q_lora_rank]   L<i>.kvn [kv_lora_rank]
  L<i>.dq [H, q_lora_rank]    L<i>.uq [q_lora_rank, heads * (nope + rope)]
  L<i>.dkv [H, kv_lora_rank + rope]
  L<i>.ukv [kv_lora_rank, heads * (nope + v)]    L<i>.o [heads * v, H]
  L<i>.sg / L<i>.su [H, F_shared]   L<i>.sd [F_shared, H]    (shared expert)
  L<i>.r [H, E]                                               (router)
  L<i>.eg / L<i>.eu [G, H, F]   L<i>.ed [G, F, H]   (the G experts HELD, in
      ascending order of their index; expert e's draw depends on e and not
      on which others are held)
What is trained, in float32: for each of ``ADAPTED`` (a layer's dq, uq, dkv,
ukv, o, sg, su, sd, and lm) the adapter ``m.a`` [fan_in, r] and ``m.b``
[r, fan_out]; the model applies ``x m + (x m.a) m.b``. Both factors start
from the seed, nonzero, so both have a gradient in the first step.

Every matrix is normal(0, ``initializer_range``), every norm's scale 1.
The base never stands whole on the device here: ``make`` draws a layer on
the device and fetches it, so what it returns is on the host (the harness
builds the engine, which draws a base of its own, before it hands this one
over: two would not fit), and the reference draws a layer again when it
reaches it (``layer``).
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

WEIGHT_LANE = 0x4D53  # the weights' lane of the seed
ADAPTED = ("dq", "uq", "dkv", "ukv", "o", "sg", "su", "sd")
PROGRAM_NAMES = {"dq": ("attention", "q_a_proj"), "uq": ("attention", "q_b_proj"),
                 "dkv": ("attention", "kv_a_proj"), "ukv": ("attention", "kv_b_proj"),
                 "o": ("attention", "o_proj"), "sg": ("moe", "shared_experts", "gate_proj"),
                 "su": ("moe", "shared_experts", "up_proj"),
                 "sd": ("moe", "shared_experts", "down_proj")}


def dims(sizes):
    """The sizes by short names, the cut applied."""
    held = held_experts(sizes)
    return dict(
        H=sizes["hidden_size"], L=sizes["layers"], V=sizes["vocab_rows"],
        heads=sizes["num_attention_heads"], qr=sizes["q_lora_rank"],
        kvr=sizes["kv_lora_rank"], dn=sizes["qk_nope_head_dim"],
        dr=sizes["qk_rope_head_dim"], dv=sizes["v_head_dim"],
        E=sizes["n_routed_experts"], k=sizes["num_experts_per_tok"],
        F=sizes["moe_intermediate_size"],
        Fs=sizes["moe_intermediate_size"] * sizes["n_shared_experts"],
        held=held, G=len(held), r=sizes["lora"]["r"])


def held_experts(sizes):
    """The held experts' indices: a count n means experts 0 .. n-1 (the
    first of E / n equal shares), a list names them."""
    h = sizes["experts_held"]
    return tuple(range(h)) if isinstance(h, int) else tuple(sorted(h))


def matrix_shapes(sizes):
    """``{short name: (fan_in, fan_out)}`` of a layer's 2-D matrices."""
    d = dims(sizes)
    H = d["H"]
    return {"dq": (H, d["qr"]), "uq": (d["qr"], d["heads"] * (d["dn"] + d["dr"])),
            "dkv": (H, d["kvr"] + d["dr"]), "ukv": (d["kvr"], d["heads"] * (d["dn"] + d["dv"])),
            "o": (d["heads"] * d["dv"], H), "sg": (H, d["Fs"]), "su": (H, d["Fs"]),
            "sd": (d["Fs"], H), "r": (H, d["E"])}


def adapted_shapes(sizes):
    """``{flat name: (fan_in, fan_out)}`` of every matrix with an adapter."""
    m = matrix_shapes(sizes)
    d = dims(sizes)
    out = {f"L{i}.{n}": m[n] for i in range(d["L"]) for n in ADAPTED}
    out["lm"] = (d["H"], d["V"])
    return out


def _key(seed):
    return jax.random.fold_in(jax.random.key(seed), WEIGHT_LANE)


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shapes", "held", "expert", "std", "dtype"))
def _layer(seed, i, shapes, held, expert, std, dtype):
    """One layer's base: ``shapes`` ((name, shape), ...) of the 2-D matrices,
    the held experts' stacks by expert index."""
    key = jax.random.fold_in(_key(seed), 1 + i)
    out = {}
    for j, (name, shape) in enumerate(shapes):
        out[name] = _normal(jax.random.fold_in(key, j), shape, std, dtype)
    H, F = expert
    ekey = jax.random.fold_in(key, 1000)
    ids = jnp.asarray(held, jnp.uint32)
    for j, (name, shape) in enumerate((("eg", (H, F)), ("eu", (H, F)), ("ed", (F, H)))):
        out[name] = jax.vmap(lambda e, j=j, shape=shape: _normal(
            jax.random.fold_in(jax.random.fold_in(ekey, e), j), shape, std, dtype))(ids)
    return out


def layer(sizes, seed, i):
    """Layer ``i``'s base on the device, flat names without the ``L<i>.``
    (the norms' scales included)."""
    d = dims(sizes)
    dtype = sizes["training"]["param_dtype"]
    out = _layer(jnp.uint32(int(seed) % 2 ** 32), jnp.uint32(i),
                 tuple(matrix_shapes(sizes).items()), d["held"], (d["H"], d["F"]),
                 float(sizes["initializer_range"]), dtype)
    for name, n in (("n1", d["H"]), ("n2", d["H"]), ("qn", d["qr"]), ("kvn", d["kvr"])):
        out[name] = jnp.ones((n,), dtype)
    return out


@functools.partial(jax.jit, static_argnames=("V", "H", "std", "dtype"))
def _ends(seed, V, H, std, dtype):
    key = jax.random.fold_in(_key(seed), 0)
    return {"emb": _normal(jax.random.fold_in(key, 0), (V, H), std, dtype),
            "lm": _normal(jax.random.fold_in(key, 1), (H, V), std, dtype),
            "norm": jnp.ones((H,), dtype)}


def ends(sizes, seed):
    """The embedding, the head and the final norm on the device."""
    d = dims(sizes)
    return _ends(jnp.uint32(int(seed) % 2 ** 32), d["V"], d["H"],
                 float(sizes["initializer_range"]), sizes["training"]["param_dtype"])


@functools.partial(jax.jit, static_argnames=("shapes", "r", "std"))
def _adapters(seed, shapes, r, std):
    key = jax.random.fold_in(_key(seed), 0xADA)
    out = {}
    for j, (name, (fi, fo)) in enumerate(shapes):
        out[name + ".a"] = _normal(jax.random.fold_in(key, 2 * j), (fi, r), std, jnp.float32)
        out[name + ".b"] = _normal(jax.random.fold_in(key, 2 * j + 1), (r, fo), std, jnp.float32)
    return out


def adapters(sizes, seed):
    """The adapters' start, float32, on the device."""
    return _adapters(jnp.uint32(int(seed) % 2 ** 32), tuple(adapted_shapes(sizes).items()),
                     dims(sizes)["r"], float(sizes["initializer_range"]))


def make(sizes, seed):
    """Every array the program starts from: the base ON THE HOST (a layer at
    a time drawn on the device and fetched), the adapters on the device."""
    t0 = time.perf_counter()
    flat = {k: np.asarray(v) for k, v in ends(sizes, seed).items()}
    for i in range(dims(sizes)["L"]):
        for k, v in jax.device_get(layer(sizes, seed, i)).items():
            flat[f"L{i}.{k}"] = v
    flat.update(adapters(sizes, seed))
    print(f"[latent_moe] base of {sum(v.nbytes for v in flat.values()) / 1e9:.2f} GB drawn and "
          f"fetched in {time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)
    return flat


def trained(flat):
    return {k: v for k, v in flat.items() if k.endswith((".a", ".b"))}


def to_program(flat, sizes):
    """``(adapters, frozen)`` as ``bcfl_tpu/models/latent_moe.py``'s flax
    names and ``models/lora.py``'s adapter keys."""
    L = dims(sizes)["L"]
    frozen = {"embed": {"embedding": flat["emb"]}, "final_norm": {"scale": flat["norm"]},
              "lm_head": {"kernel": flat["lm"]}}
    adapters_ = {"lm_head": {"a": flat["lm.a"], "b": flat["lm.b"]}}
    for i in range(L):
        n = f"L{i}."
        layer_ = {"input_norm": {"scale": flat[n + "n1"]},
                  "post_attention_norm": {"scale": flat[n + "n2"]},
                  "attention": {"q_a_norm": {"scale": flat[n + "qn"]},
                                "kv_a_norm": {"scale": flat[n + "kvn"]}},
                  "moe": {"router": flat[n + "r"], "experts_gate": flat[n + "eg"],
                          "experts_up": flat[n + "eu"], "experts_down": flat[n + "ed"],
                          "shared_experts": {}}}
        for m in ADAPTED:
            path = PROGRAM_NAMES[m]
            node = layer_
            for p in path[:-1]:
                node = node[p]
            node[path[-1]] = {"kernel": flat[n + m]}
            adapters_["/".join((f"layer_{i}",) + path)] = {
                "a": flat[n + m + ".a"], "b": flat[n + m + ".b"]}
        frozen[f"layer_{i}"] = layer_
    return adapters_, frozen


def from_program(trainable, sizes):
    out = {"lm.a": trainable["lm_head"]["a"], "lm.b": trainable["lm_head"]["b"]}
    for i in range(dims(sizes)["L"]):
        for m in ADAPTED:
            ab = trainable["/".join((f"layer_{i}",) + PROGRAM_NAMES[m])]
            out[f"L{i}.{m}.a"], out[f"L{i}.{m}.b"] = ab["a"], ab["b"]
    return out
