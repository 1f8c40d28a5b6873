"""LoRA adapters as a pure param-tree transform.

The reference does full fine-tuning only (1-epoch AdamW over all params,
``src/Servercase/server_IID_IMDB.py:108-118``); LoRA is required by the
BASELINE.json Llama-2-7B federated config and is the practical answer to the
per-client-state memory cost of stacking clients on a mesh (SURVEY.md §7
"hard parts"). Implementation is model-agnostic: it targets 2D(-reshapeable)
``kernel`` leaves of the frozen base tree, so the SAME federated client step
trains either full params or adapters — only the optimized tree changes.

Communication win: in federated mode only the adapter tree is aggregated /
gossiped, which is the real mechanism behind the reference's "0.043 GB instead
of 0.4036 GB" blockchain-payload claim (MT notebook cell 27).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

DEFAULT_TARGETS = ("query", "key", "value", "out", "mlp_in", "mlp_out")
# task heads are TRAINED IN FULL under LoRA (HF modules_to_save convention):
# a LoRA-only run would otherwise optimize against a frozen randomly-
# initialized head and plateau. They are small (hidden x labels / hidden x
# hidden); the vocab-sized lm_head instead gets a LoRA adapter (llama
# LORA_TARGETS) — full-training it would be ~131M params/client on
# llama2-7b, defeating the adapter-only communication win.
HEAD_MODULES = ("classifier", "pooler")


def _is_target(path: Tuple[str, ...], targets: Sequence[str]) -> bool:
    return len(path) >= 2 and path[-1] == "kernel" and path[-2] in targets


def init_lora(key: jax.Array, params, rank: int,
              targets: Sequence[str] = DEFAULT_TARGETS,
              head_modules: Sequence[str] = HEAD_MODULES,
              dtype=None, tied: Sequence[Tuple[str, str]] = ()):
    """Create the adapter tree: for each targeted kernel W (viewed 2D as
    [fan_in, fan_out]) an ``a`` [fan_in, rank] (gaussian/sqrt(rank)) and
    ``b`` [rank, fan_out] (zeros — adapters start as identity), in ``dtype``
    (None: the kernel's own; a family's policy, ``models.lora_policy``, may
    keep float32 adapters over a bfloat16 base). Leaves of
    ``head_modules`` are copied into the tree whole and substituted (not
    low-rank-added) at merge time, so task heads fine-tune in full.
    ``tied`` (``models.LoRAPolicy.tied``): ``(module, leaf path)`` pairs, an
    adapter under ``module``'s name for the product with the TRANSPOSE of the
    named leaf (a head whose kernel is the ``[vocab, hidden]`` embedding)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    adapters = {}
    tied_to = {leaf_path: module for module, leaf_path in tied}
    for path, leaf in flat:
        names = tuple(getattr(p, "key", getattr(p, "name", str(p))) for p in path)
        shape = leaf.shape
        module = tied_to.get("/".join(names))
        if module is not None:  # this leaf's transpose is ``module``'s kernel
            names, shape = (module, "kernel"), shape[::-1]
        elif len(names) >= 2 and names[-2] in head_modules:
            adapters["/".join(names)] = {"full": leaf}
            continue
        elif not _is_target(names, targets):
            continue
        if len(shape) == 2:
            fan_in, fan_out = shape
        elif len(shape) == 3:
            # row-parallel output projections (DenseGeneral axis=(-2,-1))
            # have kernel [heads, head_dim, out]; column-parallel qkv
            # (features=(heads, head_dim)) have kernel [in, heads, head_dim]
            if names[-2] in ("out", "o_proj"):
                fan_in, fan_out = shape[0] * shape[1], shape[2]
            else:
                fan_in, fan_out = shape[0], shape[1] * shape[2]
        else:
            continue
        key, k1 = jax.random.split(key)
        dt = leaf.dtype if dtype is None else jnp.dtype(dtype)
        adapters["/".join(names[:-1])] = {
            "a": (jax.random.normal(k1, (fan_in, rank), dt)
                  / jnp.sqrt(jnp.asarray(rank, dt))),
            "b": jnp.zeros((rank, fan_out), dt),
        }
    return adapters


def as_collection(adapters):
    """The adapter tree (keys ``"module/path"``) as the nested ``lora``
    variable collection of a model whose dense layers apply adapters on the
    activations, ``x W + (x a) b`` (``models.lora_policy(...).on_activations``):
    ``{"module": {"path": {"a": ..., "b": ...}}}``."""
    out = {}
    for path, entry in adapters.items():
        node = out
        for name in path.split("/"):
            node = node.setdefault(name, {})
        node.update(entry)
    return out


def apply_lora(params, adapters, scale: float = 1.0):
    """Return params with ``W + scale * (a @ b)`` merged into each targeted
    kernel (reshaped back to the kernel's native rank); head leaves stored
    whole in the adapter tree (``init_lora`` ``head_modules``) substitute
    the frozen value outright."""

    def merge(path, leaf):
        names = tuple(getattr(p, "key", getattr(p, "name", str(p))) for p in path)
        k_leaf = "/".join(names)
        entry = adapters.get(k_leaf)
        if isinstance(entry, dict) and "full" in entry:
            return entry["full"].astype(leaf.dtype)
        k = "/".join(names[:-1])
        if names and names[-1] == "kernel" and k in adapters:
            ab = adapters[k]["a"] @ adapters[k]["b"]
            return leaf + scale * ab.reshape(leaf.shape).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(merge, params)


def num_params(tree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# Heterogeneous per-client ranks (RBLA, arXiv 2408.08699).
#
# A fleet where client c trains at rank r_c is materialized at the COHORT MAX
# rank R = max(r_c): every 'a' is [fan_in, R], every 'b' is [R, fan_out], and
# client c's columns/rows >= r_c are structural zero padding. The padding is
# described by a [C, R] mask that is a pure function of the (static) rank
# spec — it compiles into the round programs as a closure constant, so
# heterogeneous fleets add ZERO per-round retraces. Padding stays exactly
# zero through training without re-clipping after aggregation: both factors
# start at 0 there, so gradients are 0, and AdamW (m=0, v=0, decay of a 0
# param) produces an exactly-0 update — clipping the global tree once at
# local-train entry covers every path (server, serverless, async, gossip).
# ---------------------------------------------------------------------------


def rank_mask(ranks: Sequence[int]) -> jnp.ndarray:
    """``[C, max(ranks)]`` float mask: ``mask[c, j] = 1`` iff ``j < ranks[c]``.
    Static in the rank spec — built once at program-build time."""
    r = jnp.asarray([int(x) for x in ranks], jnp.int32)
    rmax = int(max(int(x) for x in ranks))
    return (jnp.arange(rmax)[None, :] < r[:, None]).astype(jnp.float32)


def clip_adapters(adapters, mask_row: jnp.ndarray):
    """Zero one client's padding dims: ``a * row[None, :]``,
    ``b * row[:, None]``; ``full`` head leaves pass through. Applied to the
    replicated global tree at local-train entry (vmapped over mask rows)."""

    def clip(path, leaf):
        names = tuple(getattr(p, "key", getattr(p, "name", str(p)))
                      for p in path)
        last = names[-1] if names else ""
        if last == "a":
            return leaf * mask_row[None, :].astype(leaf.dtype)
        if last == "b":
            return leaf * mask_row[:, None].astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(clip, adapters)


def init_lora_ranks(key: jax.Array, params, ranks: Sequence[int],
                    targets: Sequence[str] = DEFAULT_TARGETS,
                    head_modules: Sequence[str] = HEAD_MODULES):
    """Stacked ``[C, ...]`` adapter tree for a heterogeneous fleet: client
    ``c`` is initialized AT ITS OWN rank (gaussian/sqrt(r_c) — the init
    scale a homogeneous rank-r_c client would get), then zero-padded to the
    cohort max rank so all clients share one stacked structure."""
    ranks = tuple(int(r) for r in ranks)
    rmax = max(ranks)
    per_client = []
    for c, r in enumerate(ranks):
        adp = init_lora(jax.random.fold_in(key, c), params, r,
                        targets=targets, head_modules=head_modules)
        padded = {}
        for k, entry in adp.items():
            if "full" in entry:
                padded[k] = entry
            else:
                padded[k] = {
                    "a": jnp.pad(entry["a"], ((0, 0), (0, rmax - r))),
                    "b": jnp.pad(entry["b"], ((0, rmax - r), (0, 0))),
                }
        per_client.append(padded)
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per_client)


def effective_rank(adapters) -> jnp.ndarray:
    """Mean Shannon effective rank over the adapter factor pairs of one
    (unstacked) adapter tree — the rank-collapse guard of arXiv 2602.13486,
    without an SVD: per rank dim ``e_j = ||a[:, j]||^2 * ||b[j, :]||^2`` is
    the squared Frobenius energy of the j-th rank-1 component, and
    ``exp(entropy(e / sum e))`` counts how many components carry it. 0.0
    when the adapters carry no energy at all (b starts at zeros)."""
    tiny = jnp.float32(1e-30)
    effs = []
    flat = jax.tree_util.tree_flatten_with_path(adapters)[0]
    pairs = {}
    for path, leaf in flat:
        names = tuple(getattr(p, "key", getattr(p, "name", str(p)))
                      for p in path)
        if names and names[-1] in ("a", "b"):
            pairs.setdefault("/".join(names[:-1]), {})[names[-1]] = leaf
    for entry in pairs.values():
        if "a" not in entry or "b" not in entry:
            continue
        a = entry["a"].astype(jnp.float32)
        b = entry["b"].astype(jnp.float32)
        e = (a * a).sum(axis=0) * (b * b).sum(axis=1)
        tot = e.sum()
        p = e / jnp.maximum(tot, tiny)
        ent = -(p * jnp.log(jnp.maximum(p, tiny))).sum()
        effs.append(jnp.where(tot > tiny, jnp.exp(ent), 0.0))
    if not effs:
        return jnp.asarray(0.0, jnp.float32)
    return jnp.stack(effs).mean()
