"""Aggregation and gossip collectives over the ``clients`` mesh axis.

This is the framework's distributed communication backend — the TPU-native
replacement for the reference's Flower-over-Ray parameter shipping (server
mode, ``src/Servercase/server_IID_IMDB.py:211-218``) and its Python-list
"weight transfer" (serverless mode, ``serverless_NonIID_IMDB.py:293-296``) —
SURVEY.md §2.5:

- FedAvg            -> masked weighted mean (all-reduce over ICI/DCN)
- P2P ring gossip   -> neighbour exchange (collective-permute) + local mixing
- arbitrary topology-> mixing-matrix einsum

The math is written over the GLOBAL stacked-client arrays and compiled with
plain ``jit`` + sharding annotations, so the XLA SPMD partitioner inserts the
collectives itself (the scaling-book recipe: pick a mesh, annotate shardings,
let XLA lower reductions/rolls over a sharded axis to all-reduce /
collective-permute). Every function takes leaves with a leading GLOBAL client
dim ``C`` (the device-major stacked order of
:class:`bcfl_tpu.core.mesh.ClientMesh`) and a ``[C]`` mask/weight vector.
Anomaly-gated aggregation keeps the mesh shape fixed: excluded clients keep
computing but carry weight 0 (SURVEY.md §7 "anomaly gating without reshaping
the mesh").
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

Tree = Any
EPS = 1e-12


def masked_weighted_mean(tree: Tree, weights: jnp.ndarray,
                         fallback: Optional[Tree] = None) -> Tree:
    """Weighted mean over the global client dim; ``weights`` [C] already
    folds participation mask x (optionally) example counts.

    weights = mask                  -> reference serverless unweighted mean
              (``serverless_NonIID_IMDB.py:296``)
    weights = mask * num_examples   -> Flower FedAvg example weighting
              (``server_IID_IMDB.py:199-204``)

    If EVERY client is masked out (an anomaly filter can do that on a bad
    round) the mean is undefined; rather than silently zeroing the model we
    return ``fallback`` (e.g. the round's starting params). With no fallback,
    an unweighted mean of the tree is returned."""
    den = weights.sum()
    empty = den <= EPS

    def leaf_mean(x, fb):
        w = weights.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        mean = (w * x).sum(axis=0) / jnp.maximum(den, EPS).astype(x.dtype)
        if fb is None:
            fb = x.mean(axis=0)
        return jnp.where(empty, fb, mean)

    if fallback is None:
        return jax.tree.map(lambda x: leaf_mean(x, None), tree)
    return jax.tree.map(leaf_mean, tree, fallback)


def hierarchical_weighted_mean(tree: Tree, weights: jnp.ndarray, groups: int,
                               fallback: Optional[Tree] = None) -> Tree:
    """Two-level masked weighted mean over the client dim (SCALING.md
    "Cohort mode"): the ``[C]`` axis splits into ``[groups, C/groups]`` —
    with ``groups`` = the mesh's clients-axis device count, each group is
    exactly one device's stacked cohort slice, so the inner ``sum(axis=1)``
    is a WITHIN-SHARD reduction XLA lowers with no collective at all, and
    only the outer ``[groups]``-long partial-sum reduction becomes the
    cross-device all-reduce. Same math as :func:`masked_weighted_mean`
    (identical all-masked ``fallback`` semantics) up to floating-point
    summation order — the explicit device -> global reduction tree of the
    cross-replica-sharding recipe (arXiv 2004.13336), written so the
    hierarchy is a structural property of the program, not an XLA
    scheduling accident."""
    C = int(weights.shape[0])
    if groups <= 1 or C % groups:
        return masked_weighted_mean(tree, weights, fallback=fallback)
    per = C // groups
    den = weights.sum()
    empty = den <= EPS

    def leaf_mean(x, fb):
        w = weights.reshape((C,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        part = (w * x).reshape((groups, per) + x.shape[1:]).sum(axis=1)
        mean = part.sum(axis=0) / jnp.maximum(den, EPS).astype(x.dtype)
        if fb is None:
            fb = x.mean(axis=0)
        return jnp.where(empty, fb, mean)

    if fallback is None:
        return jax.tree.map(lambda x: leaf_mean(x, None), tree)
    return jax.tree.map(leaf_mean, tree, fallback)


def rank_aware_weighted_mean(tree: Tree, weights: jnp.ndarray,
                             rank_mask: jnp.ndarray,
                             fallback: Optional[Tree] = None) -> Tree:
    """RBLA-style weighted mean over a heterogeneous-rank stacked adapter
    tree (arXiv 2408.08699): every client is materialized zero-padded at
    the cohort max rank R, and ``rank_mask`` [C, R] (1 iff rank dim j is
    REAL for client c — a static closure constant built from the rank spec)
    marks which coordinates are structural padding. Per rank dim j, factor
    leaves average only over the clients that cover j, normalized by THEIR
    weight sum — so a low-rank client's padding never votes, and a
    high-rank client's extra dims aren't diluted toward zero by the fleet's
    low-rank majority (the naive mean's rank-collapse mechanism,
    arXiv 2602.13486). ``a`` leaves are [C, fan_in, R] (mask on the last
    axis), ``b`` leaves [C, R, fan_out] (mask on axis 1); ``full`` head
    leaves and anything unrecognized take the plain weighted mean. Rank
    dims NO participating client covers this round keep ``fallback``
    (the previous global — same all-masked semantics as
    :func:`masked_weighted_mean`, applied per dim)."""
    den_all = weights.sum()
    empty = den_all <= EPS
    R = int(rank_mask.shape[1])

    def leaf(path, x, fb):
        names = tuple(getattr(p, "key", getattr(p, "name", str(p)))
                      for p in path)
        last = names[-1] if names else ""
        fb_v = x.mean(axis=0) if fb is None else fb
        if last == "a" and x.ndim == 3 and x.shape[-1] == R:
            w = (weights[:, None] * rank_mask).astype(x.dtype)   # [C, R]
            num = jnp.einsum("cj,cfj->fj", w, x)
            den = w.sum(axis=0)                                  # [R]
            mean = num / jnp.maximum(den, EPS)[None, :]
            mean = jnp.where(den[None, :] > EPS, mean, fb_v)
            return jnp.where(empty, fb_v, mean)
        if last == "b" and x.ndim == 3 and x.shape[1] == R:
            w = (weights[:, None] * rank_mask).astype(x.dtype)
            num = jnp.einsum("cj,cjf->jf", w, x)
            den = w.sum(axis=0)
            mean = num / jnp.maximum(den, EPS)[:, None]
            mean = jnp.where(den[:, None] > EPS, mean, fb_v)
            return jnp.where(empty, fb_v, mean)
        wl = weights.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        mean = (wl * x).sum(axis=0) / jnp.maximum(den_all, EPS).astype(x.dtype)
        return jnp.where(empty, fb_v, mean)

    if fallback is None:
        return jax.tree_util.tree_map_with_path(
            lambda p, x: leaf(p, x, None), tree)
    return jax.tree_util.tree_map_with_path(leaf, tree, fallback)


# ---------------------------------------------------------------------------
# Byzantine-robust aggregation rules (ROBUSTNESS.md).
#
# All three are plain global-array math over the stacked client dim, so under
# the gspmd programs they compile into the SAME fused round executable as the
# mean — no host round-trips, no per-leaf dispatches, and the participation
# mask stays a runtime input (switching WHICH clients participate never
# retraces). They are mask-aware through order statistics, not weighting:
# ``weights > 0`` marks a client as participating; magnitudes (example
# counts) are deliberately ignored — a trimmed mean with fractional votes has
# no sound definition, and a Byzantine client could inflate its own weight.
# All-masked rounds return ``fallback`` exactly like masked_weighted_mean.
# ---------------------------------------------------------------------------

# sort sentinel for non-participating clients: large but finite, so a
# ``sentinel * 0`` term in a masked sum is 0.0 rather than inf * 0 = NaN
_SENTINEL = 1e30


def _participation(weights: jnp.ndarray):
    """(active [C] float, k active count int32, empty bool) from a weight
    vector whose positive entries mark participating clients."""
    active = (weights > 0).astype(jnp.float32)
    k = active.sum().astype(jnp.int32)
    return active, k, k <= 0


def _sort_active_first(x: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """Sort the client dim ascending with non-participants pushed to the
    tail: slots [0, k) hold the participating values in order."""
    a = active.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.sort(jnp.where(a > 0, x.astype(jnp.float32), _SENTINEL),
                    axis=0)


def _trim_count(k: jnp.ndarray, trim: float) -> jnp.ndarray:
    """ceil(trim * k), clamped so at least one client survives trimming
    (2t <= k - 1). With trim = the assumed Byzantine fraction f/C this drops
    at least every corrupted coordinate when f/C <= trim."""
    t = jnp.ceil(trim * k.astype(jnp.float32)).astype(jnp.int32)
    return jnp.clip(t, 0, jnp.maximum((k - 1) // 2, 0))


def masked_trimmed_mean(tree: Tree, weights: jnp.ndarray, trim: float = 0.2,
                        fallback: Optional[Tree] = None) -> Tree:
    """Coordinate-wise trimmed mean over participating clients: per
    coordinate, drop the ``t = ceil(trim * k)`` smallest and largest values
    and mean the middle ``k - 2t``. Tolerates up to ``t`` arbitrarily
    corrupted clients per coordinate."""
    active, k, empty = _participation(weights)
    t = _trim_count(k, trim)
    cnt = jnp.maximum(k - 2 * t, 1).astype(jnp.float32)

    def leaf(x, fb):
        xs = _sort_active_first(x, active)
        pos = jnp.arange(xs.shape[0]).reshape(
            (-1,) + (1,) * (x.ndim - 1))
        sel = ((pos >= t) & (pos < k - t)).astype(jnp.float32)
        mean = (xs * sel).sum(axis=0) / cnt
        if fb is None:
            fb = x.mean(axis=0)
        return jnp.where(empty, fb, mean.astype(x.dtype))

    if fallback is None:
        return jax.tree.map(lambda x: leaf(x, None), tree)
    return jax.tree.map(leaf, tree, fallback)


def masked_median(tree: Tree, weights: jnp.ndarray,
                  fallback: Optional[Tree] = None) -> Tree:
    """Coordinate-wise median over participating clients (mean of the two
    middle order statistics for even ``k``). Tolerates any minority of
    corrupted clients per coordinate."""
    active, k, empty = _participation(weights)
    lo = jnp.maximum((k - 1) // 2, 0)
    hi = jnp.maximum(k // 2, 0)

    def leaf(x, fb):
        xs = _sort_active_first(x, active)
        c = xs.shape[0] - 1
        med = (jnp.take(xs, jnp.minimum(lo, c), axis=0)
               + jnp.take(xs, jnp.minimum(hi, c), axis=0)) * 0.5
        if fb is None:
            fb = x.mean(axis=0)
        return jnp.where(empty, fb, med.astype(x.dtype))

    if fallback is None:
        return jax.tree.map(lambda x: leaf(x, None), tree)
    return jax.tree.map(leaf, tree, fallback)


def masked_krum(tree: Tree, weights: jnp.ndarray, trim: float = 0.2,
                fallback: Optional[Tree] = None) -> Tree:
    """Krum (Blanchard et al., NeurIPS 2017) over participating clients:
    every client is scored by the summed squared distance to its
    ``m = k - f - 2`` nearest participating neighbours (``f = ceil(trim*k)``,
    ``m`` clamped to >= 1) and the single lowest-scoring client's update is
    adopted wholesale. Requires ``k >= 2f + 3`` for the classical guarantee;
    below that it degrades to nearest-neighbour selection rather than
    failing. The broadcast result replaces every client's slot (callers use
    it exactly like the mean)."""
    active, k, empty = _participation(weights)
    f = _trim_count(k, trim)
    m = jnp.clip(k - f - 2, 1, None)

    # pairwise squared distances over the FULL update (summed across leaves,
    # f32 accumulation); one [C, C] matrix, no host round-trips
    leaves = jax.tree.leaves(tree)
    C = leaves[0].shape[0]
    D = jnp.zeros((C, C), jnp.float32)
    for x in leaves:
        xf = x.reshape(C, -1).astype(jnp.float32)
        sq = (xf * xf).sum(axis=1)
        D = D + (sq[:, None] + sq[None, :] - 2.0 * (xf @ xf.T))
    pair = active[:, None] * active[None, :]
    D = jnp.where(pair > 0, jnp.maximum(D, 0.0), _SENTINEL)
    D = D.at[jnp.arange(C), jnp.arange(C)].set(_SENTINEL)  # no self-distance
    Ds = jnp.sort(D, axis=1)
    pos = jnp.arange(C)[None, :]
    score = jnp.where(pos < m, Ds, 0.0).sum(axis=1)
    score = jnp.where(active > 0, score, jnp.inf)
    sel = jnp.argmin(score)

    def leaf(x, fb):
        pick = jnp.take(x, sel, axis=0)
        if fb is None:
            fb = x.mean(axis=0)
        return jnp.where(empty, fb, pick)

    if fallback is None:
        return jax.tree.map(lambda x: leaf(x, None), tree)
    return jax.tree.map(leaf, tree, fallback)


AGGREGATORS = ("mean", "trimmed_mean", "median", "krum")


def make_aggregator(name: str, trim: float = 0.2,
                    hierarchical_groups: int = 0,
                    rank_mask: Optional[jnp.ndarray] = None):
    """``(tree, weights, fallback) -> tree`` aggregation closure for the
    round-program builders. ``mean`` keeps full weighted-FedAvg semantics;
    the robust rules treat ``weights`` as a participation mask only (see
    module note above).

    ``hierarchical_groups`` > 1 switches ``mean`` to the explicit two-level
    device -> global reduction (:func:`hierarchical_weighted_mean`, cohort
    mode). The robust rules ignore it: order statistics over the client dim
    are global by definition — a per-device trimmed mean of trimmed means
    is a DIFFERENT (weaker) estimator, so 'hierarchical trimmed_mean' would
    be a label lying about its breakdown point.

    ``rank_mask`` [C, R] (heterogeneous LoRA ranks) swaps ``mean`` for the
    rank-aware RBLA rule (:func:`rank_aware_weighted_mean`); FedConfig
    rejects the robust rules for heterogeneous fleets at config time (order
    statistics over structural zero padding are unsound), so pairing a mask
    with any other rule raises here too."""
    if rank_mask is not None:
        if name != "mean":
            raise ValueError(
                f"rank-aware aggregation (heterogeneous LoRA ranks) is "
                f"defined for the mean only, got aggregator {name!r}")
        return lambda t, w, fb: rank_aware_weighted_mean(
            t, w, rank_mask, fallback=fb)
    if name == "mean":
        if hierarchical_groups > 1:
            return lambda t, w, fb: hierarchical_weighted_mean(
                t, w, hierarchical_groups, fallback=fb)
        return lambda t, w, fb: masked_weighted_mean(t, w, fallback=fb)
    if name == "trimmed_mean":
        return lambda t, w, fb: masked_trimmed_mean(t, w, trim, fallback=fb)
    if name == "median":
        return lambda t, w, fb: masked_median(t, w, fallback=fb)
    if name == "krum":
        return lambda t, w, fb: masked_krum(t, w, trim, fallback=fb)
    raise ValueError(f"unknown aggregator {name!r} (one of {AGGREGATORS})")


def ring_shift(tree: Tree, direction: int = +1) -> Tree:
    """Each client's ring neighbor over the global order: ``direction=+1``
    means client ``i`` receives ``(i+1) mod C``'s value (a ``roll`` by -1;
    XLA lowers a roll over a sharded dim to collective-permute)."""
    if direction not in (+1, -1):
        raise ValueError("direction must be +1 or -1")
    return jax.tree.map(lambda x: jnp.roll(x, -direction, axis=0), tree)


def gossip_step_mix(x, xl, xr, ml, mr, me, alpha: float):
    """One client's masked ring-gossip update (masks already reshaped to
    broadcast against ``x``): THE definition of the mixing rule."""
    mixed = x + (alpha / 2) * ml * (xl - x) + (alpha / 2) * mr * (xr - x)
    return me * mixed + (1 - me) * x


def gossip_mix(tree: Tree, mask: jnp.ndarray, alpha: float,
               steps: int = 1) -> Tree:
    """Symmetric masked ring gossip over the global client order: each
    client averages toward its two ring neighbors. With mixing weight
    ``alpha`` and participation ``mask`` [C]:

        x_i <- x_i + (alpha/2) * m_{i-1} (x_{i-1} - x_i)
                   + (alpha/2) * m_{i+1} (x_{i+1} - x_i)

    Anomalous neighbors (mask 0) contribute nothing, and a client that is
    itself masked out is frozen entirely, so its (possibly poisoned) state
    neither spreads nor drifts. Repeated ``steps`` diffuse toward the global
    average — the intended semantics of the reference's all-client averaging
    (``serverless_NonIID_IMDB.py:296``) without any all-to-all. The
    self==received special case of :func:`gossip_mix_recv` (one mixing-rule
    definition, not two)."""
    return gossip_mix_recv(tree, tree, mask, alpha, steps=steps)


def gossip_mix_recv(self_tree: Tree, recv_tree: Tree, mask: jnp.ndarray,
                    alpha: float, steps: int = 1) -> Tree:
    """``gossip_mix`` with distinct SELF and RECEIVED trees: each client's
    self-term comes from ``self_tree`` (its local, honest state) while the
    neighbor terms are ring-shifted from ``recv_tree`` (the transported
    copies, which a corrupted link may have perturbed — the fused-ledger
    verification path). The communication codecs ride the same split
    (COMPRESSION.md): ``recv_tree`` is then each peer's lossy
    reconstruction from the compressed delta payload, so only what crossed
    the wire diffuses — a sender's own carry never degrades through its own
    codec. With ``recv_tree`` value-equal to ``self_tree``
    this is bit-identical to ``gossip_mix``. Only the FIRST step models
    transport (later steps exchange post-mix state, whose transport is not
    simulated)."""
    m_left = jnp.roll(mask, 1, axis=0)   # value of client i-1, at slot i
    m_right = jnp.roll(mask, -1, axis=0)
    for _ in range(steps):
        left = ring_shift(recv_tree, direction=-1)
        right = ring_shift(recv_tree, direction=+1)

        def mix(x, xl, xr):
            ml = m_left.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
            mr = m_right.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
            me = mask.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
            return gossip_step_mix(x, xl, xr, ml, mr, me, alpha)

        self_tree = jax.tree.map(mix, self_tree, left, right)
        recv_tree = self_tree
    return self_tree


def mix_with_matrix(tree: Tree, W: jnp.ndarray) -> Tree:
    """Arbitrary-topology mixing ``x_i <- sum_j W[i, j] x_j`` as one einsum
    over the global client dim (XLA shards the contraction)."""
    return jax.tree.map(
        lambda x: jnp.einsum("ij,j...->i...", W.astype(x.dtype), x), tree)
