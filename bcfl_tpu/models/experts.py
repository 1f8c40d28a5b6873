"""The expert layer that holds a share of the experts, for every family with
one (:mod:`bcfl_tpu.models.latent_moe`, :mod:`bcfl_tpu.models.ssm_moe`,
:mod:`bcfl_tpu.models.delta_moe`), and
the dense product that carries a LoRA adapter on the activations.

- **Expert layer** (:class:`ExpertLayer`): a float32 softmax router over ALL
  ``n_routed_experts``, the top ``num_experts_per_tok`` renormalised, plus an
  MLP every position passes (``shared``), times ``sigmoid(x w)`` of a frozen
  ``shared_expert_gate`` [H, 1] where the config's ``shared_expert_gate`` is
  true. The layer is told which experts it
  HOLDS (the config's ``held``) and computes their part of the result; what
  the absent experts would add is left out, and that partial result goes on
  (no code stands in for the absent chips or their exchange). No token is
  dropped and no capacity is set: the assignments are sorted by expert, the
  held ones go through one grouped product a projection
  (:mod:`bcfl_tpu.ops.grouped_matmul`), the absent ones sort to the tail
  where nothing is computed. No balance term: the router is frozen. The
  layer reads ``hidden_size``, ``n_routed_experts``, ``num_experts_per_tok``,
  ``moe_intermediate_size``, ``n_shared_experts``, ``held``,
  ``initializer_range`` and the two types off whatever config it is given.
- **LoRA on the activations** (:class:`LoRADense`): ``x W + (x a) b`` when the
  ``lora`` collection carries ``a`` and ``b`` for the module
  (``models.policy``): no ``[in, out]`` product ``a b``, no merged kernel a
  client, no weight-gradient product of a frozen kernel.
- **Clients fold into rows**: under the round program's ``vmap`` over
  clients with the frozen base NOT batched, the expert block's own batching
  rule (:func:`expert_block`) runs one grouped product over all clients'
  rows; the base is never broadcast.
- **Counters** (collection ``counters``, :data:`COUNTERS`): the real
  positions' assignments that fell on held and on absent experts, and the
  fullest held expert's rows. Padded positions are routed to no expert.

Named scopes (``metrics.tracing.scope``): ``fed.moe.route``,
``fed.moe.experts``, ``fed.moe.shared``, ``fed.lora``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.custom_batching import custom_vmap

from bcfl_tpu.metrics.tracing import scope
from bcfl_tpu.ops.grouped_matmul import grouped_matmul

# what the expert layer counts in a step, and how a count folds over steps,
# clients and rounds: ``(name, "sum" | "max")``, sums first. Per client: the
# slots are one client's assignments, the fullest expert's rows one client's
# rows.
COUNTERS = (("moe_slots_held", "sum"), ("moe_slots_absent", "sum"),
            ("moe_rows_max", "max"))


# ------------------------------------------------------------------- dense


def held_experts(experts_held, n_routed_experts):
    """A config's ``experts_held`` (a tuple of indices, a count n meaning
    experts 0 .. n-1, or None for all) as the held experts' indices,
    ascending."""
    h = n_routed_experts if experts_held is None else experts_held
    held = tuple(range(h)) if isinstance(h, int) else tuple(sorted(h))
    if (not held or len(set(held)) != len(held) or held[0] < 0
            or held[-1] >= n_routed_experts):
        raise ValueError(
            f"experts_held {experts_held!r} is not a set of experts "
            f"out of {n_routed_experts}")
    return held


def adapted(module, x, y, dtype, out):
    """``y + (x a) b`` where the ``lora`` collection has ``module``'s ``a``
    [in, r] and ``b`` [r, out]; ``y`` as it is where it has none."""
    if module.has_variable("lora", "a"):
        with scope("lora"):
            a = module.get_variable("lora", "a").astype(dtype)
            b = module.get_variable("lora", "b").astype(dtype)
            xa = checkpoint_name(
                jnp.dot(x, a, preferred_element_type=jnp.float32), "lora_xa")
            y = y + jnp.dot(xa.astype(dtype), b, preferred_element_type=out)
    return y


class LoRADense(nn.Module):
    """``x W`` with a 2-D frozen kernel, plus ``(x a) b`` on the activations
    when the ``lora`` collection has this module's ``a`` [in, r] and ``b``
    [r, out]: products in the compute type, adapters stored in their own."""

    features: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    init_std: float = 0.02
    out_dtype: Optional[jnp.dtype] = None  # None = the compute type

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.normal(self.init_std),
                            (x.shape[-1], self.features), self.param_dtype)
        out = self.out_dtype or self.dtype
        x = x.astype(self.dtype)
        y = jnp.dot(x, kernel.astype(self.dtype), preferred_element_type=out)
        return adapted(self, x, y, self.dtype, out)


def dense(c, features: int, name: str, **kw):
    """A :class:`LoRADense` in the config's types and initialiser."""
    return LoRADense(features, c.dtype, c.param_dtype, c.initializer_range,
                     name=name, **kw)


class SwiGLU(nn.Module):
    cfg: Any
    width: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        gate = checkpoint_name(dense(c, self.width, "gate_proj")(x), "shared_gate")
        up = checkpoint_name(dense(c, self.width, "up_proj")(x), "shared_up")
        return dense(c, c.hidden_size, "down_proj")(nn.silu(gate) * up)


# ------------------------------------------------------------ expert block

# ------------------------------------------------------------ expert block

# The sorted assignments go through the experts a chunk at a time, and only
# the chunks that hold an assignment to a HELD expert run: those sort first,
# and a step whose routing sends more to the held experts takes more chunks
# and drops nothing. A chunk's sort, gather, scatter-add and float32
# products run over the WHOLE chunk whatever it holds (12 ms a layer a folded
# step of the state-space cell), so one chunk has to be the rule: a chunk is
# a quarter of all the assignments (twice the mean held share where a chip
# holds an eighth of the experts), or the mean held share and a quarter
# more where that is larger (18 of 72 held: a quarter of a full step's
# assignments is the MEAN there, and with chunks of a quarter the steps whose
# rows are all full took a second, nearly empty chunk about every other
# time: 3% of a round, by the seed's draw; PERF.md section 6, PR 32).
CHUNK_SHARE = 4
HELD_HEADROOM = (5, 4)


def _chunk_rows(M, G, E):
    """The rows of a chunk of ``M`` sorted assignments where ``G`` of ``E``
    experts are held (``E`` None: a quarter)."""
    rows = -(-M // CHUNK_SHARE)
    if E is not None:
        num, den = HELD_HEADROOM
        rows = max(rows, -(-num * M * G // (den * E)))
    return min(rows, M)


def _chunks(slot, cw, G, E):
    """The N*k assignments sorted by slot (held experts first, in order; the
    absent ones, slot G, at the tail), cut into chunks: ``(rows_of, n, order)``.
    ``rows_of(c)`` gives chunk c's sorted assignments ``idx`` (padded past the
    last), their tokens, which of them fall on a held expert, their combine
    weights (zero elsewhere) and the chunk's group sizes; ``n`` is how many
    chunks hold a held assignment; ``order`` is the sorting permutation."""
    N, k = slot.shape
    M = N * k
    flat = slot.reshape(-1)
    chunk = _chunk_rows(M, G, E)
    by_slot = jnp.argsort(flat, stable=True)
    order = jnp.pad(by_slot, (0, -M % chunk))
    # where each held expert's rows end in the sorted order (no scatter: the
    # TPU compiler merges look-alike scatters into one it cannot emit)
    ends = jnp.searchsorted(flat[by_slot], jnp.arange(G), side="right")
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    total = ends[-1]
    cwf = cw.reshape(-1)

    def rows_of(c):
        lo = c * chunk
        idx = lax.dynamic_slice(order, (lo,), (chunk,))
        live = lo + jnp.arange(chunk) < total
        sizes = (jnp.clip(ends, lo, lo + chunk)
                 - jnp.clip(starts, lo, lo + chunk)).astype(jnp.int32)
        return idx, idx // k, live, jnp.where(live, cwf[idx], 0.0), sizes

    return rows_of, (total + chunk - 1) // chunk, by_slot


def _silu_gate(g, u):
    """``silu(g) * u`` in float32 and what its gradient needs."""
    gf, uf = g.astype(jnp.float32), u.astype(jnp.float32)
    sg = jax.nn.sigmoid(gf)
    return gf, uf, sg


def _expert_block_fwd(E, x, slot, cw, wg, wu, wd):
    """One row axis: ``x`` [N, H], ``slot`` [N, k] (a held expert's place in
    ``wg``/``wu``/``wd``, or their count G for an absent expert or a padded
    position), ``cw`` [N, k] combine weights; ``E`` (static) the layer's
    routed experts, for the chunks' size. Returns ``(y,)``, ``y`` [N, H]."""
    rows_of, n, _ = _chunks(slot, cw, wg.shape[0], E)

    def chunk(c, y):
        with scope("moe.route"):
            _, tok, _, cws, sizes = rows_of(c)
            xs = x[tok]
        with scope("moe.experts"):
            gf, uf, sg = _silu_gate(grouped_matmul(xs, wg, sizes),
                                    grouped_matmul(xs, wu, sizes))
            o = grouped_matmul((gf * sg * uf).astype(x.dtype), wd, sizes)
        with scope("moe.route"):
            return y.at[tok].add(o.astype(jnp.float32) * cws[:, None])

    y = lax.fori_loop(0, n, chunk, jnp.zeros(x.shape, jnp.float32))
    return (y.astype(x.dtype),)


def _expert_block_bwd(E, x, slot, cw, dy, wg, wu, wd):
    """``(dx, dcw)``: the gate and up products once more, then the
    activation-gradient products of the same frozen weights
    (``transpose_rhs``); no weight-gradient product. The combine weight's
    gradient ``<o, dy>`` is read as ``<silu(g) u, dy W_down^T>``, which the
    pass has anyway."""
    rows_of, n, order = _chunks(slot, cw, wg.shape[0], E)

    M = slot.size
    span = _chunk_rows(M, wg.shape[0], E)

    def chunk(c, carry):
        dx, dcw_sorted = carry
        with scope("moe.route"):
            _, tok, live, cws, sizes = rows_of(c)
            xs, dys = x[tok], dy[tok]
        with scope("moe.experts"):
            gf, uf, sg = _silu_gate(grouped_matmul(xs, wg, sizes),
                                    grouped_matmul(xs, wu, sizes))
            da = grouped_matmul(dys, wd, sizes,
                                transpose_rhs=True).astype(jnp.float32)
            dcws = jnp.where(live, (gf * sg * uf * da).sum(-1), 0.0)
            da = da * cws[:, None]
            dg = (da * uf * sg * (1.0 + gf * (1.0 - sg))).astype(x.dtype)
            du = (da * gf * sg).astype(x.dtype)
            dxs = (grouped_matmul(dg, wg, sizes, transpose_rhs=True).astype(jnp.float32)
                   + grouped_matmul(du, wu, sizes, transpose_rhs=True).astype(jnp.float32))
        with scope("moe.route"):
            # the combine weights' gradient stays in the sorted order here
            return (dx.at[tok].add(dxs),
                    lax.dynamic_update_slice(dcw_sorted, dcws, (c * span,)))

    dx, dcw_sorted = lax.fori_loop(
        0, n, chunk, (jnp.zeros(x.shape, jnp.float32),
                      jnp.zeros((M + -M % span,), jnp.float32)))
    with scope("moe.route"):
        # where each assignment sits in the sorted order: the inverse permutation
        dcw = dcw_sorted[jnp.argsort(order)].reshape(cw.shape)
    return dx.astype(x.dtype), dcw.astype(cw.dtype)


def _fold(fn):
    """``fn`` with the batching rule that folds a ``vmap``'s axis into the
    row axis: the per-row arguments (those before the three weight stacks)
    are reshaped ``[C, N, ...] -> [C * N, ...]``, the weights have to be
    unbatched (a frozen base under a stack of clients), and the results are
    cut back to ``[C, N, ...]``. The sort inside ``fn`` then groups all
    clients' rows by expert, so the grouped products see all the clients'
    rows and each held expert's weights once."""
    wrapped = custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        rows, weights = args[:-3], args[-3:]
        if any(jax.tree.leaves(in_batched[-3:])):
            raise NotImplementedError(
                "the expert block under vmap takes expert weights that are "
                "not batched (one frozen base for the stack of clients); "
                "batched expert weights would be held once a client")
        rows = [r if b else jnp.broadcast_to(r[None], (axis_size,) + r.shape)
                for r, b in zip(rows, in_batched[:-3])]
        out = wrapped(*(r.reshape((-1,) + r.shape[2:]) for r in rows), *weights)
        out = tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in out)
        return out, tuple(True for _ in out)

    return wrapped


@functools.lru_cache(maxsize=None)
def block_for(n_routed=None):
    """:func:`expert_block` for a layer of ``n_routed`` experts, which sizes
    its chunks by the share of them it holds (:func:`_chunk_rows`)."""
    fwd_folded = _fold(functools.partial(_expert_block_fwd, n_routed))
    bwd_folded = _fold(functools.partial(_expert_block_bwd, n_routed))

    @jax.custom_vjp
    def block(x, slot, cw, wg, wu, wd):
        return fwd_folded(x, slot, cw, wg, wu, wd)[0]

    def fwd(x, slot, cw, wg, wu, wd):
        return block(x, slot, cw, wg, wu, wd), (x, slot, cw, wg, wu, wd)

    def bwd(res, dy):
        x, slot, cw, wg, wu, wd = res
        dx, dcw = bwd_folded(x, slot, cw, dy, wg, wu, wd)
        return dx, None, dcw, None, None, None

    block.defvjp(fwd, bwd)
    return block


def expert_block(x, slot, cw, wg, wu, wd):
    """The held routed experts' part of the layer for rows ``x`` [N, H]:
    ``sum_j cw[n, j] SwiGLU_{slot[n, j]}(x[n])`` over a row's assignments
    that fall on held experts. Differentiable in ``x`` and ``cw``; the expert
    weights are frozen (their cotangent is zero: full fine-tuning of this
    family is refused at config time). Under ``jax.vmap`` with unbatched
    weights the clients fold into the rows (:func:`_fold`). This is the block
    with chunks of a quarter; a layer asks :func:`block_for` its own."""
    return block_for()(x, slot, cw, wg, wu, wd)


class ExpertLayer(nn.Module):
    cfg: Any
    # the MLP every position passes beside the routed experts, built as
    # ``shared(cfg, width, name="shared_experts")``
    shared: Callable = SwiGLU

    @nn.compact
    def __call__(self, x, valid):
        """``valid`` [B, S]: the real positions. A padded position is routed
        to no expert: it feeds no loss and no real position attends to it,
        and every padded position of a row has the same hidden state, so they
        would all fall on the same experts (a fifth of a step's rows on one
        expert, held or not by the seed's draw)."""
        c = self.cfg
        B, S, Hd = x.shape
        E, k, F = c.n_routed_experts, c.num_experts_per_tok, c.moe_intermediate_size
        held = c.held
        G = len(held)
        init = nn.initializers.normal(c.initializer_range)
        w_r = self.param("router", init, (Hd, E), c.param_dtype)
        wg = self.param("experts_gate", init, (G, Hd, F), c.param_dtype)
        wu = self.param("experts_up", init, (G, Hd, F), c.param_dtype)
        wd = self.param("experts_down", init, (G, F, Hd), c.param_dtype)
        rows = x.reshape(B * S, Hd)
        with scope("moe.route"):
            # exact products of the stored values, float32 sums
            logits = checkpoint_name(
                jnp.dot(rows.astype(jnp.float32), w_r.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST), "router_logits")
            probs = jax.nn.softmax(logits, axis=-1)
            idx = checkpoint_name(lax.top_k(probs, k)[1], "router_idx")
            # the chosen probabilities by a mask, the counts by comparison:
            # a scatter here (top_k's gradient, bincount) is one the TPU
            # compiler has failed on inside the round program's loops
            p = (probs[:, None, :] * jax.nn.one_hot(idx, E, dtype=probs.dtype)).sum(-1)
            cw = p / p.sum(-1, keepdims=True)
            place = np.full((E,), G, np.int32)  # an expert's place, G = absent
            place[list(held)] = np.arange(G)
            real = valid.reshape(B * S, 1)
            slot = jnp.where(real, jnp.asarray(place)[idx], G)
            per_expert = (slot.reshape(-1, 1) == jnp.arange(G)).sum(0)
            n_held = per_expert.sum().astype(jnp.float32)
            self.sow("counters", "moe_slots_held", n_held,
                     init_fn=lambda: 0.0, reduce_fn=jnp.add)
            self.sow("counters", "moe_slots_absent",
                     real.sum().astype(jnp.float32) * k - n_held,
                     init_fn=lambda: 0.0, reduce_fn=jnp.add)
            self.sow("counters", "moe_rows_max",
                     per_expert.max().astype(jnp.float32),
                     init_fn=lambda: 0.0, reduce_fn=jnp.maximum)
        # sort, unsort and combine name themselves fed.moe.route inside, the
        # grouped products fed.moe.experts
        y = block_for(E)(rows, slot, cw, wg.astype(c.dtype),
                         wu.astype(c.dtype), wd.astype(c.dtype))
        with scope("moe.shared"):
            shared = self.shared(c, c.n_shared_experts * F, name="shared_experts")(x)
            if getattr(c, "shared_expert_gate", False):
                # times a sigmoid of a product of its own; both factors are
                # kept for the backward pass (``shared_out``, ``shared_gate_logit``)
                logit = checkpoint_name(dense(c, 1, "shared_expert_gate")(x), "shared_gate_logit")
                shared = (checkpoint_name(shared, "shared_out").astype(jnp.float32)
                          * jax.nn.sigmoid(logit.astype(jnp.float32))).astype(c.dtype)
        return shared + y.reshape(B, S, Hd)
