"""TPU Pallas kernels for the Mamba-2 chunked scan (:mod:`bcfl_tpu.ops.ssm_scan`),
forward and backward.

The same chunked form as ``ssm_scan_xla``, with the same rounding points,
carried by the grid instead of a ``lax.scan``: grid ``(row, chunk, head
block)``, the head block innermost. A head block's state ``S^T`` [N, hb P]
float32 lives in VMEM scratch (one slot per head block, zeroed at a row's
first chunk) and is handed from chunk to chunk there; a chunk's ``C B^T``
[Q, Q] is made once, at its first head block, and kept beside it. Nothing of
a chunk's [Q, Q] decays or masked products leaves VMEM. ``x`` and ``y`` are
read and written as ``[rows, S, H P]``, the bytes the model hands over, so
no chunked copy of an operand is made around the call.

Per head ``h`` of a chunk (``cum`` the running sum of ``dt A``, ``r(.)`` a
rounding to ``x``'s type, the products' operands in that type and their sums
float32, the decays and the state float32, as ``_chunk`` has them)::

    y   = r( r(C B^T o exp(cum_i - cum_j)[i >= j]) @ r(dt x)
             + exp(cum) o (C r(S)^T) + D x )
    S'  = exp(cum_Q) S + r(exp(cum_Q - cum) dt x)^T B

A position's per-head values (``dt``, ``cum`` and their gradients) travel as
rows ``[heads, positions]``; a running sum over a chunk is a product with a
triangle of ones, taken in three bfloat16 pieces of the float32 operand so
that it is as exact as a float32 sum.

Backward (the ``custom_vjp`` of :func:`ssm_scan_pallas`; what is kept of the
forward pass is its arguments, as in ``ssm_scan_xla``): one forward pass over
the chunks writes only the states that enter them ([rows, chunks, N, H P]
float32, ``_fwd_kernel`` with ``emit="states"``), then ``_bwd_kernel`` takes
the chunks last to first with the state's gradient in VMEM and writes ``dx``,
``ddt`` and per-chunk ``dA`` / ``dD`` parts; ``dB`` and ``dC`` sum over the
heads in a chunk's accumulators across its head blocks and leave once, at the
last. Its rounding points are those of ``jax.vjp`` of ``_chunk`` on a TPU (the
gradients of ``xd``, the masked product and ``dt x`` rounded to ``x``'s type);
sums the reference takes in ``x``'s type (``dx``'s three parts, ``dB`` and
``dC``'s two) are float32 here, rounded once.

Selection (:func:`supports`, from shapes, before the call): one group of B and
C, heads of ``P`` lanes that divide 128, head blocks that meet Mosaic's (8,
128) rule, and chunks of a multiple of 128 positions or a row of one chunk.
Everything else is served by ``ssm_scan_xla``.

Kernel playbook: ``/opt/skills/guides/pallas_guide.md``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bcfl_tpu.ops import registry
from bcfl_tpu.ops.pallas_flash import VMEM_DEFAULT_BYTES, VMEM_MAX_BYTES

LANES = 128

#: Heads a grid step takes (clamped to the heads there are): a step's fixed
#: work (its running sums, the chunk's ``C B^T`` shared by its head blocks)
#: against what a step holds. At the state-space cell's shape 32 read best
#: on a TPU v5e in the sweep of the kernels' first version (``python
#: scripts/kernel_bench.py --ops ssm_scan --backward --scan-heads
#: "8;16;32"``) and in the compiled schedule of these (PERF.md section 7).
HEAD_BLOCK = 32

F32 = jnp.float32


def _geometry(H: int, P: int):
    """``(hb, W)``: heads a grid step takes and the lanes of a group of
    whole heads the kernels work on at once; None where no such split
    meets Mosaic's (8, 128) rule."""
    hb = min(HEAD_BLOCK, H)
    W = min(LANES, hb * P)
    if H % hb or W % P or (hb * P) % W:
        return None
    if hb != H and (hb % 8 or (hb * P) % LANES):
        return None
    return hb, W


def supports(x, dt, A, Bm, Cm, D, chunk: int = 256) -> bool:
    """Static predicate (``KernelOp.supports``): may the kernels take this
    call? One group (``B`` and ``C`` [rows, S, N]), whole heads in a lane
    group, a chunk of a multiple of 128 positions (the lanes of a row of
    per-head values) or a row of one chunk, whose blocks are the arrays, and
    both kernels within the VMEM that can be asked for."""
    if x.ndim != 4 or Bm.ndim != 3 or Cm.shape != Bm.shape:
        return False
    _, S, H, P = x.shape
    geometry = _geometry(H, P)
    if geometry is None or not (chunk % LANES == 0 or S <= chunk):
        return False
    (hb, W), N, item = geometry, Bm.shape[-1], jnp.dtype(x.dtype).itemsize
    return all(_vmem_bytes(kernel, chunk, hb, P, N, H, W, item) <= VMEM_MAX_BYTES
               for kernel in ("fwd", "bwd"))


# ------------------------------------------------------------------ pieces


def _dot(a, b, ca: int, cb: int):
    """A product contracting ``a``'s axis ``ca`` with ``b``'s ``cb``, summed
    in float32 (at full precision where both operands are float32)."""
    hi = a.dtype == F32 and b.dtype == F32
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if hi else None)


def _exact(v, ones, ca: int, cb: int):
    """``v`` (float32) against a matrix of zeros and ones, as exact as a
    float32 sum: ``v`` in three bfloat16 pieces, each product exact."""
    ones = jnp.where(ones, 1.0, 0.0).astype(jnp.bfloat16)
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(F32)).astype(jnp.bfloat16)
    return _dot(hi, ones, ca, cb) + _dot(mid, ones, ca, cb) + _dot(lo, ones, ca, cb)


def _causal(Q: int, upper: bool = False):
    """``[Q, Q]``: position ``i`` (row) sees ``j`` (column) where ``i >= j``;
    ``upper``: the transpose, ``i <= j``."""
    i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return i <= j if upper else i >= j


def _spread(parts, P: int, W: int):
    """Per-head values (``[R, 1]`` each, the group's heads in order) -> ``[R,
    W]``: lane ``l`` takes head ``l // P``'s."""
    R = parts[0].shape[0]
    out = jnp.broadcast_to(parts[0], (R, W))
    if len(parts) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
        for j in range(1, len(parts)):
            out = jnp.where(lane >= j * P, parts[j], out)
    return out


def _head_lanes(j: int, P: int, shape):
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= j * P) & (lane < (j + 1) * P)


def _running_sums(dt_r, a_col):
    """A head block's per-head values of a chunk: ``cum`` as rows [hb, Q]
    and columns [Q, hb], ``dt`` as columns, the chunk's last ``cum`` [1,
    hb]."""
    Q = dt_r.shape[1]
    cum_r = _exact(dt_r * a_col, _causal(Q, upper=True), 1, 0)  # sum over q <= i
    cum_c = cum_r.T
    return cum_r, cum_c, dt_r.T, cum_c[Q - 1:Q, :]


def _causal_mask(Q: int):
    """The kernels' causal mask, put on the decays' exponent: 0 where ``i
    >= j``, -inf above (``exp(-inf)`` is 0, never ``inf x 0``; an input, so
    no [Q, Q] mask is made or held in the kernels)."""
    return jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), 0.0, -jnp.inf).astype(F32)


def _group_lanes(cum_c, dt_c, heads, P: int, W: int):
    """A lane group's per-position values: each head's ``cum`` on every lane
    ``[Q, W]`` (its decays' rows), and ``cum`` and ``dt`` spread over the
    group's lanes (lane ``l`` takes head ``l // P``'s). These two lane
    broadcasts a head are all a group makes: what else a lane needs is
    computed from them."""
    Q = cum_c.shape[0]
    cols = [jnp.broadcast_to(cum_c[:, h:h + 1], (Q, W)) for h in heads]
    dt = _spread([jnp.broadcast_to(dt_c[:, h:h + 1], (Q, W)) for h in heads], P, W)
    return cols, _spread(cols, P, W), dt


def _decay(mask_ref, col, row):
    """A head's decays ``exp(cum_i - cum_j)``, 0 for ``j > i``, [Q, Q]
    float32, from its ``cum`` on every lane ``col`` [Q, W] and as a row
    ``row`` [1, Q]."""
    Q = row.shape[1]
    ci = col if col.shape[1] == Q else jnp.broadcast_to(col[:, :1], (Q, Q))
    return jnp.exp(ci - row + mask_ref[...])


# ----------------------------------------------------------------- forward


def _fwd_kernel(mask_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, out_ref,
                state_ref, cb_ref, *, P: int, W: int, emit: str):
    """One (row, chunk, head block). ``emit="y"``: the scan's output;
    ``emit="states"``: the state that enters the chunk (the backward pass's
    first pass), and no ``y``."""
    c = pl.program_id(1)
    k = pl.program_id(2)
    Q = x_ref.shape[0]
    pd = x_ref.dtype
    hb = dt_ref.shape[0]
    gh = W // P

    @pl.when(c == 0)
    def _zero():
        state_ref[k] = jnp.zeros(state_ref.shape[1:], F32)

    Bt = b_ref[...].astype(pd).T  # [N, Q]
    cum_r, cum_c, dt_c, last = _running_sums(dt_ref[...], a_ref[...])
    if emit == "states":  # the hand-over alone
        out_ref[...] = state_ref[k]
        to_end = jnp.exp(last - cum_c) * dt_c  # [Q, hb]
        for g in range(hb // gh):
            lanes = slice(g * W, (g + 1) * W)
            heads = range(g * gh, (g + 1) * gh)
            xd = (x_ref[:, lanes].astype(F32)
                  * _spread([to_end[:, h:h + 1] for h in heads], P, W)).astype(pd)
            state_ref[k, :, lanes] = (_spread([jnp.exp(last[:, h:h + 1]) for h in heads], P, W)
                                      * state_ref[k, :, lanes] + _dot(Bt, xd, 1, 0))
        return

    Cc = c_ref[...].astype(pd)

    @pl.when(k == 0)
    def _cb():
        cb_ref[...] = _dot(Cc, Bt, 1, 0)

    d_row = d_ref[...].T  # [1, hb]
    for g in range(hb // gh):
        lanes = slice(g * W, (g + 1) * W)
        heads = range(g * gh, (g + 1) * gh)
        cols, cum, dt = _group_lanes(cum_c, dt_c, heads, P, W)
        last = cum[Q - 1:Q]  # [1, W]
        xf = x_ref[:, lanes].astype(F32)
        S = state_ref[k, :, lanes]  # [N, W]
        dtx = (xf * dt).astype(pd)
        ys = [_dot((cb_ref[...] * _decay(mask_ref, cols[j], cum_r[h:h + 1])).astype(pd), dtx, 1, 0)
              for j, h in enumerate(heads)]
        y = _spread(ys, P, W) + jnp.exp(cum) * _dot(Cc, S.astype(pd), 1, 0)
        y = y + _spread([d_row[:, h:h + 1] for h in heads], P, W) * xf
        out_ref[:, lanes] = y.astype(out_ref.dtype)
        xd = (xf * (jnp.exp(last - cum) * dt)).astype(pd)
        state_ref[k, :, lanes] = jnp.exp(last) * S + _dot(Bt, xd, 1, 0)


# ---------------------------------------------------------------- backward


def _bwd_kernel(mask_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, s_ref, dy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                ds_ref, cb_ref, dcb_ref, dbacc_ref, dcacc_ref, rows_ref, heads_ref,
                *, P: int, W: int):
    """One (row, chunk, head block), the chunks last to first. ``ds_ref``
    holds each head block's gradient of the state its chunk hands on;
    ``rows_ref`` [2, hb, Q] the chunk's per-head gradients of ``cum`` and
    ``dt``; ``heads_ref`` [2, hb, 1] those of the last ``cum`` and of ``D``."""
    c = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    Q = x_ref.shape[0]
    pd = x_ref.dtype
    hb = dt_ref.shape[0]
    gh = W // P

    @pl.when(c == 0)
    def _zero_state():
        ds_ref[k] = jnp.zeros(ds_ref.shape[1:], F32)

    Bc = b_ref[...].astype(pd)
    Cc = c_ref[...].astype(pd)
    Bt, Ct = Bc.T, Cc.T  # [N, Q]

    @pl.when(k == 0)
    def _chunk_start():
        cb_ref[...] = _dot(Cc, Bt, 1, 0)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        dbacc_ref[...] = jnp.zeros_like(dbacc_ref)
        dcacc_ref[...] = jnp.zeros_like(dcacc_ref)

    dt_r = dt_ref[...]
    a_col = a_ref[...]
    cum_r, cum_c, dt_c, _ = _running_sums(dt_r, a_col)
    d_row = d_ref[...].T  # [1, hb]
    for g in range(hb // gh):
        lanes = slice(g * W, (g + 1) * W)
        heads = range(g * gh, (g + 1) * gh)
        cols, cum, dt_e = _group_lanes(cum_c, dt_c, heads, P, W)
        last = cum[Q - 1:Q]
        e_e = jnp.exp(cum)
        r_e = jnp.exp(last - cum)  # decay to the chunk's end
        te_e = r_e * dt_e
        e_last = jnp.exp(last)  # [1, W]
        xf = x_ref[:, lanes].astype(F32)
        dy = dy_ref[:, lanes]
        dyf = dy.astype(F32)
        S = s_ref[:, lanes]
        Sb = S.astype(pd)
        dSn = ds_ref[k, :, lanes]
        dSp = dSn.astype(pd)
        # the state handed on: S' = exp(cum_Q) S + r(to_end x)^T B
        xd = (xf * te_e).astype(pd)
        dxd = _dot(Bc, dSp, 1, 0).astype(pd).astype(F32)
        dbacc_ref[...] += _dot(xd, dSp, 1, 1)
        # what entered the chunk: exp(cum) o (C r(S)^T)
        G = _dot(Cc, Sb, 1, 0)
        dG = (e_e * dyf).astype(pd)
        dcacc_ref[...] += _dot(dG, Sb, 1, 1)
        ds_ref[k, :, lanes] = e_last * dSn + _dot(Ct, dG, 1, 0)
        # inside the chunk: r(C B^T o decay) @ r(dt x)
        dtx = (xf * dt_e).astype(pd)
        ddtx = jnp.zeros_like(xf)
        dcb = 0.0
        for j, h in enumerate(heads):
            decay = _decay(mask_ref, cols[j], cum_r[h:h + 1])
            cbd = cb_ref[...] * decay
            dyh = jnp.where(_head_lanes(j, P, dy.shape), dy, 0) if gh > 1 else dy
            dm = _dot(dyh, dtx, 1, 1).astype(pd).astype(F32)
            ddtx = ddtx + _dot(cbd.astype(pd), dyh, 0, 0)
            dcb = dcb + dm * decay
            dd = dm * cbd  # the gradient of cum_i - cum_j
            rows_ref[0, h:h + 1, :] = (jnp.sum(dd.T, axis=0, keepdims=True)
                                       - jnp.sum(dd, axis=0, keepdims=True))
        dcb_ref[...] += dcb
        ddtx = ddtx.astype(pd).astype(F32)
        d_e = _spread([d_row[:, h:h + 1] for h in heads], P, W)
        dx_ref[:, lanes] = (d_e * dyf + te_e * dxd + dt_e * ddtx).astype(dx_ref.dtype)
        # per-head sums over the group's lanes: rows [1, Q], scalars [1, 1]
        arg = te_e * xf * dxd
        by_pos_dt = (xf * (r_e * dxd + ddtx)).T  # [W, Q]
        by_pos_cum = (e_e * dyf * G - arg).T
        by_lane_d = jnp.sum(dyf * xf, axis=0, keepdims=True)  # [1, W]
        by_lane_last = (jnp.sum(arg, axis=0, keepdims=True),
                        jnp.sum(dSn * S, axis=0, keepdims=True))
        for j, h in enumerate(heads):
            seg = slice(j * P, (j + 1) * P)

            def per_head(v, seg=seg):
                return jnp.sum(v[:, seg], axis=1, keepdims=True)  # [1, 1]

            rows_ref[0, h:h + 1, :] += jnp.sum(by_pos_cum[seg], axis=0, keepdims=True)
            rows_ref[1, h:h + 1, :] = jnp.sum(by_pos_dt[seg], axis=0, keepdims=True)
            heads_ref[0, h:h + 1, :] = (per_head(by_lane_last[0])
                                        + per_head(e_last * by_lane_last[1]))
            heads_ref[1, h:h + 1, :] = per_head(by_lane_d)

    # cum_Q's gradient joins cum's at the chunk's last position; then back
    # through the running sum (d_a_j = sum over i >= j) to dt and A
    lane = jax.lax.broadcasted_iota(jnp.int32, (hb, Q), 1)
    dcum = rows_ref[0] + jnp.where(lane == Q - 1, heads_ref[0], 0.0)
    d_a = _exact(dcum, _causal(Q), 1, 0)
    ddt_ref[...] = rows_ref[1] + d_a * a_col
    da_ref[...] = jnp.sum(d_a * dt_r, axis=1, keepdims=True)
    dd_ref[...] = heads_ref[1]

    @pl.when(k == nk - 1)
    def _chunk_end():
        dcb = dcb_ref[...].astype(pd)
        db_ref[...] = (dbacc_ref[...] + _dot(dcb, Cc, 0, 0)).astype(db_ref.dtype)
        dc_ref[...] = (dcacc_ref[...] + _dot(dcb, Bc, 1, 0)).astype(dc_ref.dtype)


# ------------------------------------------------------------------- calls


def _vmem_bytes(kernel: str, Q: int, hb: int, P: int, N: int, H: int, W: int, item: int) -> int:
    """The scoped VMEM a kernel needs: its blocks twice (the pipeline's
    double buffers), its scratch, and temporaries: a few [Q, Q] float32
    tiles and a few [Q, W] float32 values a lane group of the head block
    (the groups' code is unrolled). The temporaries are fitted to the TPU
    compiler's own count at the cell's shape (a described v5e, heads of 64,
    state 128, chunks of 256, bfloat16): 8.1 / 10.4 MiB forward and 14.8 /
    19.2 MiB backward at 16 / 32 heads a step, against 9.6 / 13.6 and 18.4
    / 27.6 here: an upper estimate."""
    hbP = hb * P
    groups = hbP // W
    bc = Q * N * item  # a chunk of B or C
    per_head = hb * Q * 4 + 2 * hb * LANES * 4  # dt's rows; A and D, lane-padded
    if kernel == "bwd":
        blocks = 3 * Q * hbP * item + N * hbP * 4 + 4 * bc + 2 * per_head
        scratch = H * P * N * 4 + 2 * Q * Q * 4 + 2 * Q * N * 4 + 2 * per_head
        temps = 16 * Q * Q * 4 + 5 * groups * Q * W * 4
    else:
        blocks = Q * hbP * item + max(Q * hbP * item, N * hbP * 4) + 2 * bc + per_head
        scratch = H * P * N * 4 + Q * Q * 4
        temps = 4 * Q * Q * 4 + 2 * groups * Q * W * 4
    return 2 * blocks + scratch + temps


def _params(kernel, Q, hb, P, N, H, W, item):
    need = _vmem_bytes(kernel, Q, hb, P, N, H, W, item)
    assert need <= VMEM_MAX_BYTES, (kernel, need)  # ``supports`` refused it
    return None if need <= VMEM_DEFAULT_BYTES else pltpu.CompilerParams(vmem_limit_bytes=need)


def _layout(x, dt, A, Bm, Cm, D, chunk):
    """The kernels' operands: ``x`` as [rows, S', H P] (a reshape: the same
    bytes), ``dt`` as rows [rows, H, S'], ``A`` and ``D`` as columns [H, 1],
    every row padded to whole chunks with zeros (a padded position has ``dt``
    0: it decays nothing and adds nothing)."""
    H = x.shape[2]
    return (_rows(x, chunk), jnp.swapaxes(_rows(dt.astype(F32), chunk), 1, 2),
            A.astype(F32).reshape(H, 1), D.astype(F32).reshape(H, 1),
            _rows(Bm, chunk), _rows(Cm, chunk))


def _rows(a, chunk: int):
    """[rows, S, ...] -> [rows, S', last] with S' whole chunks (zeros) and
    the trailing axes of ``a`` flattened (a reshape: the same bytes)."""
    b, S = a.shape[:2]
    a = a.reshape(b, S, -1)
    pad = -S % chunk
    return jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a


def _specs(Q: int, hb: int, P: int, N: int, nc: int, reverse: bool = False):
    """The blocks of a grid step (row ``r``, chunk ``c`` or, ``reverse``, the
    ``c``-th from the last, head block ``k``), by operand kind."""
    hbP = hb * P

    def chunk(c):
        return nc - 1 - c if reverse else c

    return {
        "heads": pl.BlockSpec((None, Q, hbP), lambda r, c, k: (r, chunk(c), k)),
        "dt": pl.BlockSpec((None, hb, Q), lambda r, c, k: (r, k, chunk(c))),
        "col": pl.BlockSpec((hb, 1), lambda r, c, k: (k, 0)),
        "mask": pl.BlockSpec((Q, Q), lambda r, c, k: (0, 0)),
        "bc": pl.BlockSpec((None, Q, N), lambda r, c, k: (r, chunk(c), 0)),
        "state": pl.BlockSpec((None, None, N, hbP), lambda r, c, k: (r, chunk(c), 0, k)),
        "part": pl.BlockSpec((None, None, hb, 1), lambda r, c, k: (r, chunk(c), k, 0)),
    }


def _static(x):
    """What the kernels read besides the shapes, handed to their jitted
    calls as static arguments: the heads a grid step takes (``HEAD_BLOCK``,
    which the bench sets) and interpret mode."""
    _, _, H, P = x.shape
    return _geometry(H, P)[0], registry.interpret_mode()


# Each kernel call sits in a ``jax.jit``: the layers of a model call the op
# with the same shapes, so a kernel is traced once and lowered once a
# program (JAX keeps one lowering of a jitted function a module; XLA inlines
# it at each call site, under that site's scopes). Unwrapped, each call
# lowered its kernels again: about 4 s a mamba layer of host time in every
# compile, cached or not.
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _forward(x, dt, A, Bm, Cm, D, chunk: int, emit: str, hb: int, interpret: bool):
    b, S, H, P = x.shape
    N = Bm.shape[-1]
    W = min(LANES, hb * P)
    xr, dtr, a_col, d_col, Bp, Cp = _layout(x, dt, A, Bm, Cm, D, chunk)
    nc = xr.shape[1] // chunk
    sp = _specs(chunk, hb, P, N, nc)
    if emit == "y":
        out_spec, out_shape = sp["heads"], jax.ShapeDtypeStruct(xr.shape, x.dtype)
    else:
        out_spec, out_shape = sp["state"], jax.ShapeDtypeStruct((b, nc, N, H * P), F32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, W=W, emit=emit),
        grid=(b, nc, H // hb),
        in_specs=[sp["mask"], sp["heads"], sp["dt"], sp["col"], sp["col"], sp["bc"], sp["bc"]],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((H // hb, N, hb * P), F32), pltpu.VMEM((chunk, chunk), F32)],
        compiler_params=_params("fwd", chunk, hb, P, N, H, W, x.dtype.itemsize),
        interpret=interpret,
    )(_causal_mask(chunk), xr, dtr, a_col, d_col, Bp, Cp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssm_scan_pallas(x, dt, A, Bm, Cm, D, chunk=256):
    """``ssm_scan_xla``'s contract: ``x`` [rows, S, H, P], ``dt`` [rows, S,
    H], ``A`` and ``D`` [H], ``B`` and ``C`` [rows, S, N] -> ``y`` as ``x``."""
    b, S, H, P = x.shape
    return _forward(x, dt, A, Bm, Cm, D, chunk, "y", *_static(x))[:, :S].reshape(b, S, H, P)


def _vjp_fwd(x, dt, A, Bm, Cm, D, chunk):
    return ssm_scan_pallas(x, dt, A, Bm, Cm, D, chunk), (x, dt, A, Bm, Cm, D)


def _vjp_bwd(chunk, res, dy):
    return _backward(*res, dy, chunk, *_static(res[0]))


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _backward(x, dt, A, Bm, Cm, D, dy, chunk: int, hb: int, interpret: bool):
    b, S, H, P = x.shape
    N = Bm.shape[-1]
    W = min(LANES, hb * P)
    entered = _forward(x, dt, A, Bm, Cm, D, chunk, "states", hb, interpret)
    xr, dtr, a_col, d_col, Bp, Cp = _layout(x, dt, A, Bm, Cm, D, chunk)
    dyr = _rows(dy, chunk)
    nc = xr.shape[1] // chunk
    sp = _specs(chunk, hb, P, N, nc, reverse=True)
    part = jax.ShapeDtypeStruct((b, nc, H, 1), F32)
    dx, ddt, dB, dC, dA, dD = pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, W=W),
        grid=(b, nc, H // hb),
        in_specs=[sp["mask"], sp["heads"], sp["dt"], sp["col"], sp["col"], sp["bc"], sp["bc"],
                  sp["state"], sp["heads"]],
        out_specs=[sp["heads"], sp["dt"], sp["bc"], sp["bc"], sp["part"], sp["part"]],
        out_shape=[jax.ShapeDtypeStruct(xr.shape, x.dtype), jax.ShapeDtypeStruct(dtr.shape, F32),
                   jax.ShapeDtypeStruct(Bp.shape, Bm.dtype), jax.ShapeDtypeStruct(Cp.shape, Cm.dtype),
                   part, part],
        scratch_shapes=[pltpu.VMEM((H // hb, N, hb * P), F32),  # the state's gradient
                        pltpu.VMEM((chunk, chunk), F32),  # C B^T
                        pltpu.VMEM((chunk, chunk), F32),  # its gradient, over the heads
                        pltpu.VMEM((chunk, N), F32),  # dB, over the heads
                        pltpu.VMEM((chunk, N), F32),  # dC, over the heads
                        pltpu.VMEM((2, hb, chunk), F32),
                        pltpu.VMEM((2, hb, 1), F32)],
        compiler_params=_params("bwd", chunk, hb, P, N, H, W, x.dtype.itemsize),
        interpret=interpret,
    )(_causal_mask(chunk), xr, dtr, a_col, d_col, Bp, Cp, entered, dyr)
    return (dx[:, :S].reshape(x.shape), jnp.swapaxes(ddt, 1, 2)[:, :S].astype(dt.dtype),
            dA.sum((0, 1))[:, 0].astype(A.dtype), dB[:, :S], dC[:, :S],
            dD.sum((0, 1))[:, 0].astype(D.dtype))


ssm_scan_pallas.defvjp(_vjp_fwd, _vjp_bwd)
