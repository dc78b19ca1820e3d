"""Pallas TPU kernels: Kimi Delta Attention's chunked gated delta rule
(``ops/kda.py``) with the carried state and each chunk's tiles kept on the
chip, forward and backward.

The ``lax`` form runs a ``lax.scan`` over the chunks whose every step is a
dozen small fusions and one triangular solve (a custom call of its own), each
with its operands in HBM, three times a step (forward, the block's
rematerialised forward, and the scan's backward).  Here a chunk's work stays
in VMEM from its operands to its outputs, with the mathematics and the
precisions of that form: q, k, v in the model's dtype, everything inside a
chunk float32, every product at ``HIGHEST`` precision (Mosaic lowers it as
``#tpu.contract_precision<fp32>``), only differences ``G_t - G_j <= 0``
exponentiated.  The running sums ``G`` of ``g`` inside each chunk stay
outside, in XLA: the kernels take ``G`` as an operand and the backward
returns its cotangent, which XLA's transpose of its own ``cumsum`` turns into
``g``'s.

Per head and chunk of ``c`` tokens (a head is 128 lanes wide, or a whole
number of such registers), with ``S`` the (d_k x d_v) state entering it,
kept transposed (``S^T``, (d_v, d_k)) so that the decay of its channels is a
row broadcast:

- ``PA`` (strictly lower) and ``PM`` (lower) ``= sum_d x_td k_jd exp(G_td -
  G_jd)`` for x = k, q: pairs in two sub-blocks of ``SUB`` tokens split at
  the later one's first token ``r`` (two factors of at most 1; one product
  a sub-block on the matrix unit), pairs inside one sub-block with their
  decays whole, taken a lag ``t - j`` at a time over all the chunk's rows
  (the earlier token's row brought down by a sublane rotation);
- ``(I + diag(beta) PA)^-1`` EXACTLY, by the block form of forward
  substitution: the inverses of the diagonal blocks doubled from 1 x 1 to
  c x c, ``X_2b = X_b - X_b L_b X_b`` with ``L_b`` the lower-left quarter of
  each 2b block (an exact rearrangement of row-by-row substitution, no series);
- ``U = (I + A)^-1 diag(beta) (V - (K o exp G) S)``, ``O = scale [(Q o exp G)
  S + PM U]``, ``S_out = Diag(exp G_c) S + (K o exp(G_c - G))^T U``.

A product on the matrix unit costs a 128 x 128 tile of weights whatever the
rows it streams, so the (c, c) tiles of ``128 / c`` heads (two at the chunk
of 64) go through it together, block-diagonally: the inverse, ``U``, ``PM U``
and their transposes in the backward, at the cost of one head's.

``_forward``  grid (batch, head block, chunk), the chunks in order and
    innermost: the states of the block's heads are VMEM scratch that one
    chunk hands the next, zeroed at the row's first chunk.  Under
    differentiation it also writes the state ENTERING each chunk (2 MB a
    chunk at 32 heads of 128), the one thing kept for the backward beside
    the operands.

``_backward``  ONE kernel, the chunks in reverse, carrying ``dS`` as the
    forward carries ``S``.  A chunk rebuilds ``PA``, ``PM``, the inverse and
    ``U`` from the operands and the saved entry state (this is the chunk's
    rematerialised forward) and yields the cotangents of q, k, v, beta and
    ``G``; through the pair sums ``dG = x o dx - k o dk`` (each pair's decay
    enters as ``G_t - G_j``), so no (c, c, d) cotangent is formed.

Jax cannot partition a Mosaic call over a mesh: a caller that holds a mesh
stays on the ``lax`` scan (``ops/kda.kda_path`` decides).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

#: tokens of a sub-block whose pairs take their decays whole (``ops/kda.SUB``)
SUB = 16
_LANES = 128
#: heads a grid step takes at most, unrolled (a head's work is its own but
#: for the products its group shares, so this sets the grid's step count and
#: how many heads the scheduler can interleave).  Read when a shape is first
#: traced
HEADS = 4
_VMEM_LIMIT = 100 << 20
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HI, preferred_element_type=_F32)


def heads_per_step(h: int) -> int:
    """Heads a grid step takes: the most, up to ``HEADS``, that divide ``h``."""
    return next(hb for hb in range(min(HEADS, h), 0, -1) if h % hb == 0)


def tiles(q, v, chunk: int) -> bool:
    """Whether the kernels take q (b, s, h, d_k) with v (b, s, h, d_v) in
    chunks of ``chunk``: whole chunks of whole sub-blocks, and key and value
    heads of whole 128-lane registers."""
    s, _, dk = q.shape[1:]
    dv = v.shape[-1]
    return chunk > 0 and s % chunk == 0 and chunk % SUB == 0 and dk % _LANES == 0 and dv % _LANES == 0


def _iotas(c: int):
    """Row and column of a (c, c) tile, and each row's place in its sub-block
    as a (c, 1) column."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    local = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) & (SUB - 1)
    return row, col, local


def _across(q, k, gam, i: int):
    """Sub-block i's rows against every earlier sub-block, split at its first
    token r: (its rows of k and q, each times exp(G - G_r)), exp(G_r - G_j)
    for the chunk's rows j < r (0 from r on), and its rows' slice."""
    r = i * SUB
    rows = slice(r, r + SUB)
    ref = gam[r:r + 1]
    down = jnp.exp(gam[rows] - ref)
    earlier = jax.lax.broadcasted_iota(jnp.int32, (gam.shape[0], 1), 0) < r
    up = jnp.exp(jnp.where(earlier, ref - gam, -jnp.inf))
    return jnp.concatenate([k[rows] * down, q[rows] * down], axis=0), up, down, rows


def _inside(k, gam, lag: int, local):
    """For the pairs ``(t, t - lag)`` inside one sub-block: the earlier
    token's k brought to row t, and the pair's decay exp(G_t - G_{t-lag})
    (0 where row t has no such partner in its sub-block)."""
    if lag == 0:
        return k, 1.0
    earlier = pltpu.roll(gam, lag, 0)
    return pltpu.roll(k, lag, 0), jnp.exp(jnp.where(local >= lag, gam - earlier, -jnp.inf))


def _pairs(q, k, gam):
    """q, k, gam: (c, d) float32 -> PA, PM (c, c): ``sum_d x_td k_jd exp(G_td -
    G_jd)`` for x = k over ``j < t`` and x = q over ``j <= t``, 0 elsewhere."""
    c = k.shape[0]
    row, col, local = _iotas(c)
    zero = jnp.zeros((SUB, c), _F32)
    pa, pm = [zero], [zero]
    for i in range(1, c // SUB):
        xr, up, _, _ = _across(q, k, gam, i)
        p = _mm(xr, k * up, _NT)                                         # (2 SUB, c)
        pa.append(p[:SUB])
        pm.append(p[SUB:])
    pa, pm = jnp.concatenate(pa, axis=0), jnp.concatenate(pm, axis=0)
    for lag in range(SUB):
        ks, decay = _inside(k, gam, lag, local)
        ksd = ks * decay
        at = col == row - lag
        if lag:
            pa = pa + jnp.where(at, jnp.sum(k * ksd, axis=1, keepdims=True), 0.0)
        pm = pm + jnp.where(at, jnp.sum(q * ksd, axis=1, keepdims=True), 0.0)
    return pa, pm


def _pairs_cotangents(heads):
    """The cotangents of q, k and G through ``_pairs``, given PA's and PM's:
    a group's heads' (q, k, gam, dPA, dPM) -> each head's (dq, dk, dG), the
    products across sub-blocks of the group's heads taken together."""
    c, d = heads[0][1].shape
    row, col, local = _iotas(c)
    zero = jnp.zeros((SUB, d), _F32)
    dxa = [[zero] for _ in heads]      # through each pair's later token (x = k for PA, q for PM)
    dxm = [[zero] for _ in heads]
    dkj = [jnp.zeros((c, d), _F32) for _ in heads]      # through each pair's earlier token
    for i in range(1, c // SUB):
        across = [_across(q, k, gam, i) for q, k, gam, _, _ in heads]
        rows = across[0][3]
        dp = _block_diag([jnp.concatenate([dpa[rows], dpm[rows]], axis=0) for _, _, _, dpa, dpm in heads])
        dxr = _mm(dp, jnp.concatenate([h[1] * a[1] for h, a in zip(heads, across)], axis=0))   # (n 2 SUB, d)
        dkr = _mm(dp, jnp.concatenate([a[0] for a in across], axis=0), _TN)                      # (n c, d)
        for h, (_, up, down, _) in enumerate(across):
            dxa[h].append(dxr[2 * h * SUB:(2 * h + 1) * SUB] * down)
            dxm[h].append(dxr[(2 * h + 1) * SUB:(2 * h + 2) * SUB] * down)
            dkj[h] = dkj[h] + dkr[h * c:(h + 1) * c] * up
    out = []
    for (q, k, gam, dpa, dpm), dxa_h, dxm_h, dkj_h in zip(heads, dxa, dxm, dkj):
        dxa_h, dxm_h = jnp.concatenate(dxa_h, axis=0), jnp.concatenate(dxm_h, axis=0)
        for lag in range(SUB):
            ks, decay = _inside(k, gam, lag, local)
            ksd = ks * decay
            at = col == row - lag
            dpm_l = jnp.sum(jnp.where(at, dpm, 0.0), axis=1, keepdims=True)    # (c, 1): dPM[t, t - lag]
            dxm_h = dxm_h + dpm_l * ksd
            later = dpm_l * q
            if lag:
                dpa_l = jnp.sum(jnp.where(at, dpa, 0.0), axis=1, keepdims=True)
                dxa_h = dxa_h + dpa_l * ksd
                later = later + dpa_l * k
            # what row t gives its partner t - lag, taken back up to that row
            dkj_h = dkj_h + (pltpu.roll(later * decay, c - lag, 0) if lag else later * decay)
        out.append((dxm_h, dxa_h + dkj_h, k * dxa_h + q * dxm_h - k * dkj_h))
    return out


def _unit_lower_inverse(a, c):
    """(I + a)^-1 for a strictly lower, block-diagonal in blocks of c: the
    inverses of the diagonal blocks doubled from 1 x 1 to c x c,
    ``X_2b = X_b - X_b L_b X_b`` with ``L_b`` the lower-left b x b quarter of
    each 2b block."""
    row, col, _ = _iotas(a.shape[0])
    inv = jnp.where(row == col, 1.0, 0.0)
    b = 1
    while b < c:
        shift = b.bit_length() - 1
        quarter = ((row >> (shift + 1)) == (col >> (shift + 1))) & ((row >> shift) & 1 == 1) & ((col >> shift) & 1 == 0)
        lower = jnp.where(quarter, a, 0.0)
        inv = inv - lower if b == 1 else inv - _mm(_mm(inv, lower), inv)
        b *= 2
    return inv


def _block_diag(ms):
    """n tiles of one shape (r, c) -> their block-diagonal (n r, n c)."""
    z = jnp.zeros(ms[0].shape, _F32)
    return jnp.concatenate([jnp.concatenate([m if i == j else z for j in range(len(ms))], axis=1)
                            for i, m in enumerate(ms)], axis=0)


def group_size(c: int) -> int:
    """Heads whose (c, c) tiles share one 128 x 128 tile of the matrix unit
    (the inverse's doubling runs on blocks at multiples of their size)."""
    return _LANES // c if _LANES % c == 0 else 1


def _groups(hb: int, c: int):
    """A grid step's heads in groups of ``group_size(c)``."""
    n = min(group_size(c), hb)
    return [list(range(j0, min(j0 + n, hb))) for j0 in range(0, hb, n)]


def _chunks(heads):
    """A group of heads' chunks: each head's (q, k, v, gam, beta, st) ->
    per head what the forward and the backward both build, the (c, c) tiles of
    the group's heads taken together block-diagonally."""
    c = heads[0][1].shape[0]
    ts = []
    for q, k, v, gam, beta, st in heads:
        eg = jnp.exp(gam)
        kp, qp = k * eg, q * eg
        by_state = _mm(jnp.concatenate([kp, qp], axis=0), st, _NT)       # (2c, d_v): (K o e^G) S, (Q o e^G) S
        pa, pm = _pairs(q, k, gam)
        ts.append(dict(eg=eg, kp=kp, qp=qp, qs=by_state[c:], w=v - by_state[:c], pa=pa, pm=pm,
                       last=gam[c - 1:c]))
    inv = _unit_lower_inverse(_block_diag([h[4] * t["pa"] for h, t in zip(heads, ts)]), c)
    u = _mm(inv, jnp.concatenate([h[4] * t["w"] for h, t in zip(heads, ts)], axis=0))
    return ts, inv, u


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *refs, hb, scale, save):
    s_ref = refs[0] if save else None
    state_sc = refs[-1]
    dk, dv = q_ref.shape[1] // hb, v_ref.shape[1] // hb

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_sc[...] = jnp.zeros(state_sc.shape, _F32)

    c = q_ref.shape[0]
    for group in _groups(hb, c):
        heads = []
        for j in group:
            kl, vl = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
            q, k, v = (r[:, sl].astype(_F32) for r, sl in ((q_ref, kl), (k_ref, kl), (v_ref, vl)))
            st = state_sc[vl, :]
            if save:
                s_ref[vl, :] = st
            heads.append((q, k, v, g_ref[:, kl], beta_ref[:, j:j + 1], st))
        ts, _, u = _chunks(heads)
        intra = _mm(_block_diag([t["pm"] for t in ts]), u)                    # PM U, a head's rows each
        for i, (j, (q, k, v, gam, beta, st), t) in enumerate(zip(group, heads, ts)):
            vl, rows = slice(j * dv, (j + 1) * dv), slice(i * c, (i + 1) * c)
            o_ref[:, vl] = (scale * (t["qs"] + intra[rows])).astype(o_ref.dtype)
            state_sc[vl, :] = st * jnp.exp(t["last"]) + _mm(u[rows], k * jnp.exp(t["last"] - gam), _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_sc, *, hb, scale):
    c = q_ref.shape[0]
    dk_w, dv_w = q_ref.shape[1] // hb, v_ref.shape[1] // hb
    row, col, _ = _iotas(c)
    lane_of = jax.lax.broadcasted_iota(jnp.int32, (c, hb), 1)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1

    @pl.when(pl.program_id(2) == 0)                 # the row's last chunk: nothing comes after
    def _():
        dstate_sc[...] = jnp.zeros(dstate_sc.shape, _F32)

    dbeta_cols = jnp.zeros((c, hb), _F32)
    for group in _groups(hb, c):
        heads, grads = [], []
        for j in group:
            kl, vl = slice(j * dk_w, (j + 1) * dk_w), slice(j * dv_w, (j + 1) * dv_w)
            q, k, v = (r[:, sl].astype(_F32) for r, sl in ((q_ref, kl), (k_ref, kl), (v_ref, vl)))
            heads.append((q, k, v, g_ref[:, kl], beta_ref[:, j:j + 1], s_ref[vl, :]))
            grads.append((scale * do_ref[:, vl].astype(_F32), dstate_sc[vl, :]))
        ts, inv, u_all = _chunks(heads)
        fs = [jnp.exp(t["last"] - h[3]) for h, t in zip(heads, ts)]             # exp(G_c - G)
        kfs = [h[1] * f for h, f in zip(heads, fs)]
        # dU = PM^T dO + (K o e^{G_c - G}) dS_out, then (I + A)^-T dU: a group's heads at once
        du_all = (_mm(_block_diag([t["pm"] for t in ts]), jnp.concatenate([g[0] for g in grads], axis=0), _TN)
                  + jnp.concatenate([_mm(kf, g[1], _NT) for kf, g in zip(kfs, grads)], axis=0))
        drhs_all = _mm(inv, du_all, _TN)
        pairs, rest = [], []          # what the pair sums' cotangents take, and the rest of each head's
        for i, j in enumerate(group):
            kl, vl = slice(j * dk_w, (j + 1) * dk_w), slice(j * dv_w, (j + 1) * dv_w)
            (q, k, v, gam, beta, st), t, (dos, dnext) = heads[i], ts[i], grads[i]
            rows = slice(i * c, (i + 1) * c)
            u, f, kf, drhs = u_all[rows], fs[i], kfs[i], drhs_all[rows]
            w, pa, el = t["w"], t["pa"], jnp.exp(t["last"])
            dpm = jnp.where(col <= row, _mm(dos, u, _NT), 0.0)
            dkf = _mm(u, dnext)
            da = -jnp.where(col < row, _mm(drhs, u, _NT), 0.0)
            dw = beta * drhs
            dbeta = jnp.sum(drhs * w, axis=1, keepdims=True) + jnp.sum(da * pa, axis=1, keepdims=True)
            dbeta_cols = jnp.where(lane_of == j, dbeta, dbeta_cols)
            d_by_state = _mm(jnp.concatenate([-dw, dos], axis=0), st)   # (2c, d_k): through K o e^G, Q o e^G
            dkp, dqp = d_by_state[:c], d_by_state[c:]
            dstate_sc[vl, :] = dnext * el + _mm(jnp.concatenate([dos, -dw], axis=0),
                                                jnp.concatenate([t["qp"], t["kp"]], axis=0), _TN)
            dlast = (jnp.sum(dnext * st, axis=0, keepdims=True) * el
                     + jnp.sum(dkf * kf, axis=0, keepdims=True))         # (1, d_k)
            pairs.append((q, k, gam, beta * da, dpm))
            eg = t["eg"]
            rest.append((dqp * eg, dkp * eg + dkf * f,
                         dkp * t["kp"] + dqp * t["qp"] - dkf * kf + jnp.where(at_last, dlast, 0.0)))
            dv_ref[:, vl] = dw.astype(dv_ref.dtype)
        for j, (dq, dk, dgam), (dq_rest, dk_rest, dgam_rest) in zip(group, _pairs_cotangents(pairs), rest):
            kl = slice(j * dk_w, (j + 1) * dk_w)
            dq_ref[:, kl] = (dq + dq_rest).astype(dq_ref.dtype)
            dk_ref[:, kl] = (dk + dk_rest).astype(dk_ref.dtype)
            dg_ref[:, kl] = dgam + dgam_rest
    dbeta_ref[...] = dbeta_cols


def _operands(q, k, v, gam, beta, chunk: int, hb: int, reverse: bool = False):
    """The kernels' views of the operands and their block specs by kind, for
    grid (batch, head block, chunk); with ``reverse`` step ``ci`` is given the
    chunk ``nc - 1 - ci``."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    nc, nh = s // chunk, h // hb
    wide = lambda t: t.reshape(b, s, h * t.shape[-1])
    # a head's beta as a column of its block's (chunk, hb) tile
    arrays = (wide(q), wide(k), wide(v), wide(gam), jnp.moveaxis(beta.reshape(b, s, nh, hb), 2, 1))
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    spec = lambda block, index: pl.BlockSpec(block, lambda bi, hi, ci: index(bi, hi, at(ci)))
    kinds = dict(
        keys=spec((None, chunk, hb * dk), lambda bi, hi, ci: (bi, ci, hi)),
        values=spec((None, chunk, hb * dv), lambda bi, hi, ci: (bi, ci, hi)),
        col=spec((None, None, chunk, hb), lambda bi, hi, ci: (bi, hi, ci, 0)),
        state=spec((None, None, hb * dv, dk), lambda bi, hi, ci: (bi, ci, hi, 0)))
    specs = [kinds["keys"], kinds["keys"], kinds["values"], kinds["keys"], kinds["col"]]
    return arrays, specs, kinds


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT))


# jitted, so that a model's layers of one shape share ONE trace of the
# unrolled kernel body and one lowering of it
@functools.partial(jax.jit, static_argnames=("chunk", "scale", "save", "interpret"))
def _forward(q, k, v, gam, beta, chunk: int, scale: float, save: bool, interpret: bool):
    """-> the output (b, s, h, d_v) in q's dtype and, with ``save``, the state
    entering each chunk, transposed: (b, s // chunk, h * d_v, d_k) float32."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    hb = heads_per_step(h)
    nc, nh = s // chunk, h // hb
    arrays, specs, kinds = _operands(q, k, v, gam, beta, chunk, hb)
    out_specs = [kinds["values"], kinds["state"]][:1 + save]
    out_shape = [jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
                 jax.ShapeDtypeStruct((b, nc, h * dv, dk), _F32)][:1 + save]
    out = _call(functools.partial(_fwd_kernel, hb=hb, scale=scale, save=save), "fedml_kda_fwd",
                (b, nh, nc), specs, out_specs, out_shape, [pltpu.VMEM((hb * dv, dk), _F32)],
                interpret)(*arrays)
    return (out[0].reshape(b, s, h, dv),) + tuple(out[1:])


@functools.partial(jax.jit, static_argnames=("chunk", "scale", "interpret"))
def _backward(q, k, v, gam, beta, states, d_out, chunk: int, scale: float, interpret: bool):
    """-> the cotangents of q, k, v, gam and beta."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    hb = heads_per_step(h)
    nc, nh = s // chunk, h // hb
    arrays, specs, kinds = _operands(q, k, v, gam, beta, chunk, hb, reverse=True)
    dq, dk_, dv_, dgam, dbeta = _call(
        functools.partial(_bwd_kernel, hb=hb, scale=scale), "fedml_kda_bwd", (b, nh, nc),
        specs + [kinds["state"], kinds["values"]],
        [kinds["keys"], kinds["keys"], kinds["values"], kinds["keys"], kinds["col"]],
        [jax.ShapeDtypeStruct((b, s, h * dk), q.dtype), jax.ShapeDtypeStruct((b, s, h * dk), k.dtype),
         jax.ShapeDtypeStruct((b, s, h * dv), v.dtype), jax.ShapeDtypeStruct((b, s, h * dk), _F32),
         jax.ShapeDtypeStruct((b, nh, s, hb), _F32)],
        [pltpu.VMEM((hb * dv, dk), _F32)], interpret)(*arrays, states, d_out.reshape(b, s, h * dv))
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape), dgam.reshape(gam.shape),
            jnp.moveaxis(dbeta, 1, 2).reshape(beta.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def rule(q, k, v, gam, beta, chunk, scale, interpret):
    """The chunks' pass on the running sums ``gam`` of ``g`` (inside each
    chunk); differentiable in q, k, v, gam and beta."""
    return _forward(q, k, v, gam, beta, chunk, scale, False, interpret)[0]


def _rule_fwd(q, k, v, gam, beta, chunk, scale, interpret):
    out, states = _forward(q, k, v, gam, beta, chunk, scale, True, interpret)
    return out, (q, k, v, gam, beta, states)


def _rule_bwd(chunk, scale, interpret, saved, d_out):
    return _backward(*saved, d_out, chunk, scale, interpret)


rule.defvjp(_rule_fwd, _rule_bwd)


def kda(q, k, v, g, beta, chunk: int, scale: float, *, interpret=None):
    """q, k: (b, s, h, d_k); v: (b, s, h, d_v); g: (b, s, h, d_k) float32,
    <= 0; beta: (b, s, h); ``tiles(q, v, chunk)`` -> the chunked gated delta
    rule of ``ops/kda.kda``, (b, s, h, d_v) in q's dtype; differentiable in
    all five.  ``interpret`` None derives from the backend
    (``backend.resolve_interpret``)."""
    if not tiles(q, v, chunk):
        raise ValueError(f"the kernel does not tile {q.shape}, {v.shape} in chunks of {chunk}")
    b, s, h, dk = q.shape
    # the running sum of g inside each chunk, as the lax form takes it
    gam = jnp.cumsum(g.astype(_F32).reshape(b, s // chunk, chunk, h, dk), axis=2).reshape(q.shape)
    return rule(q, k, v, gam, beta.astype(_F32), chunk, float(scale), resolve_interpret(interpret))
