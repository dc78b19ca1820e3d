"""Pallas TPU kernels: Mamba-2's chunked selective scan (``ops/ssd.py``) with
a chunk's decay tile and the carried state kept on the chip, forward and
backward.

The ``lax`` form builds, a chunk and a head, a float32 (chunk x chunk) tile
``exp(l_i - l_j)``, multiplies it into ``C B^T``, casts it and runs four
products around it, every piece a fusion of its own with its operands in
HBM.  Here the tile lives in VMEM from the difference to the product that
reads it, and the (p x n) state of every head is VMEM scratch that one chunk
hands the next, with the mathematics and the precisions of that form:
operands in their own dtype (bfloat16) into float32 products, ``dt``, the
running sums ``l``, the decays and the state in float32 (the state a float32
operand of ``C S_in`` at the default precision, which Mosaic, like XLA on a
TPU, runs as one bfloat16 pass), only differences of ``l``.  The running sums
(and with them ``a``) stay outside, in XLA: the kernels take ``l`` as an
operand and the backward returns its cotangent.

``_forward``  grid (batch, chunk, head block), the chunks in order and a
    chunk's head blocks innermost: ``C B^T`` and the mask of a chunk are
    built once for its heads, the states of ALL heads are scratch (2 MB at 64
    heads x 64 x 128), zeroed at the row's first chunk.  A head's tile is
    ``W = (C B^T o exp(l_i - l_j + mask))`` cast to x's dtype, built and
    multiplied a (128 x 128) piece at a time, and only the pieces on or under
    the diagonal (three of four at chunk 256).  Heads are taken in groups that
    fill the 128 lanes of the (tokens, heads x p) view of ``x`` (two 64-wide
    heads), so that everything but ``W``'s own product runs on whole
    registers: ``Y = sum_k W_k (dt o X o lanes_k) + r o (C S_in^T)``,
    ``S_out = tau S_in + (k o dt o X)^T B`` on the group's stacked (128 x n)
    state.  A head's ``dt`` and ``l`` come as columns of a (chunk, heads a
    step) tile and ``l`` also as a row: the tile wants both and Mosaic has no
    cheap transpose of one into the other.  The ``D`` skip and the cast
    happen inside.  Under differentiation it also writes the state ENTERING
    each chunk, the one thing kept for the backward beside the operands.

``_backward``  ONE kernel, the chunks in reverse, carrying ``dS`` as the
    forward carries ``S``.  A chunk rebuilds ``C B^T``, the mask, each head's
    pieces and ``W`` from the operands and the saved entry state (this is the
    chunk's rematerialised forward), and yields ``dx``, the cotangents of
    ``dt`` (through ``dt o X``) and of ``l`` by token and head (the part along
    a tile's rows as columns, the part along its columns as rows: XLA adds
    them), ``dB`` and ``dC`` summed over a group's heads in VMEM, and ``D``'s
    by token.  The reverse running sum that turns ``dl`` into ``d dt`` and
    ``da`` is XLA's (the transpose of its own ``cumsum``).

Documents (``doc``: ``ops/segments.document_index``, never falling along a
row): a pair of tokens is masked unless both are of one document; the state
that enters reaches the tokens of the document it belongs to (``r``), the
state that leaves is fed by the chunk's last document (``k``) and keeps what
entered only if no document starts inside (``tau``).  A chunk learns the
document its entry state belongs to, and its own last one, from two SMEM
scalars.

Jax cannot partition a Mosaic call over a mesh: a caller that holds a mesh
stays on the ``lax`` scan (``ops/ssd.scan_path`` decides).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

NEG = -1e30
_LANES = 128
#: heads a grid step takes at most (in whole lane groups, inside one group of
#: B, C), unrolled: on a v5e at 32,768 tokens x 64 heads x 64, state 128, chunk
#: 256 the forward, its rematerialised twin and the backward together take
#: 19.6 / 17.4 / 16.7-17.0 / 16.5 ms at 4 / 8 / 16 / 32 (PERF.md section 6, PR 36).
#: Read when a shape is first traced (``_forward`` and ``_backward`` are jitted)
HEADS = 16
_VMEM_LIMIT = 100 << 20
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_F32 = jnp.float32


def heads_per_step(h: int, g: int, p: int) -> int:
    """Heads a grid step takes: the most, up to ``HEADS``, that divide a
    group's heads and fill whole 128-lane registers of the (tokens, heads x p)
    view; 0 where there are none."""
    if p > _LANES or _LANES % p:
        return 0
    per_lanes, group = _LANES // p, h // g
    return next((hb for hb in range(min(HEADS, group), 0, -1)
                 if group % hb == 0 and hb % per_lanes == 0), 0)


def tiles(x, b_in, chunk: int) -> bool:
    """Whether the kernels take x (b, s, h, p) with b_in (b, s, g, n) in
    chunks of ``chunk``: whole chunks of whole 128-token registers, a state
    width in whole registers, head widths that divide a register."""
    s, h, p = x.shape[1:]
    g, n = b_in.shape[2:]
    return (chunk > 0 and s % chunk == 0 and chunk % _LANES == 0 and n % _LANES == 0
            and h % g == 0 and heads_per_step(h, g, p) > 0)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _by_lanes(cols, p: int, shape):
    """cols: one (rows, 1) or (1, 1) value a head of a lane group -> ``shape``
    = (rows, 128) with head k's value on its p lanes."""
    out = jnp.broadcast_to(cols[-1], shape)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    for k in range(len(cols) - 2, -1, -1):
        out = jnp.where(lane < (k + 1) * p, cols[k], out)
    return out


def _by_rows(vals, p: int):
    """vals: one (1, n) row a head of a lane group -> (128, n) with head k's
    row on its p rows (the group's stacked state)."""
    shape = (len(vals) * p, vals[0].shape[1])
    out = jnp.broadcast_to(vals[-1], shape)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    for k in range(len(vals) - 2, -1, -1):
        out = jnp.where(row < (k + 1) * p, vals[k], out)
    return out


def _blocks(c: int):
    """The chunk's tokens in runs of 128: a register's lanes."""
    return [slice(r, r + _LANES) for r in range(0, c, _LANES)]


def _lower_tiles(c: int):
    """The (128 x 128) pieces of a chunk's (c x c) tile that hold a pair
    ``i >= j``: (row piece, column piece, rows, columns).  The pieces above
    the diagonal are all mask and are never built."""
    blocks = _blocks(c)
    return [(r, s, blocks[r], blocks[s]) for r in range(len(blocks)) for s in range(r + 1)]


def _chunk_tiles(hi, first_of_group, b_ref, c_ref, dcol_ref, drow_ref, g_sc, bias_sc):
    """What a chunk's heads share: ``C B^T`` (once a group of B, C) and the
    mask as an addend of the decay's exponent (once a chunk)."""
    @pl.when(first_of_group)
    def _():
        g_sc[...] = jax.lax.dot_general(c_ref[...], b_ref[...], _NT, preferred_element_type=_F32)

    @pl.when(hi == 0)
    def _():
        shape = bias_sc.shape
        later = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                 >= jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        bias_sc[...] = jnp.where(later & (dcol_ref[...] == drow_ref[...]), 0.0, NEG)


def _group_terms(refs, gi, heads, p, doc_in, doc_last):
    """What a lane group's heads need of ``dt``, ``l`` and the documents, on
    the group's (chunk, 128) registers (each head's value on its p lanes): x
    (in its dtype and float32), ``dt``, ``dt o x`` cast, ``r`` (the entry
    state's reach), ``k`` (what feeds the state that leaves); and, a head,
    ``l`` as a column and ``l_last`` as a (1, 1)."""
    x_ref, dtc_ref, lc_ref, dcol_ref = refs
    lanes = slice(gi * _LANES, (gi + 1) * _LANES)
    c = x_ref.shape[0]
    dcol = dcol_ref[...]
    lcs = [lc_ref[:, k:k + 1] for k in heads]                           # (c, 1) each
    lasts = [lc[c - 1:c, :] for lc in lcs]                              # (1, 1) each
    xg = x_ref[:, lanes]
    xf = xg.astype(_F32)
    dts = _by_lanes([dtc_ref[:, k:k + 1] for k in heads], p, xf.shape)
    xdt = (xf * dts).astype(xg.dtype)
    l = _by_lanes(lcs, p, xf.shape)
    reach = jnp.where(dcol == doc_in, jnp.exp(l), 0.0)
    kept = jnp.where(dcol == doc_last, jnp.exp(_by_lanes(lasts, p, (1, _LANES)) - l), 0.0)
    return lanes, xg, xf, dts, xdt, lcs, lasts, reach, kept


def _through(lasts, through, p: int, n: int):
    """``tau`` on the group's stacked (128, n) state.  (1, 1) -> (1, n) ->
    (128, n): Mosaic broadcasts along lanes or along sublanes, not both at
    once, and the exp between keeps the two apart."""
    return _by_rows([jnp.where(through, jnp.exp(jnp.broadcast_to(last, (1, n))), 0.0)
                     for last in lasts], p)


def _of_head(t, j: int, p: int):
    """``t`` (rows, 128) with the lanes of the group's other heads zeroed."""
    if p == _LANES:
        return t
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    return jnp.where((lane >= j * p) & (lane < (j + 1) * p), t, jnp.zeros_like(t))


def _fwd_kernel(*refs, p, hb, per_group, save):
    (din_ref, dlast_ref, x_ref, dtc_ref, lc_ref, lr_ref, b_ref, c_ref, dcol_ref, drow_ref,
     skip_ref, y_ref) = refs[:12]
    s_ref = refs[12] if save else None
    state_sc, g_sc, bias_sc = refs[-3:]
    bi, ci, hi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    c, n = b_ref.shape
    q = _LANES // p                                  # heads a lane group
    doc_in, doc_last = din_ref[bi, ci], dlast_ref[bi, ci]

    @pl.when(ci == 0)
    def _():
        state_sc[hi] = jnp.zeros(state_sc.shape[1:], _F32)

    _chunk_tiles(hi, (hi * hb) % per_group == 0, b_ref, c_ref, dcol_ref, drow_ref, g_sc, bias_sc)
    c_f32 = c_ref[...].astype(_F32)
    for gi in range(hb * p // _LANES):
        heads = range(gi * q, (gi + 1) * q)
        lanes, xg, xf, _, xdt, lcs, lasts, reach, kept = _group_terms(
            (x_ref, dtc_ref, lc_ref, dcol_ref), gi, heads, p, doc_in, doc_last)
        state = state_sc[hi, lanes, :]                                  # (128, n): q heads stacked
        if save:
            s_ref[lanes, :] = state
        y = reach * jax.lax.dot_general(c_f32, state, _NT, preferred_element_type=_F32)
        y = y + skip_ref[:, lanes] * xf
        ys = [y[rows] for rows in _blocks(c)]
        for j, k in enumerate(heads):
            mine = _of_head(xdt, j, p)
            for r, s, rows, cols in _lower_tiles(c):
                decay = jnp.exp(lcs[j][rows] - lr_ref[k:k + 1, cols] + bias_sc[rows, cols])
                w = (g_sc[rows, cols] * decay).astype(xg.dtype)
                ys[r] = ys[r] + jnp.dot(w, mine[cols], preferred_element_type=_F32)
        for rows, y_r in zip(_blocks(c), ys):
            y_ref[rows, lanes] = y_r.astype(y_ref.dtype)
        fed = (xdt.astype(_F32) * kept).astype(xg.dtype)
        state_sc[hi, lanes, :] = _through(lasts, doc_last == doc_in, p, n) * state + jax.lax.dot_general(
            fed, b_ref[...], _TN, preferred_element_type=_F32)


def _bwd_kernel(*refs, p, hb, per_group):
    (din_ref, dlast_ref, x_ref, dtc_ref, lc_ref, lr_ref, b_ref, c_ref, dcol_ref, drow_ref,
     skip_ref, s_ref, dy_ref,
     dx_ref, ddt_ref, dlc_ref, dlr_ref, dskip_ref, db_ref, dc_ref,
     dstate_sc, g_sc, bias_sc, dg_sc, db_sc, dc_sc) = refs
    bi, ci, hi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ci = pl.num_programs(1) - 1 - ci                 # the chunk this step is given
    c, n = b_ref.shape
    q = _LANES // p
    doc_in, doc_last = din_ref[bi, ci], dlast_ref[bi, ci]
    first_of_group = (hi * hb) % per_group == 0
    last_of_group = ((hi + 1) * hb) % per_group == 0

    @pl.when(pl.program_id(1) == 0)                  # the row's last chunk: nothing comes after
    def _():
        dstate_sc[hi] = jnp.zeros(dstate_sc.shape[1:], _F32)

    _chunk_tiles(hi, first_of_group, b_ref, c_ref, dcol_ref, drow_ref, g_sc, bias_sc)

    @pl.when(first_of_group)
    def _():
        dg_sc[...] = jnp.zeros_like(dg_sc)
        db_sc[...] = jnp.zeros_like(db_sc)
        dc_sc[...] = jnp.zeros_like(dc_sc)

    through = doc_last == doc_in
    b_f32, c_f32 = b_ref[...].astype(_F32), c_ref[...].astype(_F32)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    lane_of = jax.lax.broadcasted_iota(jnp.int32, (c, hb), 1)
    by_token = lambda t, j: jnp.sum(_of_head(t, j, p), axis=1, keepdims=True)      # (c, 1)
    ddt_cols = dl_cols = dskip_cols = jnp.zeros((c, hb), _F32)
    for gi in range(hb * p // _LANES):
        heads = range(gi * q, (gi + 1) * q)
        lanes, xg, xf, dts, xdt, lcs, lasts, reach, kept = _group_terms(
            (x_ref, dtc_ref, lc_ref, dcol_ref), gi, heads, p, doc_in, doc_last)
        xdt_f32 = xdt.astype(_F32)
        state, dstate = s_ref[lanes, :], dstate_sc[hi, lanes, :]        # (128, n) each
        dyg = dy_ref[:, lanes]
        dy = dyg.astype(_F32)
        z = jax.lax.dot_general(c_f32, state, _NT, preferred_element_type=_F32)
        dfed = jax.lax.dot_general(b_f32, dstate, _NT, preferred_element_type=_F32)
        dxdt = kept * dfed
        dxdts = [dxdt[rows] for rows in _blocks(c)]
        by_pair = []
        for j, k in enumerate(heads):
            # the chunk's forward again: this head's tile, a (128 x 128) piece at a time
            dy_k = _of_head(dyg, j, p)
            along_rows = [jnp.zeros((_LANES, 1), _F32) for _ in _blocks(c)]
            along_cols = [jnp.zeros((1, _LANES), _F32) for _ in _blocks(c)]
            for r, s, rows, cols in _lower_tiles(c):
                decay = jnp.exp(lcs[j][rows] - lr_ref[k:k + 1, cols] + bias_sc[rows, cols])
                w_f32 = g_sc[rows, cols] * decay
                dw = jax.lax.dot_general(dy_k[rows], xdt[cols], _NT, preferred_element_type=_F32)
                dxdts[s] = dxdts[s] + jax.lax.dot_general(w_f32.astype(xg.dtype), dy_k[rows], _TN,
                                                          preferred_element_type=_F32)
                dg_sc[rows, cols] += dw * decay
                # d l through the tile: + along a row (the later token), - along a column
                pairs = dw * w_f32
                along_rows[r] = along_rows[r] + jnp.sum(pairs, axis=1, keepdims=True)
                along_cols[s] = along_cols[s] + jnp.sum(pairs, axis=0, keepdims=True)
            by_pair.append(jnp.concatenate(along_rows, axis=0))
            dlr_ref[k:k + 1, :] = -jnp.concatenate(along_cols, axis=1)
        dxdt = jnp.concatenate(dxdts, axis=0)
        # ... through r (reach of the entry state) and k (what feeds the next)
        dz = reach * dy
        via_r, via_k = dz * z, kept * dfed * xdt_f32
        dstate_s = jnp.sum(dstate * state, axis=1, keepdims=True)       # (128, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, dstate_s.shape, 0)
        for j, k in enumerate(heads):
            fed_k = by_token(via_k, j)
            dtau = jnp.sum(jnp.where((row >= j * p) & (row < (j + 1) * p), dstate_s, 0.0),
                           axis=0, keepdims=True)                       # (1, 1)
            tau = jnp.where(through, jnp.exp(lasts[j]), 0.0)
            at_end = jnp.sum(fed_k, axis=0, keepdims=True) + dtau * tau
            dl_k = by_pair[j] + by_token(via_r, j) - fed_k + jnp.where(at_last, at_end, 0.0)
            dl_cols = jnp.where(lane_of == k, dl_k, dl_cols)
            ddt_cols = jnp.where(lane_of == k, by_token(dxdt * xf, j), ddt_cols)
            dskip_cols = jnp.where(lane_of == k, by_token(dy * xf, j), dskip_cols)
        dx_ref[:, lanes] = (dxdt * dts + skip_ref[:, lanes] * dy).astype(dx_ref.dtype)
        fed = (xdt_f32 * kept).astype(xg.dtype)
        dc_sc[...] += jnp.dot(dz, state, preferred_element_type=_F32)
        db_sc[...] += jnp.dot(fed.astype(_F32), dstate, preferred_element_type=_F32)
        dstate_sc[hi, lanes, :] = _through(lasts, through, p, n) * dstate + jax.lax.dot_general(
            dz, c_f32, _TN, preferred_element_type=_F32)
    ddt_ref[...], dlc_ref[...], dskip_ref[...] = ddt_cols, dl_cols, dskip_cols

    @pl.when(last_of_group)
    def _():
        dg = dg_sc[...].astype(b_ref.dtype)
        dc_ref[...] = (dc_sc[...] + jnp.dot(dg, b_ref[...], preferred_element_type=_F32)
                       ).astype(dc_ref.dtype)
        db_ref[...] = (db_sc[...] + jax.lax.dot_general(dg, c_ref[...], _TN, preferred_element_type=_F32)
                       ).astype(db_ref.dtype)


def _operands(x, dt, l, b_in, c_in, d_skip, doc, chunk: int, hb: int, reverse: bool = False):
    """The kernels' views of the scan's operands -> (SMEM scalars, arrays,
    their block specs by kind) for grid (batch, chunk, head block); with
    ``reverse`` step ``ci`` is given the chunk ``nc - 1 - ci``."""
    b, s, h, p = x.shape
    g, n = b_in.shape[2:]
    nc, nh = s // chunk, h // hb
    blocks_a_group = (h // g) // hb
    doc_last = doc.reshape(b, nc, chunk)[:, :, -1]
    doc_in = jnp.concatenate([doc[:, :1], doc_last[:, :-1]], axis=1)
    # a head's dt and l as a column of its block's (chunk, hb) tile; l also as a row
    cols = lambda t: jnp.moveaxis(t.reshape(b, s, nh, hb), 2, 1)
    rows = lambda t: jnp.moveaxis(t, 1, 2).reshape(b, nh, hb, s)
    skip = jnp.repeat(d_skip.astype(_F32), p)[None]
    arrays = (x.reshape(b, s, h * p), cols(dt), cols(l), rows(l), b_in.reshape(b, s, g * n),
              c_in.reshape(b, s, g * n), doc[:, :, None], doc[:, None, :], skip)
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    spec = lambda block, index: pl.BlockSpec(block, lambda bi, ci, hi, *_: index(bi, at(ci), hi))
    kinds = dict(
        wide=spec((None, chunk, hb * p), lambda bi, ci, hi: (bi, ci, hi)),
        col=spec((None, None, chunk, hb), lambda bi, ci, hi: (bi, hi, ci, 0)),
        row=spec((None, None, hb, chunk), lambda bi, ci, hi: (bi, hi, 0, ci)),
        bc=spec((None, chunk, n), lambda bi, ci, hi: (bi, ci, hi // blocks_a_group)),
        state=spec((None, None, hb * p, n), lambda bi, ci, hi: (bi, ci, hi, 0)))
    specs = [kinds["wide"], kinds["col"], kinds["col"], kinds["row"], kinds["bc"], kinds["bc"],
             spec((None, chunk, 1), lambda bi, ci, hi: (bi, ci, 0)),
             spec((None, 1, chunk), lambda bi, ci, hi: (bi, 0, ci)),
             spec((1, hb * p), lambda bi, ci, hi: (0, hi))]
    return (doc_in, doc_last), arrays, specs, kinds


def _call(kernel, name, grid, scalars, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, out_shape=out_shape, compiler_params=_PARAMS, name=name, interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=grid, in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch))


# jitted, so that a model's layers of one shape share ONE trace of the unrolled
# kernel body and one lowering of it: traced anew a layer, nine Mamba layers
# cost a warm start 11 s of Python (PERF.md section 6, PR 36)
@functools.partial(jax.jit, static_argnames=("chunk", "save", "interpret"))
def _forward(x, dt, l, b_in, c_in, d_skip, doc, chunk: int, save: bool, interpret: bool):
    """-> y (b, s, h, p) in x's dtype and, with ``save``, the state entering
    each chunk (b, s // chunk, h * p, n) float32 (a head's p rows together)."""
    b, s, h, p = x.shape
    g, n = b_in.shape[2:]
    hb = heads_per_step(h, g, p)
    nc, nh = s // chunk, h // hb
    scalars, arrays, specs, kinds = _operands(x, dt, l, b_in, c_in, d_skip, doc, chunk, hb)
    # y, and with ``save`` (static: a Python bool) the entry states
    out_specs = [kinds["wide"], kinds["state"]][:1 + save]
    out_shape = [jax.ShapeDtypeStruct((b, s, h * p), x.dtype),
                 jax.ShapeDtypeStruct((b, nc, h * p, n), _F32)][:1 + save]
    out = _call(
        functools.partial(_fwd_kernel, p=p, hb=hb, per_group=h // g, save=save),
        "fedml_ssd_fwd", (b, nc, nh), scalars, specs, out_specs, out_shape,
        [pltpu.VMEM((nh, hb * p, n), _F32), pltpu.VMEM((chunk, chunk), _F32),
         pltpu.VMEM((chunk, chunk), _F32)], interpret)(*scalars, *arrays)
    return (out[0].reshape(x.shape),) + tuple(out[1:])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _backward(x, dt, l, b_in, c_in, d_skip, doc, states, d_out, chunk: int, interpret: bool):
    """-> the cotangents of x, dt (through ``dt o x`` alone), l, b_in, c_in
    and d_skip."""
    b, s, h, p = x.shape
    g, n = b_in.shape[2:]
    hb = heads_per_step(h, g, p)
    nc, nh = s // chunk, h // hb
    scalars, arrays, specs, kinds = _operands(x, dt, l, b_in, c_in, d_skip, doc, chunk, hb, reverse=True)
    by_head = jax.ShapeDtypeStruct((b, nh, s, hb), _F32)
    group = jax.ShapeDtypeStruct((b, s, g * n), b_in.dtype)
    tile = pltpu.VMEM((chunk, chunk), _F32)
    dx, ddt, dl_col, dl_row, dskip, db, dc = _call(
        functools.partial(_bwd_kernel, p=p, hb=hb, per_group=h // g),
        "fedml_ssd_bwd", (b, nc, nh), scalars, specs + [kinds["state"], kinds["wide"]],
        [kinds["wide"], kinds["col"], kinds["col"], kinds["row"], kinds["col"], kinds["bc"], kinds["bc"]],
        [jax.ShapeDtypeStruct((b, s, h * p), x.dtype), by_head, by_head,
         jax.ShapeDtypeStruct((b, nh, hb, s), _F32), by_head, group, group],
        [pltpu.VMEM((nh, hb * p, n), _F32), tile, tile, tile,
         pltpu.VMEM((chunk, n), _F32), pltpu.VMEM((chunk, n), _F32)],
        interpret)(*scalars, *arrays, states, d_out.reshape(b, s, h * p))
    cols = lambda t: jnp.moveaxis(t, 1, 2).reshape(b, s, h)
    dl = cols(dl_col) + jnp.moveaxis(dl_row.reshape(b, h, s), 1, 2)
    return (dx.reshape(x.shape), cols(ddt), dl, db.reshape(b_in.shape), dc.reshape(c_in.shape),
            cols(dskip).sum((0, 1)).astype(d_skip.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def scan(x, dt, l, b_in, c_in, d_skip, doc, chunk, interpret):
    """The chunks' pass on the running sums ``l`` of ``dt a`` (inside each
    chunk); differentiable in x, dt, l, b_in, c_in and d_skip."""
    return _forward(x, dt, l, b_in, c_in, d_skip, doc, chunk, False, interpret)[0]


def _scan_fwd(x, dt, l, b_in, c_in, d_skip, doc, chunk, interpret):
    y, states = _forward(x, dt, l, b_in, c_in, d_skip, doc, chunk, True, interpret)
    return y, (x, dt, l, b_in, c_in, d_skip, doc, states)


def _scan_bwd(chunk, interpret, saved, d_out):
    return (*_backward(*saved, d_out, chunk, interpret), None)


scan.defvjp(_scan_fwd, _scan_bwd)


def ssd(x, dt, a, b_in, c_in, d_skip, doc, chunk: int, *, interpret=None):
    """x: (b, s, h, p); dt: (b, s, h) float32; a: (h,) float32; b_in, c_in:
    (b, s, g, n); d_skip: (h,); doc: (b, s) int32, a document index per token
    that never falls along a row (``ops/segments.document_index``; zeros for
    one document a row); ``tiles(x, b_in, chunk)`` -> the selective scan of
    ``ops/ssd.ssd``, (b, s, h, p) in x's dtype; differentiable in all but
    ``doc``.  ``interpret`` None derives from the backend
    (``backend.resolve_interpret``)."""
    if not tiles(x, b_in, chunk):
        raise ValueError(f"the kernel does not tile {x.shape}, {b_in.shape} in chunks of {chunk}")
    b, s, h, _ = x.shape
    dt = dt.astype(_F32)
    # the running sum of dt a inside each chunk, as the lax form takes it
    l = jnp.cumsum((dt * a.astype(_F32)).reshape(b, s // chunk, chunk, h), axis=2).reshape(b, s, h)
    return scan(x, dt, l, b_in, c_in, d_skip, doc, chunk, resolve_interpret(interpret))
