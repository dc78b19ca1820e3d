"""Pallas TPU kernels: plain causal softmax attention with the scores kept on
the chip (flash attention), forward and backward.

The blockwise ``lax`` pass of ``ops/sparse_attention.py`` writes every pair of
chunks' float32 scores to HBM between the product that makes them, the
fusions that mask, exponentiate and sum them, and the product that reads the
probabilities.  Here a (``BLOCK`` x ``BLOCK``) tile lives in VMEM from the first
product to the last, with the mathematics and the precisions of that pass:
operands in their own dtype (bfloat16) into float32 products, the scale on
the float32 scores, a float32 online softmax (running max, normaliser,
rescale), probabilities cast to the values' dtype before the second product,
float32 accumulators, the output in q's dtype; the forward keeps the output
and each row's log-sum-exp, the backward recomputes each tile's probabilities
from them, once.  Tiles wholly in a query block's future are skipped (no
product, no copy), and only the tiles on the diagonal are masked.

``_forward``  grid (batch, head, query block, key block), the key blocks
    innermost: the accumulators are scratch that a query block carries over
    its key blocks.

``_backward``  ONE kernel for dq, dk and dv, grid (batch, head, key block,
    query block), on transposed tiles (keys down, queries across), so that dv
    and dk are plain products and only dq contracts over a tile's rows; dk and
    dv are scratch a key block carries over its query blocks, and the dq of a
    whole head stays in VMEM until the head's last tile: no partial dq goes
    through HBM, and no tile's scores are computed twice.  That is what bounds
    the sequence (``tiles``).

With ``segments`` (packed documents: a document index per token that never
falls along a row) a query attends the keys at or before it in ITS document:
every tile that is run compares the two indices (a column of them against a
row) beside the diagonal's causal mask, and a tile none of whose queries
shares a document with any of its keys (the key block's last index is under
the query block's first; both are scalars the grid is given ahead, in SMEM) is
not run at all.  Without ``segments`` the kernels are PR 34's to the letter.

The kernels take (batch, heads, seq, width); ``causal_attention`` takes and
gives the model's (batch, seq, heads, width) and swaps the two axes around
them.  Jax cannot partition a Mosaic call over a mesh: a caller that holds a
mesh stays on the ``lax`` pass (``ops/sparse_attention.py`` decides).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

NEG_INF = -1e30
#: queries and keys a tile holds at most, forward and backward: on a v5e at
#: 8,192 tokens x 128 heads x 192 | 128 the forward takes 81.7 / 40.9 / 25.8 ms
#: and the backward 80.0 / 56.8 / 53.3 ms at 256 / 512 / 1,024, and no
#: oblong tile up to 2,048 beats the square one (PERF.md section 6, PR 34)
BLOCK = 1024
_LANES = 128
#: VMEM the backward may give a head's dq (float32 scratch and the
#: double-buffered output block) of a v5e's 128 MiB
_DQ_VMEM = 40 << 20
_VMEM_LIMIT = 100 << 20
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _lanes(width: int) -> int:
    return -(-width // _LANES) * _LANES


def block_of(s: int) -> int:
    """The tile's edge for ``s`` tokens: the largest divisor of ``s`` that is
    a multiple of 128 and at most ``BLOCK``; 0 where there is none."""
    return next((c for c in range(min(BLOCK, s) // _LANES * _LANES, 0, -_LANES) if s % c == 0), 0)


def tiles(q, k, v) -> bool:
    """Whether the kernels take these (b, s, h, d) / (b, s, kv, d) /
    (b, s, kv, dv) operands: a sequence that tiles, head widths in whole
    sublane groups, query heads in whole groups of KV heads, and a head's dq
    that fits the backward's VMEM."""
    s, h, d = q.shape[1:]
    return (block_of(s) > 0 and d % 8 == 0 and v.shape[-1] % 8 == 0 and h % k.shape[2] == 0
            and s * _lanes(d) * (4 + 2 * q.dtype.itemsize) <= _DQ_VMEM)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _fwd_kernel(*refs, scale, block, segmented):
    if segmented:   # first/last document of each block (SMEM), then a column and a row of indices
        lo_ref, hi_ref, q_ref, k_ref, v_ref, col_ref, row_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc = refs
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def attend(masked: bool):
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:      # i == j: the tile's own rows against its own columns
            s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                          >= jax.lax.broadcasted_iota(jnp.int32, s.shape, 1), s, NEG_INF)
        if segmented:   # a query (row) and a key (column) of one document
            s = jnp.where(col_ref[...] == row_ref[...], s, NEG_INF)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        m_sc[...] = m_next
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)

    before = j < i
    if segmented:   # ... and the key block's last document reaches the query block's first
        b_ = pl.program_id(0)
        before &= hi_ref[b_, j] >= lo_ref[b_, i]
    pl.when(before)(functools.partial(attend, False))

    @pl.when(j == i)    # the query block's last tile
    def _():
        attend(True)
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        # a row's log-sum-exp goes out ACROSS lanes, as the backward's
        # transposed tiles read it: (block, 1) -> (1, block)
        lse = jnp.broadcast_to(m_sc[...] + jnp.log(l), (block, _LANES))
        lse_ref[...] = lse.T[:1]


def _segment_operands(doc, block: int):
    """doc (b, s) int32, never falling along a row -> what the segmented
    kernels take beside q, k, v: each block's first and last index (b, n) for
    SMEM, and the indices as a column (b, s, 1) and as a row (b, 1, s)."""
    b, s = doc.shape
    by_block = doc.reshape(b, s // block, block)
    return (by_block[:, :, 0], by_block[:, :, -1]), (doc[:, :, None], doc[:, None, :])


def _call(kernel, name: str, grid, in_specs, out_specs, out_shape, scratch_shapes, interpret,
          scalars=()):
    """``pallas_call`` on ``grid``; with ``scalars`` they are prefetched to
    SMEM and every index map is given them after the grid's indices."""
    if scalars:
        spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=grid, in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes))
    else:
        spec = dict(grid=grid, in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch_shapes)
    return pl.pallas_call(kernel, out_shape=out_shape, compiler_params=_PARAMS, name=name,
                          interpret=interpret, **spec)


def _forward(q, k, v, doc, scale: float, interpret: bool):
    """q (b, h, s, d), k (b, kv, s, d), v (b, kv, s, dv), doc (b, s) or None
    -> out (b, h, s, dv) in q's dtype, log-sum-exp (b, h, 1, s) float32."""
    b, h, s, d = q.shape
    group, dv, block = h // k.shape[1], v.shape[-1], block_of(s)
    n = s // block
    # a tile in the query block's future is not run: it names the diagonal
    # tile's keys again, which copies nothing
    kv_at = lambda b_, h_, i, j, *_: (b_, h_ // group, jnp.minimum(i, j), 0)
    q_at = lambda b_, h_, i, j, *_: (b_, h_, i, 0)
    in_specs = [pl.BlockSpec((None, None, block, d), q_at),
                pl.BlockSpec((None, None, block, d), kv_at),
                pl.BlockSpec((None, None, block, dv), kv_at)]
    scalars, operands = (), (q, k, v)
    if doc is not None:
        scalars, (col, row) = _segment_operands(doc, block)
        in_specs += [pl.BlockSpec((None, block, 1), lambda b_, h_, i, j, *_: (b_, i, 0)),
                     pl.BlockSpec((None, 1, block), lambda b_, h_, i, j, *_: (b_, 0, jnp.minimum(i, j)))]
        operands += (col, row)
    return _call(
        functools.partial(_fwd_kernel, scale=scale, block=block, segmented=doc is not None),
        "fedml_causal_attention_fwd", (b, h, n, n), in_specs,
        [pl.BlockSpec((None, None, block, dv), q_at),
         pl.BlockSpec((None, None, 1, block), lambda b_, h_, i, j, *_: (b_, h_, 0, i))],
        [jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
         jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        [pltpu.VMEM((block, 1), jnp.float32), pltpu.VMEM((block, 1), jnp.float32),
         pltpu.VMEM((block, dv), jnp.float32)],
        interpret, scalars)(*scalars, *operands)


def _bwd_kernel(*refs, scale, block, segmented):
    if segmented:
        (lo_ref, hi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, col_ref, row_ref,
         dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
         dq_sc, dk_sc, dv_sc) = refs
    j, i = pl.program_id(2), pl.program_id(3)
    last = pl.num_programs(3) - 1

    @pl.when((j == 0) & (i == 0))
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(i == 0)
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def attend(masked: bool):
        q, k, do = q_ref[...], k_ref[...], do_ref[...]
        # transposed tiles: a row is a key, a column a query
        st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            st = jnp.where(jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
                           >= jax.lax.broadcasted_iota(jnp.int32, st.shape, 0), st, NEG_INF)
        if segmented:   # a key (row) and a query (column) of one document
            st = jnp.where(col_ref[...] == row_ref[...], st, NEG_INF)
        pt = jnp.exp(st - lse_ref[...])
        dv_sc[...] += jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[...], do, _NT, preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[...]) * scale).astype(q.dtype)
        dk_sc[...] += jnp.dot(dst, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        dq_sc[rows, :] += jax.lax.dot_general(dst, k, _TN, preferred_element_type=jnp.float32)

    pl.when(i == j)(functools.partial(attend, True))
    after = i > j
    if segmented:
        b_ = pl.program_id(0)
        after &= hi_ref[b_, j] >= lo_ref[b_, i]
    pl.when(after)(functools.partial(attend, False))

    @pl.when(i == last)
    def _():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when((j == last) & (i == last))
    def _():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _backward(q, k, v, doc, out, lse, d_out, scale: float, interpret: bool):
    b, h, s, d = q.shape
    kv, dv, block = k.shape[1], v.shape[-1], block_of(s)
    group, n = h // kv, s // block
    # delta_t = sum_j p_tj dp_tj = do_t . o_t
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32), -1)[:, :, None]
    # several query heads on one KV head: each writes its part in float32,
    # summed below
    part = k.dtype if group == 1 else jnp.float32
    # a tile in the key block's past is not run: it names the diagonal tile's
    # queries again
    q_at = lambda b_, h_, j, i, *_: (b_, h_, jnp.maximum(i, j), 0)
    row_at = lambda b_, h_, j, i, *_: (b_, h_, 0, jnp.maximum(i, j))
    kv_at = lambda b_, h_, j, i, *_: (b_, h_ // group, j, 0)
    dkv_at = lambda b_, h_, j, i, *_: (b_, h_, j, 0)
    in_specs = [pl.BlockSpec((None, None, block, d), q_at),
                pl.BlockSpec((None, None, block, d), kv_at),
                pl.BlockSpec((None, None, block, dv), kv_at),
                pl.BlockSpec((None, None, block, dv), q_at),
                pl.BlockSpec((None, None, 1, block), row_at),
                pl.BlockSpec((None, None, 1, block), row_at)]
    scalars, operands = (), (q, k, v, d_out, lse, delta)
    if doc is not None:     # transposed tiles: the keys' indices down, the queries' across
        scalars, (col, row) = _segment_operands(doc, block)
        in_specs += [pl.BlockSpec((None, block, 1), lambda b_, h_, j, i, *_: (b_, j, 0)),
                     pl.BlockSpec((None, 1, block), lambda b_, h_, j, i, *_: (b_, 0, jnp.maximum(i, j)))]
        operands += (col, row)
    dq, dk, dv_ = _call(
        functools.partial(_bwd_kernel, scale=scale, block=block, segmented=doc is not None),
        "fedml_causal_attention_bwd", (b, h, n, n), in_specs,
        [pl.BlockSpec((None, None, s, d), lambda b_, h_, j, i, *_: (b_, h_, 0, 0)),
         pl.BlockSpec((None, None, block, d), dkv_at),
         pl.BlockSpec((None, None, block, dv), dkv_at)],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((b, h, s, d), part),
         jax.ShapeDtypeStruct((b, h, s, dv), part)],
        [pltpu.VMEM((s, d), jnp.float32), pltpu.VMEM((block, d), jnp.float32),
         pltpu.VMEM((block, dv), jnp.float32)],
        interpret, scalars)(*scalars, *operands)
    if group > 1:
        dk = dk.reshape(b, kv, group, s, d).sum(2).astype(k.dtype)
        dv_ = dv_.reshape(b, kv, group, s, dv).sum(2).astype(v.dtype)
    return dq, dk, dv_


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attention(q, k, v, doc, scale, interpret):
    return _forward(q, k, v, doc, scale, interpret)[0]


def _attention_fwd(q, k, v, doc, scale, interpret):
    out, lse = _forward(q, k, v, doc, scale, interpret)
    return out, (q, k, v, doc, out, lse)


def _attention_bwd(scale, interpret, saved, d_out):
    return (*_backward(*saved, d_out, scale, interpret), None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def causal_attention(q, k, v, *, scale, segments=None, interpret=None):
    """q: (b, s, h, d); k: (b, s, kv, d); v: (b, s, kv, dv), ``tiles(q, k, v)``
    -> softmax attention of each query over the tokens at or before it,
    (b, s, h, dv) in q's dtype; differentiable in q, k and v.  ``segments``
    (b, s) int32, a document index per token that never falls along a row
    (``ops/segments.document_index``): over the tokens at or before it in its own
    document.  ``interpret`` None derives from the backend
    (``backend.resolve_interpret``)."""
    if not tiles(q, k, v):
        raise ValueError(f"the kernel does not tile {q.shape}, {k.shape}, {v.shape}")
    heads_first = lambda t: jnp.swapaxes(t, 1, 2)
    out = _attention(heads_first(q), heads_first(k), heads_first(v), segments, float(scale),
                     resolve_interpret(interpret))
    return heads_first(out)
