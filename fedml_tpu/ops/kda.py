"""Kimi Delta Attention (KDA): the gated delta rule with a decay per channel,
in chunks.

Per head, with a key-wide log-decay ``g_t <= 0`` (one number a channel),
a write strength ``beta_t`` in (0, 1) and L2-normed ``q_t``, ``k_t``, the
layer is the recurrence on a (d_k x d_v) state in float32

    S'_t = Diag(exp g_t) S_{t-1}
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = scale * S_t^T q_t

(the Kimi Linear report, arXiv:2510.26692): before it writes, the state
forgets per channel and takes out what it already predicts for ``k_t``.
``kda`` computes it ``C`` tokens at a time under one ``lax.scan``.  With
``G_t`` the running sum of ``g`` inside the chunk (inclusive) and ``S`` the
state entering it:

    A_tj  = beta_t sum_c k_tc k_jc exp(G_tc - G_jc)    (j < t)
    M_tj  =        sum_c q_tc k_jc exp(G_tc - G_jc)    (j <= t)
    U     = (I + A)^-1 diag(beta) (V - (K o exp G) S)
    O     = scale [(Q o exp G) S + M U]
    S_out = Diag(exp G_C) S + (K o exp(G_C - G))^T U

``A`` and ``M`` weigh each channel of a pair by its own decay, so they are no
plain ``Q K^T``.  Only differences ``G_t - G_j <= 0`` are exponentiated, so
nothing overflows: a chunk goes in sub-blocks of ``SUB`` tokens; a pair in
two different sub-blocks splits its decay at the later one's first token
``r`` (``exp(G_t - G_r) exp(G_r - G_j)``, both factors at most 1, and a
product over the channels on the matrix unit), and the pairs inside one
sub-block take their (SUB, SUB, d_k) decays whole.  Everything inside a
chunk is float32, its products at ``HIGHEST`` precision (a TPU's default
rounds a float32 operand to bfloat16, and the solve and the carried state
would inherit that); q, k, v come in the model's dtype.

Two paths, one algorithm; ``kda_path`` chooses, from what the program can
observe, as ``ops/ssd.scan_path`` does.  ``"scan"`` is the ``lax.scan``
below: its triangular solve is ``lax.linalg``'s, and its backward pass is the
scan's own with each chunk rematerialised (``jax.checkpoint`` around the
body), so only the carried states (2 MB a chunk at 32 heads of 128) are kept.
``"kernel"`` is the fused Pallas pair of ``ops/pallas/kda.py``, for one TPU
device and shapes it tiles: the state and a chunk's tiles stay in VMEM, the
solve is exact inside the kernel, and the backward is written out from the
saved entry states.  The scan is the path of every mesh and of the CPU, and
the form the kernel is held to (``tests/test_kda_kernel.py``);
``kda_recurrent`` is the token-by-token form both are held to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.trace import LLM_KDA_SITES
from .pallas import kda as kda_kernel

#: tokens a step of the scan takes where the caller names none (the chunk of
#: flash-linear-attention's KDA kernels)
CHUNK = 64
#: tokens of a sub-block whose pairs take their decays whole (the kernel's too)
SUB = kda_kernel.SUB
_HI = jax.lax.Precision.HIGHEST

#: the paths a KDA call site is built on
KDA_PATHS = ("kernel", "scan")


def kda_sites() -> dict:
    """Call sites counted so far, by path (``fedml_llm_kda_sites_total``): a
    program's own are what its tracing adds."""
    return {path: int(LLM_KDA_SITES.value(path=path)) for path in KDA_PATHS}


def kda_path(q, v, chunk: int, mesh) -> str:
    """``"kernel"`` where the fused Pallas pair can stand for the ``lax.scan``,
    else ``"scan"``: no mesh in the caller's hands (jax cannot partition a
    Mosaic call), a TPU backend, and shapes the kernel tiles (whole chunks
    among them: a ragged tail is the scan's).  Counts the call site under the
    path it takes: called while a program is traced, so once for each site
    the program has."""
    kernel = (mesh is None and jax.default_backend() == "tpu"
              and kda_kernel.tiles(q, v, min(chunk or CHUNK, q.shape[1])))
    path = "kernel" if kernel else "scan"
    LLM_KDA_SITES.inc(1, path=path)
    return path


def _pairs(x, k, gam, strict: bool = False):
    """x, k, gam: (..., c, d) float32 -> (..., c, c): ``sum_d x_td k_jd
    exp(G_td - G_jd)`` for ``j <= t`` (``j < t`` with ``strict``), 0 above."""
    c, d = x.shape[-2:]
    sub = SUB if c % SUB == 0 else c
    n, lead = c // sub, x.shape[:-2]
    blocks = lambda t: t.reshape(*lead, n, sub, d)
    gb = blocks(gam)
    ref = gb[..., :1, :]                                         # G at each sub-block's first token
    # pairs across sub-blocks: each factor's exponent is <= 0
    xr = blocks(x) * jnp.exp(gb - ref)                           # (..., n, sub, d)
    earlier = jnp.arange(c)[None, :] < (jnp.arange(n) * sub)[:, None]              # (n, c)
    kr = k[..., None, :, :] * jnp.exp(jnp.where(earlier[:, :, None], ref - gam[..., None, :, :], -jnp.inf))
    across = jnp.einsum("...nid,...njd->...nij", xr, kr, precision=_HI).reshape(*lead, c, c)
    # pairs inside a sub-block, their decays whole
    tri = jnp.tril(jnp.ones((sub, sub), bool), -1 if strict else 0)
    decay = jnp.exp(jnp.where(tri[:, :, None], gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    inside = jnp.sum(blocks(x)[..., :, None, :] * blocks(k)[..., None, :, :] * decay, -1)
    inside = jnp.einsum("...nij,nm->...nimj", inside, jnp.eye(n, dtype=inside.dtype),
                        precision=_HI).reshape(*lead, c, c)
    return across + inside


def kda(q, k, v, g, beta, chunk: int = 0, scale=None, mesh=None):
    """q, k: (b, s, h, d_k); v: (b, s, h, d_v); g: (b, s, h, d_k) float32,
    <= 0; beta: (b, s, h) float32 -> (b, s, h, d_v) in q's dtype.  ``chunk``
    0 is ``CHUNK``; ``scale`` None is ``d_k ** -0.5``.  ``s`` need not be a
    multiple of the chunk: the tail is padded with ``g = 0`` and ``beta = 0``
    (a token that neither decays nor writes) and cut off again.  ``mesh`` is
    the mesh the calling module holds, if any: what it computes on may be
    sharded, which keeps it on the ``lax.scan`` (``kda_path``)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    scale = dk ** -0.5 if scale is None else scale
    c = min(chunk or CHUNK, s)
    if kda_path(q, v, chunk, mesh) == "kernel":
        return kda_kernel.kda(q, k, v, g, beta, c, scale)
    nc = -(-s // c)
    f32 = jnp.float32
    g, beta = g.astype(f32), beta.astype(f32)
    if nc * c != s:
        pad = lambda t: jnp.pad(t, ((0, 0), (0, nc * c - s)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = pad(q), pad(k), pad(v), pad(g), pad(beta)
    # (nc, b, h, c, ...): the scan runs over chunks
    chunks = lambda t: jnp.moveaxis(t.reshape(b, nc, c, h, *t.shape[3:]), (1, 3), (0, 2))
    eye = jnp.eye(c, dtype=f32)

    def one_chunk(state, xs):
        qc, kc, vc, gc, bc = xs                     # (b, h, c, ...); state (b, h, d_k, d_v)
        q32, k32, v32 = (t.astype(f32) for t in (qc, kc, vc))
        gam = jnp.cumsum(gc, axis=-2)
        a = bc[..., None] * _pairs(k32, k32, gam, strict=True)
        m = _pairs(q32, k32, gam)
        eg = jnp.exp(gam)
        rhs = bc[..., None] * (v32 - jnp.einsum("bhck,bhkv->bhcv", k32 * eg, state, precision=_HI))
        u = jax.scipy.linalg.solve_triangular(eye + a, rhs, lower=True, unit_diagonal=True)
        out = scale * (jnp.einsum("bhck,bhkv->bhcv", q32 * eg, state, precision=_HI)
                       + jnp.einsum("bhcj,bhjv->bhcv", m, u, precision=_HI))
        last = gam[..., -1:, :]
        state = jnp.exp(last)[..., 0, :, None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", k32 * jnp.exp(last - gam), u, precision=_HI)
        return state, out.astype(qc.dtype)

    _, out = jax.lax.scan(jax.checkpoint(one_chunk, prevent_cse=False), jnp.zeros((b, h, dk, dv), f32),
                          tuple(chunks(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(b, nc * c, h, dv)[:, :s]


def chunk_decay(g, chunk: int = 0):
    """Mean over batch, heads, channels and chunks of ``G_C``, the log of how
    much of the state a chunk lets through (the tail's padding decays by 0)."""
    b, s = g.shape[:2]
    c = min(chunk or CHUNK, s)
    return jnp.sum(g.astype(jnp.float32)) / (b * -(-s // c) * g.shape[2] * g.shape[3])


def kda_recurrent(q, k, v, g, beta, scale=None):
    """The token-by-token recurrence in float32: what the chunked form is
    held to in the tests."""
    f32 = jnp.float32
    scale = q.shape[-1] ** -0.5 if scale is None else scale

    def one_token(state, xs):
        qt, kt, vt, gt, bt = xs                                     # (b, h, ...)
        state = jnp.exp(gt)[..., None] * state
        u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, state))
        state = state + kt[..., None] * u[..., None, :]
        return state, scale * jnp.einsum("bhk,bhkv->bhv", qt, state)

    b, _, h, dk = q.shape
    seq_first = lambda t: jnp.moveaxis(t.astype(f32), 1, 0)
    _, out = jax.lax.scan(one_token, jnp.zeros((b, h, dk, v.shape[-1]), f32),
                          tuple(seq_first(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)
