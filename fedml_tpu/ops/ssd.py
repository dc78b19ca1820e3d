"""Mamba-2's selective state-space scan (SSD), in chunks, with a reset of the
state at document starts.

Per head ``h`` (width ``p``) with an input-dependent step ``dt_t > 0`` and a
fixed ``A_h < 0`` the layer is the recurrence

    a_t = exp(dt_t A_h)
    S_t = a_t S_{t-1} + (dt_t x_t) (x) B_t          (p x n state, float32)
    y_t = S_t C_t + D_h x_t

with ``B_t``, ``C_t`` (n wide) shared by the heads of a group, and
``S_{t-1} := 0`` at a document's first token.  ``ssd`` computes it a chunk of
``C`` tokens at a time under one ``lax.scan``.  With ``l_t`` the running sum
of ``dt A`` inside the chunk:

    Y     = (L o (C B^T)) (dt * X) + exp(l) (C S_in)
    L_ij  = exp(l_i - l_j) for i >= j in ONE document, else 0
    S_out = exp(l_last) S_in + sum_j exp(l_last - l_j) (dt_j x_j) (x) B_j

the term in ``S_in`` only for the tokens of the document ``S_in`` belongs to,
``S_out`` from the tokens of the chunk's last document only, and from
``S_in`` only if no document starts inside the chunk.  As in
``ops/lightning_attention.py`` (the special case ``a_t = lam_h``, ``dt = 1``, a
key and a query per head) no ``s x s`` matrix is built and nothing is divided
by a decay: only differences of ``l``, never quotients of ``a``, so a fast
head underflows to 0 and not to inf.  Products take their operands in the
inputs' dtype (bfloat16 on the chip) into float32 accumulators; ``dt``, the
decays and the state (also as an operand of ``C S_in``) are float32.

Two paths, one algorithm; ``scan_path`` chooses, from what the program can
observe.  ``"scan"`` is the ``lax.scan`` below, whose backward pass is the
scan's own with each chunk rematerialised (``jax.checkpoint`` around the
body): what a chunk builds per head and pair of tokens (``L``, 16.8 MB in
float32 at 64 heads x 256 x 256) is not kept for 128 chunks, only the carried
states are (2 MB each); every piece of a chunk is a fusion with its operands
in HBM.  ``"kernel"`` is the fused Pallas pair of ``ops/pallas/ssd.py``, for
one TPU device and shapes it tiles: the tile and the carried state stay in
VMEM; between forward and backward it keeps the operands and the state
ENTERING each chunk (what the scan's checkpoint keeps), and the backward
rebuilds ``C B^T``, the decay tile and ``W`` a chunk at a time, once.  The
scan is the path of every mesh and of the CPU, and the form the kernel is
held to (``tests/test_ssd_kernel.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.trace import LLM_SCAN_SITES
from .pallas import ssd as ssd_kernel
from .segments import document_index

#: tokens a step of the scan takes where the caller names none (Mamba-2's own
#: and granite-4.0-h's ``mamba_chunk_size``)
CHUNK = 256


#: the paths a selective-scan call site is built on
SCAN_PATHS = ("kernel", "scan")


def scan_sites() -> dict:
    """Call sites counted so far, by path (``fedml_llm_scan_sites_total``): a
    program's own are what its tracing adds."""
    return {path: int(LLM_SCAN_SITES.value(path=path)) for path in SCAN_PATHS}


def scan_path(x, b_in, chunk: int, mesh) -> str:
    """``"kernel"`` where the fused Pallas pair can stand for the ``lax.scan``,
    else ``"scan"``: no mesh in the caller's hands (jax cannot partition a
    Mosaic call), a TPU backend, and shapes the kernel tiles (whole chunks
    among them: a ragged tail is the scan's).  Counts the call site under the
    path it takes: called while a program is traced, so once for each site
    the program has."""
    kernel = (mesh is None and jax.default_backend() == "tpu"
              and ssd_kernel.tiles(x, b_in, min(chunk or CHUNK, x.shape[1])))
    path = "kernel" if kernel else "scan"
    LLM_SCAN_SITES.inc(1, path=path)
    return path


def ssd(x, dt, a, b_in, c_in, d_skip, segments=None, chunk: int = 0, mesh=None):
    """x: (b, s, h, p); dt: (b, s, h) float32, positive (after its softplus);
    a: (h,) float32, negative; b_in, c_in: (b, s, g, n) with ``h % g == 0``;
    d_skip: (h,); segments: (b, s) document ids or None (one document a row)
    -> (b, s, h, p) in x's dtype.  ``chunk`` 0 is ``CHUNK``.  ``s`` need not
    be a multiple of it: the tail is padded with ``dt = 0`` (a token that
    neither decays nor feeds the state) and cut off again.  ``mesh`` is the
    mesh the calling module holds, if any: what it computes on may be sharded,
    which keeps it on the ``lax.scan`` (``scan_path``)."""
    if scan_path(x, b_in, chunk, mesh) == "kernel":
        doc = jnp.zeros(x.shape[:2], jnp.int32) if segments is None else document_index(segments)
        return ssd_kernel.ssd(x, dt, a, b_in, c_in, d_skip, doc, min(chunk or CHUNK, x.shape[1]))
    b, s, h, p = x.shape
    g, n = b_in.shape[2:]
    c = min(chunk or CHUNK, s)
    nc = -(-s // c)
    f32 = jnp.float32
    doc = jnp.zeros((b, s), jnp.int32) if segments is None else document_index(segments)
    if nc * c != s:
        pad = lambda t, **kw: jnp.pad(t, ((0, 0), (0, nc * c - s)) + ((0, 0),) * (t.ndim - 2), **kw)
        x, dt, b_in, c_in, doc = pad(x), pad(dt), pad(b_in), pad(c_in), pad(doc, mode="edge")
    # (nc, b, c, ...): the scan runs over chunks
    chunks = lambda t: jnp.moveaxis(t.reshape(b, nc, c, *t.shape[2:]), 1, 0)
    a = a.astype(f32)
    causal = jnp.tril(jnp.ones((c, c), bool))

    def one_chunk(carry, xs):
        state, doc_in = carry                       # (b, g, h/g, p, n) float32; (b,)
        xc, dtc, bc, cc, dc = xs                    # (b, c, ...)
        l = jnp.cumsum(dtc * a, axis=1)             # (b, c, h) float32, <= 0 and falling
        # L: a pair of tokens of one document, the later one first
        same = causal & (dc[:, :, None] == dc[:, None, :])                      # (b, c, c)
        diff = jnp.where(same[..., None], l[:, :, None, :] - l[:, None, :, :], -jnp.inf)
        decay = jnp.moveaxis(jnp.exp(diff), 3, 1).reshape(b, g, h // g, c, c)    # (b, g, h/g, i, j)
        scores = jnp.einsum("bign,bjgn->bgij", cc, bc, preferred_element_type=f32)
        xdt = (xc.astype(f32) * dtc[..., None]).astype(xc.dtype).reshape(b, c, g, h // g, p)
        y = jnp.einsum("bgeij,bjgep->bigep", (scores[:, :, None] * decay).astype(xc.dtype), xdt,
                       preferred_element_type=f32)
        # the carried state reaches the tokens of the document it belongs to
        reach = jnp.where((dc == doc_in[:, None])[..., None], jnp.exp(l), 0.0)  # (b, c, h)
        y = y + reach.reshape(b, c, g, h // g, 1) * jnp.einsum(
            "bign,bgepn->bigep", cc.astype(f32), state)
        # the state that leaves: the chunk's last document's tokens, and what
        # came in if that document started before the chunk
        last, doc_out = l[:, -1:], dc[:, -1]
        kept = jnp.where((dc == doc_out[:, None])[..., None], jnp.exp(last - l), 0.0)
        fed = (xdt.astype(f32) * kept.reshape(b, c, g, h // g, 1)).astype(xc.dtype)
        through = jnp.where((doc_out == doc_in)[:, None], jnp.exp(last[:, 0]), 0.0)    # (b, h)
        state = through.reshape(b, g, h // g, 1, 1) * state + jnp.einsum(
            "bjgep,bjgn->bgepn", fed, bc, preferred_element_type=f32)
        return (state, doc_out), y.reshape(b, c, h, p)

    init = (jnp.zeros((b, g, h // g, p, n), f32), doc[:, 0])
    _, y = jax.lax.scan(jax.checkpoint(one_chunk, prevent_cse=False), init,
                        tuple(chunks(t) for t in (x, dt.astype(f32), b_in, c_in, doc)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, nc * c, h, p)[:, :s]
    return (y + d_skip.astype(f32)[:, None] * x[:, :s].astype(f32)).astype(x.dtype)


def ssd_recurrent(x, dt, a, b_in, c_in, d_skip, segments=None):
    """The token-by-token recurrence in float32: what the chunked form is
    held to in the tests."""
    b, s, h, p = x.shape
    g, n = b_in.shape[2:]
    f32 = jnp.float32
    doc = jnp.zeros((b, s), jnp.int32) if segments is None else document_index(segments)
    first = jnp.pad(doc[:, 1:] != doc[:, :-1], ((0, 0), (1, 0)), constant_values=True)
    rep = lambda t: jnp.repeat(t.astype(f32), h // g, axis=2)      # a group's B, C for each of its heads

    def one_token(state, xs):
        xt, dtt, bt, ct, start = xs                                 # (b, h, p), (b, h), (b, h, n) x 2, (b,)
        state = jnp.where(start[:, None, None, None], 0.0, state)
        state = jnp.exp(dtt * a)[..., None, None] * state + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    seq_first = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(one_token, jnp.zeros((b, h, p, n), f32),
                        (seq_first(x.astype(f32)), seq_first(dt.astype(f32)), seq_first(rep(b_in)),
                         seq_first(rep(c_in)), seq_first(first)))
    return jnp.moveaxis(y, 0, 1) + d_skip.astype(f32)[:, None] * x.astype(f32)
