"""The fused selective-scan kernels (``ops/pallas/ssd.py``) through the Pallas
interpreter: against the ``lax.scan`` they stand for and against the
token-by-token recurrence, the output and the gradients to x, dt, a, B, C and
D; and the one place that chooses between the two (``ops/ssd.scan_path``):
what it takes, what it counts, what ``llm.fit`` says.

Small shapes: at most 8 heads, 384 tokens in chunks of 128 (the least the
kernels take), a state 128 wide.
"""

import numpy as np
import pytest

CHUNK, SEQ = 128, 384


def _runs(*lengths):
    """Segment ids 1, 2, ... over runs of these lengths."""
    return np.repeat(np.arange(1, len(lengths) + 1, dtype=np.int32), lengths)


#: name -> one row of segment ids each (None: one document a row); boundaries
#: at a chunk's first token, inside it, at its last token, several in a chunk
ROWS = {
    "unpacked": None,
    "start_at_a_chunks_first_token": (_runs(128, 256), _runs(256, 128)),
    "start_inside_a_chunk": (_runs(100, 284), _runs(200, 184)),
    "start_at_a_chunks_last_token": (_runs(127, 257), _runs(255, 129)),
    "several_starts_in_a_chunk": (_runs(100, 20, 1, 7, 128, 128), _runs(3, 5, 130, 1, 1, 244)),
    "a_reused_id": (np.repeat(np.int32([1, 2, 1]), [150, 84, 150]), np.repeat(np.int32([7, 7, 3, 7]), [64, 64, 128, 128])),
}


def _inputs(h, p, g, dtype, rows, seed=0):
    import jax
    import jax.numpy as jnp

    b, n = 2, 128
    ks = jax.random.split(jax.random.PRNGKey(seed + h + p), 6)
    x = jax.random.normal(ks[0], (b, SEQ, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, SEQ, h)) - 2.0)
    a = -jnp.arange(1, h + 1, dtype=jnp.float32) / 4
    b_in, c_in = (0.3 * jax.random.normal(k, (b, SEQ, g, n)).astype(dtype) for k in ks[2:4])
    d_skip = jax.random.normal(ks[4], (h,))
    w = jax.random.normal(ks[5], (b, SEQ, h, p))
    seg = None if rows is None else jnp.asarray(np.stack(rows))
    return (x, dt, a, b_in, c_in, d_skip), seg, w


def _forward_and_gradients(scan, operands, w):
    import jax
    import jax.numpy as jnp

    loss = lambda *ops: jnp.sum(scan(*ops).astype(jnp.float32) * w)
    return (scan(*operands),) + jax.jit(jax.grad(loss, tuple(range(6))))(*operands)


def _gap(got, want) -> float:
    got, want = (np.asarray(t, np.float32).ravel() for t in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _three_ways(operands, seg, w):
    """(kernel, lax scan, recurrence): each the output and six gradients."""
    import jax.numpy as jnp
    from fedml_tpu.ops import ssd as ssd_lib
    from fedml_tpu.ops.pallas import ssd as kernel
    from fedml_tpu.ops.segments import document_index

    doc = jnp.zeros(operands[0].shape[:2], jnp.int32) if seg is None else document_index(seg)
    return (_forward_and_gradients(lambda *ops: kernel.ssd(*ops, doc, CHUNK, interpret=True), operands, w),
            _forward_and_gradients(lambda *ops: ssd_lib.ssd(*ops, seg, CHUNK), operands, w),
            _forward_and_gradients(lambda *ops: ssd_lib.ssd_recurrent(*ops, seg), operands, w))


@pytest.mark.parametrize("rows", list(ROWS))
def test_kernel_is_the_scan_and_the_recurrence(rows):
    """float32, 4 heads of 32 on one group: the kernel pair differs from the
    ``lax.scan`` and from the recurrence by the order of its sums alone,
    wherever the documents start."""
    import jax.numpy as jnp
    from fedml_tpu.ops.pallas import ssd as kernel

    operands, seg, w = _inputs(4, 32, 1, jnp.float32, ROWS[rows])
    assert kernel.tiles(operands[0], operands[3], CHUNK)
    got, scan, recurrence = _three_ways(operands, seg, w)
    assert got[0].dtype == operands[0].dtype and got[0].shape == operands[0].shape
    for g, s, r, like in zip(got, scan, recurrence, (operands[0],) + operands):
        assert g.shape == like.shape and g.dtype == s.dtype
        assert _gap(g, s) < 1e-5 and _gap(g, r) < 2e-5


@pytest.mark.parametrize("h,p,g,dtype,tol", [
    (8, 32, 2, "float32", 1e-5),      # four heads a group of B, C: one grid step a group
    (4, 64, 1, "float32", 1e-5),      # the cell's head width: two heads a register
    (2, 128, 2, "float32", 1e-5),     # a head a group, a head a register
    (8, 16, 1, "float32", 1e-5),      # eight heads a register
    (4, 64, 1, "bfloat16", 1e-2),     # the cell's dtype: other roundings of W and dt x
    (8, 32, 2, "bfloat16", 1e-2),
])
def test_kernel_takes_these_heads_groups_and_dtypes(h, p, g, dtype, tol):
    """Packed rows with starts inside chunks; in bfloat16 kernel and scan are
    each as far from the float32 recurrence as from one another."""
    import jax.numpy as jnp

    operands, seg, w = _inputs(h, p, g, jnp.dtype(dtype), ROWS["several_starts_in_a_chunk"])
    got, scan, recurrence = _three_ways(operands, seg, w)
    for name, g_, s, r in zip("y x dt a B C D".split(), got, scan, recurrence):
        assert g_.dtype == s.dtype, name
        assert _gap(g_, s) < tol, name
        assert _gap(g_, r) < max(2 * tol, 1.5 * _gap(s, r)), name


def test_a_fast_head_underflows_to_zero_on_the_kernel_path():
    """``a = -64`` and steps near 10: decays of exp(-600) a token.  Only
    differences of the running sum enter an exp, so they underflow to 0, not
    to inf or nan, forward and backward; the output is then the token's own
    contribution."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.pallas import ssd as kernel
    from fedml_tpu.ops.segments import document_index

    (x, dt, a, b_in, c_in, d_skip), seg, w = _inputs(4, 32, 1, jnp.float32, ROWS["start_inside_a_chunk"])
    doc, a, dt = document_index(seg), jnp.full_like(a, -64.0), dt + 9.0
    scan = lambda *ops: kernel.ssd(*ops, doc, CHUNK, interpret=True)
    y = scan(x, dt, a, b_in, c_in, d_skip)
    grads = jax.grad(lambda *ops: jnp.sum(scan(*ops) * w), tuple(range(6)))(x, dt, a, b_in, c_in, d_skip)
    assert bool(jnp.all(jnp.isfinite(y))) and all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
    own = (jnp.einsum("bsn,bsn->bs", c_in[:, :, 0], b_in[:, :, 0])[..., None] * dt + d_skip)[..., None] * x
    assert _gap(y, own) < 1e-6


def test_tiles_refuses_what_the_kernel_does_not_take():
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.pallas import ssd as kernel

    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    cell = spec(1, 32768, 64, 64), spec(1, 32768, 1, 128)
    assert kernel.tiles(*cell, 256) and kernel.heads_per_step(64, 1, 64) == 16
    assert not kernel.tiles(spec(1, 32700, 64, 64), spec(1, 32700, 1, 128), 256)   # a ragged tail
    assert not kernel.tiles(*cell, 64)                                             # chunks under a register
    assert not kernel.tiles(spec(1, 512, 4, 48), spec(1, 512, 1, 128), 128)        # heads that split a register
    assert not kernel.tiles(spec(1, 512, 4, 256), spec(1, 512, 1, 128), 128)       # ... or outgrow one
    assert not kernel.tiles(spec(1, 512, 4, 64), spec(1, 512, 1, 16), 128)         # a narrow state
    assert not kernel.tiles(spec(1, 512, 3, 64), spec(1, 512, 1, 128), 128)        # an odd head left over
    with pytest.raises(ValueError, match="does not tile"):
        kernel.ssd(jnp.zeros((1, 200, 4, 32)), jnp.ones((1, 200, 4)), -jnp.ones(4), jnp.zeros((1, 200, 1, 128)),
                   jnp.zeros((1, 200, 1, 128)), jnp.ones(4), jnp.zeros((1, 200), jnp.int32), 128, interpret=True)


@pytest.fixture
def on_a_tpu(monkeypatch):
    """What the choice sees on a TPU backend, with the kernels it then takes
    run through the interpreter (this is still the CPU)."""
    import jax
    from fedml_tpu.ops.pallas import ssd as kernel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "resolve_interpret", lambda interpret=None: True)


@pytest.mark.parametrize("case,path", [
    ("plain", "kernel"), ("packed", "kernel"), ("mesh", "scan"), ("cpu", "scan"), ("ragged", "scan"),
    ("small_chunk", "scan"), ("narrow_state", "scan"),
])
def test_the_choice_of_path(case, path, request, eight_devices):
    """One function chooses, from the mesh, the backend and the shapes; the
    counter says which way each call site went, and either way gives the
    scan's result."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops import ssd as ssd_lib
    from fedml_tpu.parallel import mesh as meshlib

    if case != "cpu":
        request.getfixturevalue("on_a_tpu")
    (x, dt, a, b_in, c_in, d_skip), seg, _ = _inputs(
        4, 32, 1, jnp.float32, ROWS["start_inside_a_chunk"] if case == "packed" else None)
    if case == "ragged":
        x, dt, b_in, c_in = (t[:, :300] for t in (x, dt, b_in, c_in))
    if case == "narrow_state":
        b_in, c_in = b_in[..., :16], c_in[..., :16]
    chunk = 64 if case == "small_chunk" else CHUNK
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=eight_devices[:2]) if case == "mesh" else None
    before = ssd_lib.scan_sites()
    assert ssd_lib.scan_path(x, b_in, chunk, mesh) == path
    got = jax.jit(lambda *ops: ssd_lib.ssd(*ops, seg, chunk, mesh))(x, dt, a, b_in, c_in, d_skip)
    after = ssd_lib.scan_sites()
    other = "scan" if path == "kernel" else "kernel"
    assert after[path] - before[path] == 2 and after[other] == before[other]
    assert _gap(got, ssd_lib.ssd_recurrent(x, dt, a, b_in, c_in, d_skip, seg)) < 2e-5


def _tiny_mamba():
    import jax.numpy as jnp
    from fedml_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64, max_seq_len=256,
        dtype=jnp.float32, logits_dtype=jnp.float32, remat=True, remat_policy="full",
        mixer_types=("mamba", "mamba"), mamba_heads=2, mamba_head_dim=64, mamba_d_state=128,
        mamba_chunk=128)


def _fit(devices, steps=2):
    """``LLMTrainer.fit`` on the tiny two-Mamba model over a ``data`` mesh of
    these devices, on packed rows -> (history, the ``llm.fit`` span)."""
    from fedml_tpu.llm import lora
    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
    from fedml_tpu.obs import trace as obstrace
    from fedml_tpu.parallel import mesh as meshlib

    tr = LLMTrainer(_tiny_mamba(), LLMTrainArgs(batch_size=2, seq_len=256, total_steps=steps, lora_rank=2,
                                                lora_targets=lora.MAMBA_TARGETS),
                    mesh=meshlib.make_mesh((meshlib.AXIS_DATA,), devices=devices))
    rng = np.random.default_rng(0)
    rows = [rng.integers(0, 64, (2, 257)) for _ in range(steps)]
    seg = np.stack([_runs(100, 156), _runs(30, 98, 128)])
    history = tr.fit([(r[:, :-1], r[:, 1:], seg) for r in rows], steps=steps)
    return history, [s for s in obstrace.recent() if s.name == "llm.fit"][-1]


@pytest.mark.parametrize("where,kernel,scan", [("cpu", 0, 2), ("tpu", 2, 0), ("tpu_mesh", 0, 2)])
def test_fit_says_which_path_its_scan_sites_took(where, kernel, scan, request, eight_devices):
    """``scan_kernel_sites`` / ``scan_scan_sites`` on ``llm.fit``: the step
    program's own call sites (one a Mamba layer); a model that holds a mesh
    stays on the scan; the same losses either way."""
    want, span = _fit(eight_devices[:1])
    assert (span.attrs["scan_kernel_sites"], span.attrs["scan_scan_sites"]) == (0, 2)
    if where == "cpu":
        return
    request.getfixturevalue("on_a_tpu")
    got, span = _fit(eight_devices[:2 if where == "tpu_mesh" else 1])
    assert (span.attrs["scan_kernel_sites"], span.attrs["scan_scan_sites"]) == (kernel, scan)
    for g, w in zip(got, want):
        assert abs(g["loss"] - w["loss"]) < 1e-4 * abs(w["loss"])


def test_a_mamba_that_holds_a_mesh_stays_on_the_scan(on_a_tpu, eight_devices):
    """The module hands ``ssd`` the mesh it was given: with one the scan is
    built, without one the kernel pair, and they agree."""
    import jax
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops import ssd as ssd_lib
    from fedml_tpu.parallel import mesh as meshlib

    cfg = _tiny_mamba()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 32))
    positions, seg = np.arange(256)[None].repeat(2, 0), np.stack([_runs(100, 156), _runs(256)])
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=eight_devices[:2])
    params = jax.jit(tfm.Mamba(cfg).init)(jax.random.PRNGKey(1), x, positions, seg)
    outs = {}
    for name, module in (("kernel", tfm.Mamba(cfg)), ("scan", tfm.Mamba(cfg, mesh))):
        before = ssd_lib.scan_sites()
        outs[name] = jax.jit(module.apply)(params, x, positions, seg)
        grew = {p: n - before[p] for p, n in ssd_lib.scan_sites().items()}
        assert grew == {"kernel": int(name == "kernel"), "scan": int(name == "scan")}
    assert _gap(outs["kernel"], outs["scan"]) < 1e-5
