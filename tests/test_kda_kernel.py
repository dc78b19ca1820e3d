"""The fused KDA kernels (``ops/pallas/kda.py``) through the Pallas
interpreter: against the ``lax.scan`` they stand for and against the
token-by-token recurrence, the output and the gradients to q, k, v, g and
beta; and the one place that chooses between the two (``ops/kda.kda_path``):
what it takes, what it counts, what ``llm.fit`` says.

Small shapes: heads of 128 (the least the kernels take), at most 192 tokens,
batch 2, float32 in and out so that kernel, scan and recurrence differ by the
order of their sums alone.
"""

import numpy as np
import pytest

#: the tolerance ``tests/test_kimi.py::test_chunked_rule_is_the_token_recurrence``
#: holds the lax form to: of the largest entry, at least 1
TOL = 2e-5


def _inputs(seed, s, h, band, b=2, d=128):
    """L2-normed q, k, values, log-decays per channel whose exp spans
    ``band`` (the fastest channel's decay a token, the slowest's) and write
    strengths in (0, 1), float32."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    shape = (b, s, h, d)
    lo, hi = np.log(-np.log(band[1])), np.log(-np.log(band[0]))
    g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=lo, maxval=hi))
    return (l2(jax.random.normal(ks[0], shape)), l2(jax.random.normal(ks[1], shape)),
            jax.random.normal(ks[2], shape), g, jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])))


def _rule_and_grads(fn, inputs):
    """Output and the gradient of a fixed projection of it to every input."""
    import jax
    import jax.numpy as jnp

    probe = jax.random.normal(jax.random.PRNGKey(99), inputs[2].shape)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(fn)(*inputs)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * probe), argnums=(0, 1, 2, 3, 4)))(*inputs)
    return (out,) + grads


def _kernel(chunk):
    from fedml_tpu.ops.pallas import kda as kernel

    return lambda q, k, v, g, beta: kernel.kda(q, k, v, g, beta, chunk, q.shape[-1] ** -0.5, interpret=True)


def _gap(got, want) -> float:
    """The largest difference over the largest entry (at least 1)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(1.0, float(np.abs(want).max())))


#: (tokens, chunk, heads, decay band): three chunks of four sub-blocks, two
#: heads a grid step whose systems go through the matrix unit together;
#: chunks of two sub-blocks, three heads a step and together, a band down to
#: a decay of 1e-4 a token; a chunk of three sub-blocks, whose inverse's last
#: doubling covers a partial block, each head's system alone
CASES = [(192, 64, 2, (0.05, 0.999)), (128, 32, 3, (1e-4, 0.99)), (96, 48, 2, (0.5, 0.9999))]


@pytest.mark.parametrize("s,chunk,h,band", CASES)
def test_kernel_is_the_scan_and_the_recurrence(s, chunk, h, band):
    """Forward and the gradient to each of q, k, v, g, beta: the kernel pair
    differs from the ``lax.scan`` and from the recurrence by the order of its
    sums alone, within the lax form's own tolerance."""
    from fedml_tpu.ops import kda as kda_lib
    from fedml_tpu.ops.pallas import kda as kernel

    inputs = _inputs(3, s, h, band)
    assert kernel.tiles(inputs[0], inputs[2], chunk) and kernel.heads_per_step(h) == h
    got = _rule_and_grads(_kernel(chunk), inputs)
    scan = _rule_and_grads(lambda *a: kda_lib.kda(*a, chunk=chunk), inputs)
    recurrence = _rule_and_grads(kda_lib.kda_recurrent, inputs)
    for name, g, sc, r, like in zip("out q k v g beta".split(), got, scan, recurrence, (inputs[2],) + inputs):
        assert g.shape == like.shape and g.dtype == like.dtype, name
        assert _gap(g, sc) < TOL and _gap(g, r) < TOL, (name, _gap(g, sc), _gap(g, r))


def test_the_kernel_without_the_carried_state_fails_that_tolerance():
    """Each chunk from a zero state (what a pass that dropped ``S`` would
    give) is far outside the tolerance the kernel meets, with a band in which
    the state carries across chunks."""
    import jax.numpy as jnp
    from fedml_tpu.ops.kda import kda_recurrent

    s, chunk, h, band = CASES[0]
    inputs = _inputs(3, s, h, band)
    run = _kernel(chunk)
    cut = lambda *a: jnp.concatenate([run(*(t[:, i: i + chunk] for t in a)) for i in range(0, s, chunk)], axis=1)
    got, want = _rule_and_grads(cut, inputs), _rule_and_grads(kda_recurrent, inputs)
    assert _gap(got[0], want[0]) > 100 * TOL, _gap(got[0], want[0])
    assert max(_gap(g, w) for g, w in zip(got[1:], want[1:])) > TOL


def test_strong_decay_stays_finite_on_the_kernel_path():
    """A log-decay of -200 a channel and token (exp of it underflows to 0):
    nothing is divided by a decay and no exponent is positive, so the kernel
    pair's output and gradients stay finite and agree with the recurrence."""
    import jax.numpy as jnp
    from fedml_tpu.ops.kda import kda_recurrent

    q, k, v, g, beta = _inputs(4, 128, 1, (0.05, 0.999))
    g = g.at[:, 40:60].set(-200.0)
    got = _rule_and_grads(_kernel(64), (q, k, v, g, beta))
    want = _rule_and_grads(kda_recurrent, (q, k, v, g, beta))
    assert all(bool(jnp.isfinite(t).all()) for t in got)
    assert _gap(got[0], want[0]) < TOL


def test_tiles_refuses_what_the_kernel_does_not_take():
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.pallas import kda as kernel

    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    cell = spec(1, 16384, 32, 128)
    assert kernel.tiles(cell, cell, 64) and kernel.heads_per_step(32) == kernel.HEADS
    assert not kernel.tiles(spec(1, 16380, 32, 128), spec(1, 16380, 32, 128), 64)   # a ragged tail
    assert not kernel.tiles(cell, cell, 40)                                           # chunks of part sub-blocks
    assert not kernel.tiles(spec(1, 512, 4, 64), spec(1, 512, 4, 64), 64)            # heads narrower than a register
    assert not kernel.tiles(cell, spec(1, 16384, 32, 96), 64)                         # ... or values that split one
    with pytest.raises(ValueError, match="does not tile"):
        kernel.kda(*(jnp.zeros((1, 100, 2, 128)),) * 4, jnp.zeros((1, 100, 2)), 64, 1.0, interpret=True)


@pytest.fixture
def on_a_tpu(monkeypatch):
    """What the choice sees on a TPU backend, with the kernels it then takes
    run through the interpreter (this is still the CPU)."""
    import jax
    from fedml_tpu.ops.pallas import kda as kernel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "resolve_interpret", lambda interpret=None: True)


@pytest.mark.parametrize("case,path", [
    ("tpu", "kernel"), ("mesh", "scan"), ("cpu", "scan"), ("ragged", "scan"), ("narrow", "scan"),
])
def test_the_choice_of_path(case, path, request, eight_devices):
    """One function chooses, from the mesh, the backend and the shapes; the
    counter says which way each call site went, and either way gives the
    recurrence's result."""
    import jax
    from fedml_tpu.ops import kda as kda_lib
    from fedml_tpu.parallel import mesh as meshlib

    if case != "cpu":
        request.getfixturevalue("on_a_tpu")
    inputs = _inputs(5, 128, 1, (0.05, 0.999), d=64 if case == "narrow" else 128)
    if case == "ragged":
        inputs = tuple(t[:, :100] for t in inputs)
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=eight_devices[:2]) if case == "mesh" else None
    before = kda_lib.kda_sites()
    assert kda_lib.kda_path(inputs[0], inputs[2], 64, mesh) == path
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: kda_lib.kda(*a, chunk=64, mesh=mesh))(*inputs)
    after = kda_lib.kda_sites()
    other = "scan" if path == "kernel" else "kernel"
    assert after[path] - before[path] == 2 and after[other] == before[other]
    assert _gap(got, kda_lib.kda_recurrent(*inputs)) < TOL


def _tiny_kda():
    import jax.numpy as jnp
    from fedml_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64, max_seq_len=128,
        dtype=jnp.float32, logits_dtype=jnp.float32, remat=True, remat_policy="full",
        mixer_types=("kda", "kda"), kda_heads=1, kda_head_dim=128)


def _fit(devices, steps=2):
    """``LLMTrainer.fit`` on the tiny two-KDA model over a ``data`` mesh of
    these devices -> (history, the ``llm.fit`` span, ``kda_sites``)."""
    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
    from fedml_tpu.obs import trace as obstrace
    from fedml_tpu.parallel import mesh as meshlib

    tr = LLMTrainer(_tiny_kda(), LLMTrainArgs(batch_size=2, seq_len=128, total_steps=steps),
                    mesh=meshlib.make_mesh((meshlib.AXIS_DATA,), devices=devices))
    rng = np.random.default_rng(0)
    rows = [rng.integers(0, 64, (2, 129)) for _ in range(steps)]
    history = tr.fit([(r[:, :-1], r[:, 1:]) for r in rows], steps=steps)
    return history, [s for s in obstrace.recent() if s.name == "llm.fit"][-1], tr.kda_sites


@pytest.mark.parametrize("where,kernel,scan", [("tpu", 2, 0), ("tpu_mesh", 0, 2)])
def test_fit_says_which_path_its_kda_sites_took(where, kernel, scan, request, eight_devices):
    """``kda_kernel_sites`` / ``kda_scan_sites`` on ``llm.fit`` and
    ``LLMTrainer.kda_sites``: the step program's own call sites (one a KDA
    layer); on the CPU and with a mesh the scan, on one TPU device the kernel
    pair; the same losses either way."""
    want, span, sites = _fit(eight_devices[:1])
    assert (span.attrs["kda_kernel_sites"], span.attrs["kda_scan_sites"]) == (0, 2)
    assert sites == {"kernel": 0, "scan": 2}
    request.getfixturevalue("on_a_tpu")
    got, span, sites = _fit(eight_devices[:2 if where == "tpu_mesh" else 1])
    assert (span.attrs["kda_kernel_sites"], span.attrs["kda_scan_sites"]) == (kernel, scan)
    assert sites == {"kernel": kernel, "scan": scan}
    for g, w in zip(got, want):
        assert abs(g["loss"] - w["loss"]) < 1e-5 * abs(w["loss"])


def test_a_kda_that_holds_a_mesh_stays_on_the_scan(on_a_tpu, eight_devices):
    """The module hands ``kda`` the mesh it was given: with one the scan is
    built, without one the kernel pair, and they agree."""
    import jax
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops import kda as kda_lib
    from fedml_tpu.parallel import mesh as meshlib

    cfg = _tiny_kda()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 32))
    positions = np.arange(128)[None].repeat(2, 0)
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=eight_devices[:2])
    params = jax.jit(tfm.KDA(cfg).init)(jax.random.PRNGKey(1), x, positions)
    outs = {}
    for name, module in (("kernel", tfm.KDA(cfg)), ("scan", tfm.KDA(cfg, mesh))):
        before = kda_lib.kda_sites()
        with jax.default_matmul_precision("highest"):
            outs[name] = jax.jit(lambda p, x: module.apply(p, x, positions, mutable=["stats"])[0])(params, x)
        grew = {p: n - before[p] for p, n in kda_lib.kda_sites().items()}
        assert grew == {"kernel": int(name == "kernel"), "scan": int(name == "scan")}
    assert _gap(outs["kernel"], outs["scan"]) < TOL
