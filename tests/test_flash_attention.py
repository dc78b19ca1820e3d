"""The fused causal flash kernel (``ops/pallas/flash_attention.py``) through
the Pallas interpreter: against the blockwise ``lax`` pass it stands for and
against full rows of scores, forward and the gradients to q, k and v; and the
one place that chooses between the two (``ops/sparse_attention.attention_path``):
what it takes, what it counts, what ``llm.fit`` says; and the plain
``Attention`` module, whose unpacked rows take that entry too (PR 38).

Small shapes: at most 4 heads and 1,024 tokens (the tile cut to 512 or 128 a side
where a case wants several).
"""

import numpy as np
import pytest


def _qkvw(s, h, kv, d, dv, dtype, b=1):
    import jax

    ks = jax.random.split(jax.random.PRNGKey(s + h + d), 4)
    shapes = ((b, s, h, d), (b, s, kv, d), (b, s, kv, dv), (b, s, h, dv))
    q, k, v, w = (jax.random.normal(key, shape).astype(dtype) for key, shape in zip(ks, shapes))
    return q, k, v, w.astype("float32")


def _forward_and_gradients(attend, q, k, v, w):
    import jax
    import jax.numpy as jnp

    loss = lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * w)
    return (attend(q, k, v),) + jax.jit(jax.grad(loss, (0, 1, 2)))(q, k, v)


def _gap(got, want) -> float:
    got, want = (np.asarray(t, np.float32).ravel() for t in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("s,block,h,kv,d,dv,dtype,tol", [
    (256, 1024, 1, 1, 192, 128, "float32", 2e-6),    # latent attention's widths, one head, one tile
    (1024, 512, 2, 2, 192, 128, "float32", 2e-6),    # several heads, 2 x 2 tiles of 512: one is skipped
    (384, 1024, 4, 4, 64, 64, "float32", 2e-6),      # equal widths, one tile of 384 (of 1,024 at most)
    (1024, 512, 4, 2, 64, 64, "float32", 2e-6),      # two query heads on a KV head
    (640, 128, 2, 2, 24, 16, "float32", 2e-6),       # 5 x 5 tiles of 128, widths of a few sublanes
    (1024, 512, 2, 2, 192, 128, "bfloat16", 2e-4),   # the cell's dtype, at the pass's own 512 chunks
    (1024, 1024, 2, 2, 192, 128, "bfloat16", 2e-3),  # ... and in one tile: other roundings of p
])
def test_kernel_is_the_blockwise_pass_and_plain_softmax(s, block, h, kv, d, dv, dtype, tol, monkeypatch):
    import jax.numpy as jnp
    from fedml_tpu.ops.pallas import flash_attention
    from fedml_tpu.ops.ring_attention import dense_attention
    from fedml_tpu.ops.sparse_attention import attention_path, block_sparse_attention

    monkeypatch.setattr(flash_attention, "BLOCK", block)
    q, k, v, w = _qkvw(s, h, kv, d, dv, dtype)
    scale = (d + 8) ** -0.5     # not the default: the scale is the caller's
    assert flash_attention.tiles(q, k, v)
    assert attention_path(q, k, v, None, None) == "blockwise"     # this is the CPU
    kernel = _forward_and_gradients(
        lambda q, k, v: flash_attention.causal_attention(q, k, v, scale=scale, interpret=True), q, k, v, w)
    blockwise = _forward_and_gradients(
        lambda q, k, v: block_sparse_attention(q, k, v, None, q_chunk=512, k_chunk=512, scale=scale),
        q, k, v, w)
    assert kernel[0].dtype == q.dtype and kernel[0].shape == (1, s, h, dv)
    for got, want in zip(kernel, blockwise):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert _gap(got, want) < tol
    if dtype == "float32":
        rep = lambda t: jnp.repeat(t, h // kv, axis=2)
        assert _gap(kernel[0], dense_attention(q, rep(k), rep(v), causal=True, scale=scale)) < 1e-5


def test_kernel_refuses_what_it_does_not_tile():
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.pallas import flash_attention

    q, k, v, _ = _qkvw(200, 2, 2, 64, 64, "float32")
    assert flash_attention.block_of(200) == 0 and not flash_attention.tiles(q, k, v)
    with pytest.raises(ValueError, match="does not tile"):
        flash_attention.causal_attention(q, k, v, scale=0.125, interpret=True)
    # a head's dq must fit the backward's VMEM: 65,536 tokens x 192 do not
    long = [jax.ShapeDtypeStruct((1, 65536, 1, w), jnp.bfloat16) for w in (192, 192, 128)]
    assert flash_attention.block_of(65536) == 1024 and not flash_attention.tiles(*long)


@pytest.fixture
def on_a_tpu(monkeypatch):
    """What the choice sees on a TPU backend, with the kernel it then takes
    run through the interpreter (this is still the CPU)."""
    import jax
    from fedml_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash_attention, "resolve_interpret", lambda interpret=None: True)


@pytest.mark.parametrize("case,path", [
    ("plain", "kernel"), ("keep", "blockwise"), ("mesh", "blockwise"), ("cpu", "blockwise"),
    ("ragged", "blockwise"), ("kv_groups", "kernel"),
])
def test_the_choice_of_path(case, path, request, eight_devices):
    """One function chooses, from ``keep``, the mesh, the backend and the
    shapes; the counter says which way each call site went, and either way
    gives the blockwise pass's result."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops import sparse_attention as spa
    from fedml_tpu.parallel import mesh as meshlib

    if case != "cpu":
        request.getfixturevalue("on_a_tpu")
    s = 200 if case == "ragged" else 256
    q, k, v, _ = _qkvw(s, 4, 2 if case == "kv_groups" else 4, 64, 32, "float32")
    keep = jnp.ones((1, 1, s, s // 64), bool) if case == "keep" else None
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=eight_devices[:2]) if case == "mesh" else None
    before = spa.attention_sites()
    assert spa.attention_path(q, k, v, keep, mesh) == path
    got = jax.jit(lambda q, k, v: spa.block_sparse_attention(
        q, k, v, keep, q_chunk=128, k_chunk=128, mesh=mesh))(q, k, v)
    after = spa.attention_sites()
    other = "blockwise" if path == "kernel" else "kernel"
    assert after[path] - before[path] == 2 and after[other] == before[other]
    want = spa._attend(q, k, v, jnp.ones((1, 1, s, 1), bool), s, s, s, 64 ** -0.5)
    assert _gap(got, want) < 2e-6


def _tiny(mixer: str):
    """Two blocks of latent attention ("mla") or of the plain grouped-query
    mixer ("plain") -> (cfg, the adapters' targets)."""
    import jax.numpy as jnp
    from fedml_tpu.llm import lora
    from fedml_tpu.models.transformer import TransformerConfig

    common = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq_len=128,
                  dtype=jnp.float32, logits_dtype=jnp.float32, remat=True, remat_policy="full")
    if mixer == "plain":
        return TransformerConfig(n_kv_heads=1, **common), lora.DEFAULT_TARGETS
    return TransformerConfig(
        n_kv_heads=2, mixer_types=("mla", "mla"), q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, **common), lora.MLA_TARGETS


def _fit(mixer, devices, steps=2):
    """``LLMTrainer.fit`` on the tiny model over a ``data`` mesh of these
    devices -> (history, the ``llm.fit`` span)."""
    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
    from fedml_tpu.obs import trace as obstrace
    from fedml_tpu.parallel import mesh as meshlib

    cfg, targets = _tiny(mixer)
    tr = LLMTrainer(cfg, LLMTrainArgs(batch_size=2, seq_len=128, total_steps=steps, lora_rank=2,
                                      lora_targets=targets),
                    mesh=meshlib.make_mesh((meshlib.AXIS_DATA,), devices=devices))
    rng = np.random.default_rng(0)
    rows = [rng.integers(0, 64, (2, 129)) for _ in range(steps)]
    history = tr.fit([(r[:, :-1], r[:, 1:]) for r in rows], steps=steps)
    return history, [s for s in obstrace.recent() if s.name == "llm.fit"][-1]


@pytest.mark.parametrize("mixer", ["mla", "plain"])
@pytest.mark.parametrize("where,kernel,blockwise", [("cpu", 0, 2), ("tpu", 2, 0), ("tpu_mesh", 0, 2)])
def test_fit_says_which_path_its_attention_sites_took(where, kernel, blockwise, mixer, request, eight_devices):
    """``attn_kernel_sites`` / ``attn_blockwise_sites`` on ``llm.fit``: the
    step program's own call sites (one a block, however often ``fit`` runs
    and whatever else was traced), and the same losses either way; latent
    attention's, and the plain mixer's on unpacked rows (PR 38)."""
    want, span = _fit(mixer, eight_devices[:1])
    assert (span.attrs["attn_kernel_sites"], span.attrs["attn_blockwise_sites"]) == (0, 2)
    if where == "cpu":
        return
    request.getfixturevalue("on_a_tpu")
    got, span = _fit(mixer, eight_devices[:2 if where == "tpu_mesh" else 1])
    assert (span.attrs["attn_kernel_sites"], span.attrs["attn_blockwise_sites"]) == (kernel, blockwise)
    for g, w in zip(got, want):
        assert abs(g["loss"] - w["loss"]) < 1e-4 * abs(w["loss"])


# -- the plain mixer on unpacked rows (PR 38) -------------------------------------------
def _plain_mixer(s, heads=4, kv=2, width=16):
    """The plain ``Attention`` at float32 with its input and positions:
    ``heads`` query over ``kv`` KV heads of ``width``, rows of ``s`` tokens."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models.transformer import Attention, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=heads * width, n_layers=1, n_heads=heads, n_kv_heads=kv,
                            d_ff=64, max_seq_len=s, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(s), (2, s, heads * width), jnp.float32)
    return Attention(cfg), x, jnp.broadcast_to(jnp.arange(s), (2, s))


def _scores_whole(q, k, v, keep=None, *, scale=None, **_):
    """What the module did before PR 38, in the blockwise entry's place: the
    KV heads repeated, then ``dense_attention``."""
    import jax.numpy as jnp
    from fedml_tpu.ops.ring_attention import dense_attention

    rep = q.shape[2] // k.shape[2]
    return dense_attention(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), causal=True, scale=scale)


def _output_and_gradients(mixer, params, x, pos):
    import jax
    import jax.numpy as jnp

    w = jax.random.normal(jax.random.PRNGKey(1), x.shape, jnp.float32)
    loss = lambda params, x: jnp.sum(mixer.apply(params, x, pos) * w)
    return mixer.apply(params, x, pos), jax.jit(jax.grad(loss, (0, 1)))(params, x)


def test_plain_mixer_on_unpacked_rows_takes_the_kernel(on_a_tpu, monkeypatch):
    """4 query over 2 KV heads, 2 x 2 tiles of 128: the module's one call takes
    the kernel with the KV heads as they are and gives what full rows of scores
    over repeated KV heads give: output, and the gradients in the input and in
    ``wq``, ``wk``, ``wv``, ``wo``."""
    import jax
    from fedml_tpu.ops import sparse_attention as spa
    from fedml_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(flash_attention, "BLOCK", 128)
    mixer, x, pos = _plain_mixer(256)
    params = jax.jit(mixer.init)(jax.random.PRNGKey(0), x, pos)
    before = spa.attention_sites()
    got = _output_and_gradients(mixer, params, x, pos)
    after = spa.attention_sites()
    assert after["kernel"] - before["kernel"] == 2 and after["blockwise"] == before["blockwise"]
    monkeypatch.setattr(spa, "block_sparse_attention", _scores_whole)
    want = _output_and_gradients(mixer, params, x, pos)
    assert spa.attention_sites() == after       # the reference is not the entry
    assert _gap(got[0], want[0]) < 2e-6 and _gap(got[1][1], want[1][1]) < 2e-6
    kernels = lambda grads: {name: g["kernel"] for name, g in grads[1][0]["params"].items()}
    assert sorted(kernels(got)) == ["wk", "wo", "wq", "wv"]
    for name, g in kernels(got).items():
        assert _gap(g, kernels(want)[name]) < 2e-6, name


@pytest.mark.parametrize("s,chunk", [(16, 16), (512, 512), (2048, 512), (32768, 512), (1000, 500), (2176, 272),
                                     (1021, 1021), (2038, 2038), (4099, None), (2053, None)])
def test_the_chunk_of_a_row_is_decided_from_its_length(s, chunk):
    """The largest divisor up to ``CHUNK``; a length with none of 64 tokens
    (a prime 1,021, or twice 1,019) is one whole chunk up to 2,048 tokens and
    refused by name beyond: never a token-by-token pass."""
    from fedml_tpu.ops.sparse_attention import row_chunk

    if chunk is not None:
        assert row_chunk(s) == chunk
        return
    with pytest.raises(ValueError, match=f"rows of {s} tokens"):
        row_chunk(s)


def test_plain_mixer_on_a_row_of_awkward_length(monkeypatch):
    """1,021 tokens (a prime): ``dense_attention`` took any length, and the
    module still does up to 2,048 tokens, in one chunk and so one pair, with
    the same output; 4,099 tokens it refuses, naming the length."""
    import re

    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops import sparse_attention as spa

    mixer, x, pos = _plain_mixer(1021, heads=2, kv=1, width=8)
    params = jax.jit(mixer.init)(jax.random.PRNGKey(0), x, pos)
    text = str(jax.make_jaxpr(mixer.apply)(params, x, pos))
    assert re.findall(r"length=(\d+)", text) == ["1", "1"] and "1021,1021]" in text    # one pair of chunks
    got = jax.jit(mixer.apply)(params, x, pos)
    with monkeypatch.context() as patched:
        patched.setattr(spa, "block_sparse_attention", _scores_whole)
        assert _gap(got, mixer.apply(params, x, pos)) < 2e-6
    long = (jax.ShapeDtypeStruct((1, 4099, 16), jnp.float32), jax.ShapeDtypeStruct((1, 4099), jnp.int32))
    with pytest.raises(ValueError, match="rows of 4099 tokens"):
        jax.eval_shape(mixer.apply, params, *long)
