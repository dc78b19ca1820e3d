"""granite-4.0-h-micro through the normal path: the chunked selective scan
against its recurrence, the Mamba-2 mixer, the NoPE grouped-query mixer and
the whole model against the plain reference (``benchmark/ref_granite.py``,
which runs every document ALONE), packed and unpacked; document packing, the
loss mask and its counters; adapters on the Mamba projections in
``LLMTrainer``; the sharding rules; the federated adapter round.

Tiny sizes (the configuration's ``rehearsal``: hidden 64, 4 query over 2 KV
heads of 16, 8 Mamba heads of 16 with a 16-wide state, layers mamba |
attention | mamba, rows of 64 tokens packed from documents of 23, 17, 13 and
11), with the scan's chunk (8) and the attention's (16) smaller than the
documents, so that states are carried and boundaries fall inside chunks.
"""

import contextlib
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "granite_4_0_h_micro_d10.lora_32k_packed"
JOB = {"lora_rank": 4, "lora_alpha": 8.0, "lora_targets": r".*attn/((in|out)_proj|w[qkvo])/kernel"}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (it keeps them importable by bare name) and
    the cell's files at their rehearsal sizes."""
    sys.path.insert(0, BENCH)
    try:
        import compare
        import flops_granite
        import granite
        import ref_granite
        from run import load_json

        config = load_json(BENCH, "configs", "granite_4_0_h_micro_d10.json")
        traffic = load_json(BENCH, "traffic", "lora_sft_32k_packed_b1.json")
        limits = load_json(BENCH, "limits", CELL + ".json")
        yield {"compare": compare, "flops": flops_granite, "ref": ref_granite, "granite": granite,
               "config": {**config, **config["rehearsal"]}, "full_config": config,
               "traffic": {**traffic, **traffic["rehearsal"]}, "full_traffic": traffic,
               "limits": limits}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(autouse=True)
def small_attention_chunks(monkeypatch):
    """64 tokens in four chunks of the blockwise pass."""
    from fedml_tpu.ops import sparse_attention

    monkeypatch.setattr(sparse_attention, "CHUNK", 16)


def _cfg(bench, seq=64, config=None, **kw):
    """The tiny model in float32, so that it differs from the reference by
    the order of its sums alone."""
    import jax.numpy as jnp

    cfg = bench["granite"].transformer_config(config or bench["config"], seq, "full",
                                              **{**bench["traffic"]["program"], **kw})
    return dataclasses.replace(cfg, dtype=jnp.float32, logits_dtype=jnp.float32)


def _weights(bench, seed=5, config=None):
    """The reference's float32 draw of the base, flat and as the program's tree."""
    import jax.numpy as jnp
    from flax import traverse_util

    w = bench["ref"].init_weights(config or bench["config"], seed, dtype=jnp.float32)
    return w, traverse_util.unflatten_dict(w, sep="/")


def _row(bench, seed=3, step=0):
    """One packed row of the rehearsal traffic: its documents, and the
    program's three arrays."""
    from fedml_tpu.llm.packing import pack

    docs = bench["ref"].batch_documents(seed, step, 1, bench["traffic"]["doc_lengths"],
                                        bench["config"]["vocab_size"])[0]
    return docs, pack(docs, 64)


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(got, want, atol=tol * max(1.0, float(np.abs(np.asarray(want)).max())))


SEGMENTS = np.array([[1] * 13 + [2] * 20 + [3] * 17, [1] * 7 + [2] * 8 + [3] * 30 + [0] * 5], np.int32)


# -- the scan -------------------------------------------------------------------------
def _scan_inputs():
    import jax
    import jax.numpy as jnp

    b, s, h, p, g, n = 2, 50, 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(ks[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0), -jnp.arange(1.0, h + 1),
            jax.random.normal(ks[2], (b, s, g, n)), jax.random.normal(ks[3], (b, s, g, n)),
            jnp.linspace(0.5, 1.5, h))


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_scan_is_the_recurrence(chunk, packed):
    """``ops/ssd.ssd`` (chunks of 8 and 16 inside documents of 7 to 30 tokens,
    a tail that does not fill its chunk, one chunk for all) against the
    token-by-token recurrence with its resets: output and the gradients to x,
    dt, B and C."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.ssd import ssd, ssd_recurrent

    x, dt, a, b_in, c_in, d_skip = _scan_inputs()
    seg = jnp.asarray(SEGMENTS) if packed else None

    def loss(fn, *more):
        return lambda x, dt, b_in, c_in: jnp.sum(jnp.sin(fn(x, dt, a, b_in, c_in, d_skip, seg, *more)))

    _close(ssd(x, dt, a, b_in, c_in, d_skip, seg, chunk), ssd_recurrent(x, dt, a, b_in, c_in, d_skip, seg))
    got = jax.grad(loss(ssd, chunk), argnums=(0, 1, 2, 3))(x, dt, b_in, c_in)
    want = jax.grad(loss(ssd_recurrent), argnums=(0, 1, 2, 3))(x, dt, b_in, c_in)
    for g, w in zip(got, want):
        _close(g, w)


def test_scan_resets_where_the_id_changes_even_to_an_id_seen_before():
    """Ids 1, 2, 1: the third document is not the first's continuation."""
    import jax.numpy as jnp
    from fedml_tpu.ops.segments import document_index
    from fedml_tpu.ops.ssd import ssd, ssd_recurrent

    x, dt, a, b_in, c_in, d_skip = (t[:1] if t.ndim > 1 else t for t in _scan_inputs())
    seg = jnp.asarray([[1] * 20 + [2] * 10 + [1] * 20])
    assert document_index(seg)[0].tolist() == [0] * 20 + [1] * 10 + [2] * 20
    got = ssd(x, dt, a, b_in, c_in, d_skip, seg, 8)
    _close(got, ssd_recurrent(x, dt, a, b_in, c_in, d_skip, seg))
    alone = ssd(x[:, 30:], dt[:, 30:], a, b_in[:, 30:], c_in[:, 30:], d_skip, None, 8)
    _close(got[:, 30:], alone)


def test_a_fast_head_underflows_to_zero_and_not_to_inf():
    """Decays of exp(-40) a token: differences of the running sum, never
    quotients of decays."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.ssd import ssd

    x, dt, a, b_in, c_in, d_skip = _scan_inputs()
    y, g = jax.value_and_grad(lambda dt: jnp.sum(ssd(x, dt, a * 40.0, b_in, c_in, d_skip,
                                                     jnp.asarray(SEGMENTS), 16)))(dt + 1.0)
    assert np.isfinite(float(y)) and bool(jnp.all(jnp.isfinite(g)))


def test_convolution_stops_at_a_documents_start(bench):
    """``causal_conv`` on a packed row is the reference's four shifted
    products on each document alone."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models.transformer import causal_conv

    k = jax.random.split(jax.random.PRNGKey(1), 3)
    x, kernel, bias = jax.random.normal(k[0], (2, 50, 6)), jax.random.normal(k[1], (4, 6)), jax.random.normal(k[2], (6,))
    got = causal_conv(x, kernel, bias, jnp.asarray(SEGMENTS))
    for row in range(2):
        for doc in np.unique(SEGMENTS[row]):
            at = np.flatnonzero(SEGMENTS[row] == doc)
            _close(got[row, at], bench["ref"].conv_taps(x[row, at], kernel, bias))
    _close(causal_conv(x, kernel, bias)[0], bench["ref"].conv_taps(x[0], kernel, bias))


# -- attention inside documents ---------------------------------------------------------
def _masked_softmax(q, k, v, doc, scale):
    import jax
    import jax.numpy as jnp

    s, h, kv = q.shape[1], q.shape[2], k.shape[2]
    k, v = (jnp.repeat(t.astype(jnp.float32), h // kv, axis=2) for t in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
    if doc is not None:
        mask = mask & (doc[:, None, :, None] == doc[:, None, None, :])
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(mask, logits, -1e30), -1), v)


def _qkv(s, h, kv, d, dtype):
    import jax

    key = jax.random.PRNGKey(2)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (2, s, n, d), dtype)
                 for i, n in enumerate((h, kv, kv)))


def test_blockwise_pass_attends_inside_documents():
    """The ``lax`` pass with ``segments`` (chunks of 16 against documents of
    7 to 30 tokens) against a plain masked softmax: output and three
    gradients; without them it is plain causal attention still."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.sparse_attention import block_sparse_attention
    from fedml_tpu.ops.segments import document_index

    q, k, v = _qkv(48, 4, 2, 8, jnp.float32)
    seg = jnp.asarray(SEGMENTS[:, :48])
    for segments, doc in ((seg, document_index(seg)), (None, None)):
        fn = lambda q, k, v: block_sparse_attention(q, k, v, None, q_chunk=16, k_chunk=16, scale=0.3,
                                                    segments=segments)
        _close(fn(q, k, v), _masked_softmax(q, k, v, doc, 0.3))
        got = jax.grad(lambda *t: jnp.sum(jnp.sin(fn(*t))), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *t: jnp.sum(jnp.sin(_masked_softmax(*t, doc, 0.3))), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            _close(g, w)


def test_flash_kernel_attends_inside_documents(monkeypatch):
    """The fused kernel with a document index (interpreted here; tiles of 128:
    document starts inside tiles, and tiles no pair of which shares a
    document, which are not run) against the masked softmax, forward and
    backward, within bfloat16's rounding."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK", 128)
    q, k, v = _qkv(384, 2, 1, 16, jnp.bfloat16)
    doc = jnp.asarray([[0] * 100 + [1] * 30 + [2] * 254, [0] * 300 + [1] * 84], jnp.int32)
    fn = lambda q, k, v: fa.causal_attention(q, k, v, scale=0.25, segments=doc, interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(fn(q, k, v), _masked_softmax(q, k, v, doc, 0.25), atol=2e-2)
    got = jax.grad(lambda *t: jnp.sum(jnp.sin(fn(*t))), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *t: jnp.sum(jnp.sin(_masked_softmax(*t, doc, 0.25))), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        w = np.asarray(w.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)), w, atol=2e-2 * np.abs(w).max())


# -- the mixers and the model against the reference -------------------------------------
@pytest.mark.parametrize("kind,layer", [("mamba", 0), ("attention", 1)])
def test_mixer_is_the_reference(kind, layer, bench):
    """``Mamba`` (in_proj split five ways, the convolution, the scan in chunks
    of 8 with its ``D`` skip, the gated norm, out_proj) and the plain
    ``Attention`` (no RoPE, scores x 1/16, grouped heads) on one document of
    64 tokens against the reference's recurrence and full rows of scores:
    output and the gradient to the input."""
    import jax
    from fedml_tpu.models import transformer as tfm

    cfg = _cfg(bench)
    w, tree = _weights(bench)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    probe = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    pos = np.arange(64)[None]
    m = bench["ref"].parts(w, {}, bench["config"], JOB)
    module = tfm.MIXERS[kind](cfg)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(lambda x: (module.apply(
            {"params": tree[f"layer_{layer}"]["attn"]}, x[None], pos)[0] * probe).sum()))(x)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda x: (m[kind](x, f"layer_{layer}/attn/") * probe).sum()))(x)
    _close(got, want)
    _close(got_g, want_g)


def _program_loss(cfg, job, plant=None):
    """``(lora, base, tokens, targets, segments or None) -> mean loss over the
    counted positions`` through ``Transformer`` and the adapters' activation
    side, as ``LLMTrainer`` computes it."""
    import jax.numpy as jnp
    from fedml_tpu.llm import lora as lora_lib
    from fedml_tpu.models.transformer import Transformer, targets_in_document

    model = Transformer(cfg)

    def loss(lora, base, tokens, targets, segments):
        variables = {"params": base, "lora": lora_lib.as_collection(lora, job["lora_alpha"], job["lora_rank"])}
        if segments is None:
            return model.apply(variables, tokens, targets=targets).mean()
        out = model.apply(variables, tokens, targets=targets, segments=segments)
        return out.sum() / jnp.sum(targets_in_document(segments))

    return loss


def _reference_loss_and_grads(bench, w, lora, docs, config=None):
    import jax

    ref, c = bench["ref"], config or bench["config"]
    alone, n = ref.documents_alone([docs])
    grad = jax.jit(jax.value_and_grad(lambda lora, t, y, m: ref.doc_loss_sum(w, lora, t, y, m, c, JOB)))
    loss, acc = 0.0, None
    with jax.default_matmul_precision("highest"):
        for tokens, targets, counted in alone:
            l, g = grad(lora, tokens, targets, counted)
            loss, acc = loss + float(l), g if acc is None else jax.tree_util.tree_map(lambda a, b: a + b, acc, g)
    return loss / n, {k: np.asarray(v) / n for k, v in acc.items()}


@pytest.fixture(scope="module")
def against_reference(bench):
    """One packed row: the float32 program's loss and per-leaf adapter
    gradients, and the reference's (its documents alone)."""
    import jax
    from fedml_tpu.ops import sparse_attention

    w, tree = _weights(bench)
    lora = bench["ref"].init_adapters(bench["config"], JOB, 5)
    docs, (tokens, targets, segments) = _row(bench)
    want = _reference_loss_and_grads(bench, w, lora, docs)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sparse_attention, "CHUNK", 16)
        with jax.default_matmul_precision("highest"):
            got = jax.jit(jax.value_and_grad(_program_loss(_cfg(bench), JOB)))(
                bench["granite"].sala.program_adapters(lora), tree, tokens, targets, segments)
    return {"w": w, "tree": tree, "lora": lora, "docs": docs, "row": (tokens, targets, segments),
            "want": want, "got": got}


def _flat_grads(tree) -> dict:
    return {f"{k}/{ab}": np.asarray(v) for k, d in tree.items() for ab, v in d.items()}


def test_packed_model_is_the_reference_on_its_documents_alone(against_reference):
    """Loss and every adapter leaf's gradient of one packed row (four
    documents, masks and resets in the program) against the reference, which
    runs each document alone from a zero state and knows no mask."""
    (loss, grads), (want_loss, want) = against_reference["got"], against_reference["want"]
    assert abs(float(loss) - want_loss) < 2e-6 * want_loss
    got = _flat_grads(grads)
    assert sorted(got) == sorted(want) and len(want) == 2 * (2 * 2 + 4)
    for name in want:
        _close(got[name], want[name], tol=2e-5)


def test_unpacked_model_is_the_reference(bench, against_reference):
    """A row that is one document, through the two-array path."""
    import jax

    r = against_reference
    tokens = np.concatenate(r["docs"])[None]
    want_loss, want = _reference_loss_and_grads(bench, r["w"], r["lora"], [tokens[0]])
    # the reference leaves a document's last position out; the two-array path wraps it: compare on 63
    targets = np.roll(tokens, -1, axis=1)
    segments = np.ones_like(tokens)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(_program_loss(_cfg(bench), JOB)))(
            bench["granite"].sala.program_adapters(r["lora"]), r["tree"], tokens, targets, segments)
        plain = jax.jit(lambda *t: _program_loss(_cfg(bench), JOB)(*t, None))(
            bench["granite"].sala.program_adapters(r["lora"]), r["tree"], tokens, targets)
    assert abs(float(loss) - want_loss) < 2e-6 * want_loss
    for name, g in _flat_grads(grads).items():
        _close(g, want[name], tol=2e-5)
    # one document with and without ids: the same 63 losses, and the 64th (the wrapped target) beside them
    assert abs(float(plain) * 64 - float(loss) * 63) < 12.0


def test_a_packed_row_is_its_documents_run_alone(bench, against_reference):
    """Through the PROGRAM alone: the logits of a packed row are those of
    each document given a row of its own."""
    import jax
    from fedml_tpu.models.transformer import Transformer

    r = against_reference
    model, (tokens, _, segments) = Transformer(_cfg(bench, loss_chunk=0)), r["row"]
    with jax.default_matmul_precision("highest"):
        packed = jax.jit(lambda t, s: model.apply({"params": r["tree"]}, t, segments=s))(tokens, segments)
        at = 0
        for doc in r["docs"]:
            alone = model.apply({"params": r["tree"]}, doc[None])
            _close(packed[0, at: at + len(doc)], alone[0], tol=2e-5)
            at += len(doc)


@pytest.mark.parametrize("carried", ["state", "convolution", "attention"])
def test_a_reset_left_out_is_seen(carried, bench, against_reference, monkeypatch):
    """Each of the three resets alone: with the scan's, the convolution's or
    the attention's ``segments`` taken away (the other two kept), the loss or
    a leaf's gradient leaves the reference by more than the cell's rehearsal
    limits allow; with all three in place it is inside them."""
    import jax
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops import sparse_attention, ssd as ssd_mod

    r, compare, limits = against_reference, bench["compare"], bench["limits"]["rehearsal"]
    want_loss, want = r["want"]
    norms = lambda g: {k: float(np.linalg.norm(v)) for k, v in g.items()}

    def gaps(loss, grads):
        return abs(float(loss) - want_loss) / want_loss, compare.worst_leaf_gap(norms(_flat_grads(grads)), norms(want))[0]

    sound = gaps(*r["got"])
    assert sound[0] < limits["loss_gap"] and sound[1] < limits["grad_gap"], sound
    if carried == "state":
        real = ssd_mod.ssd
        monkeypatch.setattr(ssd_mod, "ssd", lambda *a, **kw: real(*a[:6], None, *a[7:], **kw))
    elif carried == "convolution":
        real = tfm.causal_conv
        monkeypatch.setattr(tfm, "causal_conv", lambda x, k, b, segments=None: real(x, k, b, None))
    else:
        real = sparse_attention.block_sparse_attention
        monkeypatch.setattr(sparse_attention, "block_sparse_attention",
                            lambda *a, segments=None, **kw: real(*a, **kw))
    with jax.default_matmul_precision("highest"):
        broken = gaps(*jax.jit(jax.value_and_grad(_program_loss(_cfg(bench), JOB)))(
            bench["granite"].sala.program_adapters(r["lora"]), r["tree"], *r["row"]))
    assert broken[0] > limits["loss_gap"] or broken[1] > limits["grad_gap"], (carried, broken)


@pytest.mark.parametrize("name,value", [("embedding_multiplier", 5), ("residual_multiplier", 0.5),
                                        ("logits_scaling", 4), ("attention_multiplier", 0.3)])
def test_each_multiplier_is_the_models(name, value, bench, against_reference):
    """The four Granite multipliers map onto ``scale_emb``, ``scale_depth``,
    ``dim_model_base`` and ``attn_scale``: with one of them changed, program
    and reference still agree, at another loss."""
    import jax

    r = against_reference
    config = {**bench["config"], name: value}
    want_loss, _ = _reference_loss_and_grads(bench, r["w"], r["lora"], r["docs"], config)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(_program_loss(_cfg(bench, config=config), JOB))(
            bench["granite"].sala.program_adapters(r["lora"]), r["tree"], *r["row"])
    assert abs(float(loss) - want_loss) < 2e-6 * want_loss
    assert abs(want_loss - r["want"][0]) > 1e-4 * want_loss


def test_the_head_is_the_embedding(bench):
    import jax
    from fedml_tpu.models.transformer import Transformer

    cfg = _cfg(bench, loss_chunk=0)
    tokens = np.arange(64, dtype=np.int32)[None] % 256
    params = jax.jit(lambda: Transformer(cfg).init({"params": jax.random.PRNGKey(0)}, tokens))()["params"]
    assert "lm_head" not in params and params["embed"]["embedding"].shape == (256, 64)
    assert sorted(params["layer_0"]["attn"]) == ["A_log", "D", "conv_bias", "conv_kernel", "dt_bias",
                                                 "in_proj", "norm", "out_proj"]
    assert params["layer_0"]["attn"]["in_proj"]["kernel"].shape == (64, 2 * 128 + 2 * 16 + 8)
    np.testing.assert_allclose(np.exp(params["layer_0"]["attn"]["A_log"]), np.arange(1, 9), rtol=1e-6)
    untied = dataclasses.replace(cfg, tie_embeddings=False)
    assert "lm_head" in jax.eval_shape(lambda: Transformer(untied).init(
        {"params": jax.random.PRNGKey(0)}, tokens))["params"]
    # logits move with the embedding alone
    logits = Transformer(cfg).apply({"params": params}, tokens)
    bumped = jax.tree_util.tree_map(lambda x: x, params)
    bumped["embed"] = {"embedding": params["embed"]["embedding"].at[7].mul(2.0)}
    moved = Transformer(cfg).apply({"params": bumped}, tokens)
    assert float(np.abs(moved[..., 7] - logits[..., 7]).max()) > 0


# -- packing ---------------------------------------------------------------------------
def test_pack_rows_ids_targets_and_counts():
    """Concat-and-chunk: a document that crosses a row's end goes on as the
    next row's first, ids count a row's documents from 1, padding is 0 and
    only in the last row, and the loss counts every position whose target is
    in its own document."""
    import jax.numpy as jnp
    from fedml_tpu.llm.packing import pack
    from fedml_tpu.llm.train import packed_stats
    from fedml_tpu.models.transformer import targets_in_document

    docs = [np.arange(1, 6), np.arange(10, 17), np.arange(20, 23), np.arange(30, 34)]   # 5, 7, 3, 4 tokens
    tokens, targets, segments = pack(docs, 8)
    assert tokens.shape == (3, 8) and tokens.dtype == targets.dtype == segments.dtype == np.int32
    assert tokens.tolist() == [[1, 2, 3, 4, 5, 10, 11, 12], [13, 14, 15, 16, 20, 21, 22, 30], [31, 32, 33, 0, 0, 0, 0, 0]]
    assert segments.tolist() == [[1, 1, 1, 1, 1, 2, 2, 2], [1, 1, 1, 1, 2, 2, 2, 3], [1, 1, 1, 0, 0, 0, 0, 0]]
    assert targets[0].tolist() == [2, 3, 4, 5, 10, 11, 12, 13] and targets[2].tolist() == [32, 33, 0, 0, 0, 0, 0, 0]
    counted = np.asarray(targets_in_document(jnp.asarray(segments)))
    assert counted.tolist() == [[1, 1, 1, 1, 0, 1, 1, 0], [1, 1, 1, 0, 1, 1, 0, 0], [1, 1, 0, 0, 0, 0, 0, 0]]
    # what the step program sums on the device from the ids alone
    assert {k: float(v) for k, v in packed_stats(jnp.asarray(segments)).items()} == {
        "docs": 6.0, "loss_tokens": 13.0, "doc_pairs": 15.0 + 6 + 10 + 6 + 1 + 6, "causal_pairs": 3 * 36.0}
    with pytest.raises(ValueError):
        pack([], 8)


def test_the_cells_rows_count_as_the_issue_says(bench):
    import jax.numpy as jnp
    from fedml_tpu.llm.packing import pack
    from fedml_tpu.llm.train import packed_stats

    t = bench["full_traffic"]
    docs = bench["ref"].batch_documents(2147483659, 4, 1, t["doc_lengths"], 100352)[0]
    assert sorted(len(d) for d in docs) == sorted(t["doc_lengths"]) != [len(d) for d in docs]
    tokens, _, segments = pack(docs, t["seq_len"])
    assert tokens.shape == (1, 32768) and segments.min() == 1 and segments.max() == 16
    stats = {k: float(v) for k, v in packed_stats(jnp.asarray(segments)).items()}
    assert (stats["docs"], stats["loss_tokens"]) == (16.0, 32752.0)
    assert abs(stats["doc_pairs"] - 74184268) <= 8            # a row's sum is exact, the batch's float32
    assert abs(stats["doc_pairs"] / stats["causal_pairs"] - 0.1382) < 1e-4


# -- the trainer ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def first_steps(bench):
    """The cell's driver at rehearsal sizes, in process: ``fit``'s first three
    packed steps and the float32 reference's, with the float8 control."""
    import jax
    from fedml_tpu.ops import sparse_attention

    cell = {"name": CELL, "chips": 1}
    driver = bench["granite"].Driver(cell, bench["config"], bench["traffic"], 11, jax.devices()[:1])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sparse_attention, "CHUNK", 16)
        driver.build()
        base = jax.tree_util.tree_map(np.asarray, driver.trainer.params)
        driver.first_steps()
    after = jax.tree_util.tree_map(np.asarray, driver.trainer.params)
    return {"driver": driver, "base": base, "after": after, "reference": driver.reference(),
            "control": driver.reference(control="fp8"), "no_reset": driver.reference(fault="no_reset")}


def test_model_follows_the_reference_and_the_control_does_not(first_steps, bench):
    """Loss of three packed steps, the first gradient's norm per adapter leaf
    and the adapters' change through ``LLMTrainer.fit`` in bfloat16, against
    the float32 reference under the cell's rehearsal limits, which must refuse
    the float8 control and the reference run without resets."""
    compare, limits = bench["compare"], bench["limits"]["rehearsal"]
    d = first_steps["driver"]
    ok, compared = compare.judge(d.gaps(d.readings, first_steps["reference"]), limits)
    assert ok, compared
    for broken in ("control", "no_reset"):
        ok, compared = compare.judge(d.gaps(first_steps[broken], first_steps["reference"]), limits)
        assert not ok, (broken, compared)
    assert d.readings["packed"] == {"docs": 4.0, "loss_tokens": 60.0, "doc_pairs": 586.0,
                                    "causal_pairs": 64 * 65 / 2}
    assert first_steps["reference"]["loss_tokens"] == 60
    assert d.readings["attention_sites"] == {"kernel": 0, "blockwise": 1}     # one softmax layer; no TPU here


def test_adapter_mode_leaves_the_base_bit_equal(first_steps):
    import jax

    d = first_steps["driver"]
    before, after = (jax.tree_util.tree_leaves(first_steps[k]) for k in ("base", "after"))
    assert len(before) == len(after) > 0
    for a, b in zip(before, after):
        assert a.dtype == b.dtype and a.dtype.name == "bfloat16"
        np.testing.assert_array_equal(a, b)
    assert sorted(d.trainer.lora) == sorted(
        [f"layer_{i}/attn/{n}/kernel" for i in (0, 2) for n in ("in_proj", "out_proj")]
        + [f"layer_1/attn/{n}/kernel" for n in ("wq", "wk", "wv", "wo")])
    assert all(v > 0 for v in d.readings["change_norms"].values())


def test_packed_spans_attributes_and_counters(first_steps):
    from fedml_tpu.obs import trace as obstrace

    steps = [s for s in obstrace.recent() if s.name == "llm.step" and "docs" in s.attrs]
    assert len(steps) >= 4
    for s in steps:
        assert (s.attrs["docs"], s.attrs["loss_tokens"], s.attrs["doc_pairs"], s.attrs["tokens"]) == (4, 60, 586, 64)
        assert s.attrs["causal_pairs"] == 64 * 65 / 2
    assert obstrace.LLM_PACKED_DOCUMENTS.value() >= 4 * len(steps)
    counted, masked = (obstrace.LLM_LOSS_TOKENS.value(kind=k) for k in ("counted", "masked"))
    assert counted >= 60 * len(steps) and masked * 15 == counted
    kept, causal = (obstrace.LLM_ATTENDED_KEYS.value(kind=k) for k in ("kept", "causal"))
    assert kept >= 586 * 2 * len(steps) and causal > kept          # 2 KV heads x 1 softmax layer


def test_two_array_batches_run_the_program_they_ran(first_steps, bench):
    """The same trainer takes an unpacked batch through its other program."""
    d = first_steps["driver"]
    tokens = np.concatenate(_row(bench)[0])[None]
    h = d.trainer.fit(iter([(tokens, np.roll(tokens, -1, axis=1))]), steps=1)
    assert np.isfinite(h[0]["loss"]) and "docs" not in h[0]


def test_adapters_on_the_mamba_projections(bench):
    """``MAMBA_TARGETS`` names the two projections of every Mamba mixer; each
    factor pair has the kernel's fan-in: ``in_proj`` 64 -> 296, ``out_proj``
    128 -> 64 here (2048 -> 8512 and 4096 -> 2048 published)."""
    import jax
    from fedml_tpu.llm import lora as lora_lib
    from fedml_tpu.models.transformer import Transformer

    cfg = _cfg(bench)
    params = jax.eval_shape(lambda: Transformer(cfg).init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 64), np.int32)))["params"]
    lora = jax.eval_shape(lambda: lora_lib.init_lora(params, 4, jax.random.PRNGKey(1), lora_lib.MAMBA_TARGETS))
    shapes = {k: (v["a"].shape, v["b"].shape) for k, v in lora.items()}
    assert shapes == {f"layer_{i}/attn/{n}/kernel": s for i in (0, 2) for n, s in
                      (("in_proj", ((64, 4), (4, 296))), ("out_proj", ((128, 4), (4, 64))))}
    # the cell's job names those and the softmax layer's four: the two named sets together
    job = bench["full_traffic"]["train_args"]["lora_targets"]
    for targets in (job, f"{lora_lib.MAMBA_TARGETS}|{lora_lib.DEFAULT_TARGETS}"):
        both = jax.eval_shape(lambda: lora_lib.init_lora(params, 4, jax.random.PRNGKey(1), targets))
        assert sorted(both) == sorted(list(lora) + [f"layer_1/attn/w{n}/kernel" for n in "qkvo"])
    a = lora_lib.init_lora(jax.tree_util.tree_map(lambda s: np.zeros(s.shape, np.float32), params), 4,
                           jax.random.PRNGKey(1), lora_lib.MAMBA_TARGETS)["layer_0/attn/out_proj/kernel"]["a"]
    assert abs(float(np.std(a)) * 128 ** 0.5 - 1.0) < 0.15       # normal / sqrt(fan_in 128)


def test_new_leaves_have_sharding_rules(bench):
    """Every leaf the configuration adds is named by a rule of its own."""
    import re

    import jax
    from jax.sharding import PartitionSpec as P
    from fedml_tpu.models.transformer import Transformer
    from fedml_tpu.parallel.sharding import TRANSFORMER_RULES, partition_specs

    params = jax.eval_shape(lambda: Transformer(_cfg(bench)).init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 64), np.int32)))["params"]
    flat = bench["compare"].flat(params)
    missing = [p for p in flat if not any(re.fullmatch(pattern, p) for pattern, _ in TRANSFORMER_RULES)]
    assert not missing, missing
    specs = bench["compare"].flat(jax.tree_util.tree_map(
        lambda s: s, partition_specs(params), is_leaf=lambda x: isinstance(x, P)))
    assert specs["layer_0/attn/in_proj/kernel"] == P("data", "model")
    assert specs["layer_0/attn/out_proj/kernel"] == P("model", "data")
    for leaf in ("conv_kernel", "conv_bias", "A_log", "D", "dt_bias", "norm/scale"):
        assert all(e is None for e in specs[f"layer_0/attn/{leaf}"]), leaf


def test_mamba_refuses_a_seq_axis(bench, eight_devices):
    import jax
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.parallel import mesh as meshlib

    mesh = jax.sharding.Mesh(np.asarray(eight_devices).reshape(4, 2), (meshlib.AXIS_DATA, meshlib.AXIS_SEQ))
    x = np.zeros((1, 64, 64), np.float32)
    with pytest.raises(NotImplementedError, match="Mamba has no sequence-sharded form"):
        tfm.Mamba(_cfg(bench), mesh, meshlib.AXIS_SEQ).init(jax.random.PRNGKey(0), x, np.arange(64)[None])
    with pytest.raises(NotImplementedError, match="packed documents"):
        tfm.LightningAttention(_cfg(bench)).init(jax.random.PRNGKey(0), x, np.arange(64)[None],
                                                 np.ones((1, 64), np.int32))


@pytest.mark.parametrize("rows", ["long_on_a_seq_axis", "on_a_data_axis", "packed_on_a_seq_axis"])
def test_unpacked_rows_take_the_path_they_took(rows, eight_devices, monkeypatch):
    """The plain mixer chooses by the ``seq`` axis alone: unpacked rows on one
    go round the ring however large their scores (32 heads x 8,192 tokens are
    8.6 GB in float32: what the ring is for), with a key per query head;
    without one they go through the blockwise entry as packed rows do (PR 38:
    they built their scores whole), with the module's mesh passed on, every
    block kept and K, V at their own ``n_kv_heads``; packed rows refuse a
    ``seq`` axis.  Shapes only (``eval_shape``): nothing of that size is
    computed."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops import ring_attention as ring, sparse_attention as spa
    from fedml_tpu.parallel import mesh as meshlib

    taken = []

    def took(name):
        def attend(q, k, v, *args, **kw):
            taken.append((name, k.shape[2], v.shape[2], args, kw))
            return q
        return attend

    monkeypatch.setattr(ring, "ring_attention", took("ring_attention"))
    monkeypatch.setattr(spa, "block_sparse_attention", took("block_sparse_attention"))
    seq = rows != "on_a_data_axis"
    mesh = jax.sharding.Mesh(np.asarray(eight_devices), (meshlib.AXIS_SEQ if seq else meshlib.AXIS_DATA,))
    b, s = (1, 8192) if seq else (16, 2048)
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=256, n_layers=1, n_heads=32, n_kv_heads=8,
                                d_ff=64, max_seq_len=s)
    x = jax.ShapeDtypeStruct((b, s, 256), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((b, s), jnp.int32)
    mixer = tfm.Attention(cfg, mesh, meshlib.AXIS_SEQ if seq else None)
    if rows == "packed_on_a_seq_axis":
        with pytest.raises(NotImplementedError, match="Attention has no sequence-sharded form"):
            jax.eval_shape(lambda x, p, seg: mixer.init(jax.random.PRNGKey(0), x, p, seg), x, pos, pos)
        assert not taken
        return
    jax.eval_shape(lambda x, p: mixer.init(jax.random.PRNGKey(0), x, p), x, pos)
    (name, k_heads, v_heads, args, kw), = taken
    if seq:
        assert (name, k_heads, v_heads) == ("ring_attention", 32, 32)
    else:
        assert (name, k_heads, v_heads, args) == ("block_sparse_attention", 8, 8, (None,))
        assert kw["mesh"] is mesh and kw["segments"] is None and kw["q_chunk"] == kw["k_chunk"] == spa.CHUNK


def test_fedllm_round_on_the_model(bench, eight_devices):
    """``FedLLMSimulator`` builds the same ``Transformer`` (Mamba-2 layers, the
    NoPE mixer, a tied head) on unpacked rows and runs it unchanged: one round
    moves the adapters and nothing else."""
    import jax
    import fedml_tpu
    from fedml_tpu.arguments import Config
    from fedml_tpu.data import loader
    from fedml_tpu.llm import lora as lora_lib
    from fedml_tpu.llm.fedllm import FedLLMSimulator

    fcfg = Config(dataset="shakespeare", model="rnn", client_num_in_total=2, client_num_per_round=2,
                  comm_round=1, epochs=1, batch_size=4, learning_rate=5e-3, synthetic_train_size=16,
                  synthetic_test_size=8, partition_method="homo", frequency_of_the_test=0,
                  extra={"lora_r": 2, "lora_targets": bench["full_traffic"]["train_args"]["lora_targets"]})
    fedml_tpu.init(fcfg)
    ds = loader.load(fcfg)
    tcfg = bench["granite"].transformer_config(bench["config"], ds.train_x.shape[1], "full",
                                               vocab_size=ds.class_num)
    sim = FedLLMSimulator(fcfg, ds, tcfg)
    assert "layer_0/attn/in_proj/kernel" in sim.global_lora and "layer_1/attn/wo/kernel" in sim.global_lora
    base = jax.tree_util.tree_map(np.asarray, sim.base_params)
    first = jax.tree_util.tree_map(np.asarray, sim.global_lora)
    out = sim.run_round()
    assert np.isfinite(out["train_loss"])
    for a, b in zip(jax.tree_util.tree_leaves(base), jax.tree_util.tree_leaves(sim.base_params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert any(float(np.abs(np.asarray(a) - b).max()) > 0 for a, b in
               zip(jax.tree_util.tree_leaves(sim.global_lora), jax.tree_util.tree_leaves(first)))


# -- the yardstick --------------------------------------------------------------------
def test_required_work_counts_and_published_widths(bench):
    """The parameter counts ISSUE 35 cut the configuration with, and every
    number of the catalog's row under its own key but the two reduced."""
    import json

    import selfcheck       # importable while the ``bench`` fixture holds benchmark/ on the path

    selfcheck.run()        # every name, unit and file of BENCHMARK.json, the new cell's among them
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        listed = json.load(fh)
    assert [w["chips"] for w in listed["workloads"] if w["name"] == CELL] == [1]
    mine = [m for m in listed["per_layer"] if m["name"].startswith("granite.")]
    assert len(mine) == 22 and all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s" for m in mine)
    bench["flops"].check()
    c = bench["full_config"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # the guide's row, where the guide is on this machine
        with open(catalog) as fh:
            row = next(r for r in map(json.loads, fh) if r["name"] == "granite-4.0-h-micro")
        differ = {k for k, v in row["config"].items() if c.get(k) != v}
        assert differ == set(c["reduced"]) == {"num_hidden_layers", "layer_types"}, differ
        assert c["layer_types"] == row["config"]["layer_types"][:10] and c["source"] == row["source_url"]
    assert c["layer_types"] == c["published"]["layer_types"][:10] and c["layer_types"].count("attention") == 1
    assert (c["hidden_size"], c["vocab_size"], c["shared_intermediate_size"], c["mamba_n_heads"], c["mamba_d_head"],
            c["mamba_d_state"], c["num_attention_heads"], c["num_key_value_heads"]) == (2048, 100352, 8192, 64, 64, 128, 32, 8)
    tcfg = bench["granite"].transformer_config(c, 32768, **bench["full_traffic"]["program"])
    assert (tcfg.mamba_heads, tcfg.mamba_head_dim, tcfg.mamba_d_state, tcfg.mamba_chunk) == (64, 64, 128, 256)
    assert (tcfg.scale_emb, tcfg.scale_depth, tcfg.mup_depth, tcfg.dim_model_base) == (12.0, 0.22, 1, 256)
    assert (tcfg.attn_scale, tcfg.attn_rope, tcfg.tie_embeddings, tcfg.head_dim) == (1 / 64, False, True, 0)
    assert tcfg.mixer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    job = {k: bench["full_traffic"]["train_args"][k] for k in ("lora_rank", "lora_targets")}
    # attention and the scan are counted OUT of the matrix-product fusions' required work
    products = sum(f for f, _ in bench["flops"].step_matmuls(c, job, 1, 32768))
    whole = bench["flops"].train_flops_per_step(c, job, 1, bench["full_traffic"]["doc_lengths"])
    assert 0.95 < products / whole < 0.97


# -- the chip's compiler, without the chip ------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip to compile for (nothing runs); skipped where the
    TPU's compiler cannot be loaded."""
    from jax.sharding import SingleDeviceSharding

    try:
        from jax.experimental import topologies

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_compile_cache():
    """A compile for an absent chip cannot be read back from the persistent
    cache: off around it, and as it was after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("b,s,width,packed", [(1, 32768, 64, True), (4, 2048, 128, False)],
                         ids=["granite_packed_32k", "mistral_unpacked_2k"])
def test_flash_kernel_pair_compiles_for_the_chip_at_a_cells_size(b, s, width, packed, one_chip):
    """Mosaic takes the kernel pair at 32 query over 8 KV heads: with segment
    ids at 32,768 tokens x 64 (this cell's packed rows: the column-against-row
    compare of document indices, the two SMEM scalars a tile, a head's dq in
    VMEM), and without at 4 x 2,048 x 128, the unpacked rows of
    ``mistral7b_d2.sft_2k`` (PR 38)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.pallas import flash_attention as fa

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q, kv = spec((b, s, 32, width), jnp.bfloat16), spec((b, s, 8, width), jnp.bfloat16)
    assert fa.tiles(q, kv, kv) and fa.block_of(s) == 1024
    loss = lambda q, k, v, doc=None: jnp.sum(fa.causal_attention(
        q, k, v, scale=1 / 64, segments=doc, interpret=False).astype(jnp.float32))
    with _no_compile_cache():
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv, *([spec((b, s), jnp.int32)] if packed else [])).compile()
    text = compiled.as_text()
    assert "fedml_causal_attention_fwd" in text and "fedml_causal_attention_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


def test_scan_kernel_pair_compiles_for_the_chip_at_the_cells_size(one_chip):
    """Mosaic takes the selective scan's forward and backward kernels at 32,768
    tokens, 64 heads of 64 on one group, a state of 128, chunks of 256, with
    document indices: a head's columns of ``dt`` and ``l`` by static lane
    slices, two 64-wide heads a register, the states of all heads in VMEM.
    No loop over the chunks is left in the program, and beside the operands
    it holds the saved entry states (268 MB), the cotangents and little else."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.pallas import ssd as kernel

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    b, s, h, p, g, n = 1, 32768, 64, 64, 1, 128
    x, bc = spec((b, s, h, p), jnp.bfloat16), spec((b, s, g, n), jnp.bfloat16)
    assert kernel.tiles(x, bc, 256)
    loss = lambda x, dt, a, b_in, c_in, d_skip, doc: jnp.sum(kernel.ssd(
        x, dt, a, b_in, c_in, d_skip, doc, 256, interpret=False).astype(jnp.float32))
    with _no_compile_cache():
        compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
            x, spec((b, s, h), jnp.float32), spec((h,), jnp.float32), bc, bc, spec((h,), jnp.float32),
            spec((b, s), jnp.int32)).compile()
    text = compiled.as_text()
    assert "fedml_ssd_fwd" in text and "fedml_ssd_bwd" in text
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
