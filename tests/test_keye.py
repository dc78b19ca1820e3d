"""Keye-VL-2.0's language model through the normal path: DeepSeek sparse
attention (a frozen learned indexer chooses the keys each query attends) and
softmax-routed expert layers of which one expert-parallel rank is held,
against the plain reference (``benchmark/ref_keye.py``), adapter fine-tuning
over a frozen base in ``LLMTrainer``, and the chip's compiler at the cell's
size.

Tiny sizes (the configuration's ``rehearsal``: hidden 64, 4 query over 2 KV
heads of 16, an indexer of 4 heads of 16 keeping 16 keys a query, 32 experts
of which 8 are held, 4 a token, two layers, sequences of 64), with the
attention's chunk (32) and the choice's (8) smaller than a sequence.
"""

import contextlib
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "keye_vl2_30b_a3b_d4_ep8.lora_32k"
JOB = {"lora_rank": 4, "lora_alpha": 8.0}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (it keeps them importable by bare name) and
    the cell's files at their rehearsal sizes."""
    sys.path.insert(0, BENCH)
    try:
        import compare
        import flops_keye
        import keye
        import ref_keye
        from run import load_json

        config = load_json(BENCH, "configs", "keye_vl2_30b_a3b_d4_ep8.json")
        traffic = load_json(BENCH, "traffic", "lora_sft_32k_b1.json")
        limits = load_json(BENCH, "limits", CELL + ".json")
        yield {"compare": compare, "flops": flops_keye, "ref": ref_keye, "keye": keye,
               "config": {**config, **config["rehearsal"]}, "full_config": config,
               "traffic": {**traffic, **traffic["rehearsal"]}, "full_traffic": traffic, "limits": limits}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """64 tokens in two chunks of the blockwise pass (a chunk of keys holds
    whole words of the packed choice: 32) and eight of the choice."""
    from fedml_tpu.ops import dsa, sparse_attention

    monkeypatch.setattr(sparse_attention, "CHUNK", 32)
    monkeypatch.setattr(dsa, "INDEX_CHUNK", 8)


def _cfg(bench, seq=64, config=None, **kw):
    """The tiny model in float32, so that it differs from the reference by
    the order of its sums alone."""
    import jax.numpy as jnp

    cfg = bench["keye"].transformer_config(config or bench["config"], seq, "full",
                                           **{**bench["traffic"]["program"], **kw})
    return dataclasses.replace(cfg, dtype=jnp.float32, logits_dtype=jnp.float32)


def _weights(bench, seed=5, config=None):
    """The reference's float32 draw of the base, flat and as the program's tree."""
    import jax.numpy as jnp
    from flax import traverse_util

    w = bench["ref"].init_weights(config or bench["config"], seed, dtype=jnp.float32)
    return w, traverse_util.unflatten_dict(w, sep="/")


def _x(seed, s=64, d=64):
    import jax

    return jax.random.normal(jax.random.PRNGKey(seed), (s, d))


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(got, want, atol=tol * max(1.0, float(np.abs(want).max())))


# -- the choice --------------------------------------------------------------------
@pytest.mark.parametrize("topk,rows_seen", [(16, (3, 63, 40, 20, 15, 16)), (1, (0, 5, 63, 1, 2, 30)),
                                            (64, (63, 62, 0, 10, 33, 7))])
def test_choice_is_lax_top_k_of_signed_tied_scores(topk, rows_seen):
    """``top_of`` over signed scores with many ties, +0.0 beside -0.0, and
    rows with fewer visible keys than ``topk``: the set ``lax.top_k`` gives
    (ties to the lower index) among the visible keys, every one where fewer
    are visible; ``pack`` and ``unpack`` keep it bit for bit."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops import dsa

    rng = np.random.default_rng(topk)
    score = rng.integers(-3, 4, size=(6, 64)).astype(np.float32) * rng.choice([0.5, 1.0], size=(6, 64))
    score[0] = 0.0
    score[1, ::2] = -0.0
    score[2, 7] = -1e30
    visible = np.arange(64)[None, :] <= np.array(rows_seen)[:, None]
    got = np.asarray(dsa.top_of(jnp.asarray(score), jnp.asarray(visible), topk))
    _, idx = jax.lax.top_k(jnp.asarray(np.where(visible, score + 0.0, -np.inf)), topk)
    want = np.zeros_like(visible)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    want &= visible
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) == np.minimum(visible.sum(1), topk)).all()
    np.testing.assert_array_equal(np.asarray(dsa.unpack(dsa.pack(jnp.asarray(got)))), got)


@pytest.mark.parametrize("case", ["random", "tied", "few_heads_zero"])
def test_indexer_choice_is_the_references(case, bench):
    """The program's ``Indexer`` (float32) against the reference's choice
    written out per query: the same keys, bit for bit.  ``tied``: a zero
    query kernel makes every key of a query score the same, so each keeps
    its lowest ``topk``; ``few_heads_zero``: half the heads read nothing."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import transformer as tfm

    cfg = _cfg(bench)
    w, tree = _weights(bench)
    if case != "random":
        kq = w["layer_0/attn/indexer/wq/kernel"]
        kq = jnp.zeros_like(kq) if case == "tied" else kq.at[:, ::2].set(0.0)
        w = {**w, "layer_0/attn/indexer/wq/kernel": kq}
        tree["layer_0"]["attn"]["indexer"]["wq"]["kernel"] = kq
    x = _x(0)
    m = bench["ref"].parts(w, {}, bench["config"], JOB)
    with jax.default_matmul_precision("highest"):
        bits, chosen = tfm.Indexer(cfg).apply({"params": tree["layer_0"]["attn"]["indexer"]}, x[None],
                                             np.arange(64)[None])
        _, want_bits, want_kept = jax.jit(lambda x: m["dsa"](x, "layer_0/attn/"))(x)
    np.testing.assert_array_equal(np.asarray(bits[0]), np.asarray(want_bits))
    kept = sum(min(t + 1, 16) for t in range(64))
    assert int(chosen) == float(want_kept) == kept
    if case == "tied":   # every query keeps its 16 lowest keys
        from fedml_tpu.ops.dsa import unpack

        lowest = np.arange(64)[None, :] < np.minimum(np.arange(64)[:, None] + 1, 16)
        np.testing.assert_array_equal(np.asarray(unpack(bits[0])), lowest)


@pytest.mark.parametrize("chunk,index_chunk", [(32, 8), (64, 64), (32, 16)])
def test_sparse_attention_mixer_is_the_reference(chunk, index_chunk, bench, monkeypatch):
    """``DSAttention`` (QK-norm, RoPE, the indexer's choice, the blockwise
    pass with the packed choice as its mask) against the reference's full
    rows of scores under its own choice: the output and the gradient to the
    input, in one chunk and in several."""
    import jax
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops import dsa, sparse_attention

    monkeypatch.setattr(sparse_attention, "CHUNK", chunk)
    monkeypatch.setattr(dsa, "INDEX_CHUNK", index_chunk)
    cfg = _cfg(bench)
    w, tree = _weights(bench)
    x, probe = _x(1), _x(2)
    pos = np.arange(64)[None]
    m = bench["ref"].parts(w, {}, bench["config"], JOB)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(lambda x: (tfm.DSAttention(cfg).apply(
            {"params": tree["layer_1"]["attn"]}, x[None], pos)[0] * probe).sum()))(x)
        want, want_g = jax.jit(jax.value_and_grad(lambda x: (m["dsa"](x, "layer_1/attn/")[0] * probe).sum()))(x)
    _close(got, want)
    _close(got_g, want_g)


# -- the blockwise pass's read of the packed choice ----------------------------------------
def _packed_choice(b, s, seed):
    """A random packed choice (b, 1, s, s / 32) uint32 that keeps about half
    of every query's keys and always its own token."""
    from fedml_tpu.ops.dsa import pack

    chosen = np.random.default_rng(seed).random((b, 1, s, s)) < 0.5
    chosen[..., np.arange(s), np.arange(s)] = True
    return pack(chosen)


def test_blockwise_pass_reads_the_packed_choice_in_place():
    """The pass cuts the packed choice by query chunk alone and slices a key
    chunk's words where the pair is computed: the uint32 choice is moved by
    its leading axes only, so no relayout of its rows into chunk-pair order
    (a minor axis of ``k_chunk / 32`` words) is left, in the forward or in
    the gradient."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.sparse_attention import block_sparse_attention

    from .conftest import minor_axes_moved, transposes_of

    q = jax.ShapeDtypeStruct((1, 256, 4, 16), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 256, 2, 16), jnp.bfloat16)
    keep = jax.ShapeDtypeStruct((1, 1, 256, 8), jnp.uint32)
    f = lambda q, k, v, keep: block_sparse_attention(q, k, v, keep, block_size=32, q_chunk=64, k_chunk=64)
    loss = lambda q, k, v, keep: jnp.sum(f(q, k, v, keep).astype(jnp.float32) ** 2)
    for jaxpr in (jax.make_jaxpr(f)(q, k, k, keep),
                  jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k, keep)):
        moved = transposes_of(jaxpr, jnp.uint32)
        assert moved, "the choice's query chunks are moved ahead by a transpose the walk must see"
        assert [m for m in moved if minor_axes_moved(m[1])] == []


@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("q_chunk,k_chunk", [(64, 64), (64, 128), (128, 64)])
def test_packed_choice_is_its_unpacked_token_mask(q_chunk, k_chunk, b):
    """The packed choice (a bit a key, ``block_size`` 32) against the same
    choice unpacked to a token mask (``block_size`` 1) and against softmax
    over the masked scores whole: the output and the gradients to q, k and v,
    with every key chunk's words sliced at its own offset."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.sparse_attention import block_sparse_attention, unpack

    s, h, kv, d = 256, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(41 + b), 4)
    q, k, v, probe = (jax.random.normal(key, (b, s, n, d)) for key, n in zip(keys, (h, kv, kv, h)))
    packed = _packed_choice(b, s, seed=b)
    tokens = unpack(packed)

    def dense(q, k, v):
        kk, vv = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") * d ** -0.5
        mask = tokens & jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision="highest")

    def run(attend):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(attend(q, k, v) * probe), argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = run(lambda q, k, v: block_sparse_attention(q, k, v, packed, block_size=32,
                                                         q_chunk=q_chunk, k_chunk=k_chunk))
        as_tokens = run(lambda q, k, v: block_sparse_attention(q, k, v, tokens, block_size=1,
                                                               q_chunk=q_chunk, k_chunk=k_chunk))
        want = run(dense)
    for g, t, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(as_tokens),
                       jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, t, rtol=1e-6, atol=1e-6)
        _close(g, w)


# -- the router and the expert layer -------------------------------------------------
def test_softmax_router_is_the_reference(bench):
    """Softmax scores over all 32, the 4 best without a sort, gates
    renormalised over them, ties to the lower index; sigmoid stays the default."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.moe import route

    z = bench["ref"].sizes(bench["config"])
    x = _x(6)
    w_r = jax.random.normal(jax.random.PRNGKey(7), (64, 32)) / 8
    w_r = w_r.at[:, 5].set(w_r[:, 2])            # experts 2 and 5 always tie
    with jax.default_matmul_precision("highest"):
        idx, gates, counts = route(x, w_r, z["k"], 1.0, z["norm"], "softmax")
        ref_idx, ref_gates = bench["ref"].route(x, w_r, z)
        sig_idx, sig_gates, _ = route(x, w_r, z["k"], 1.0, True)
    order = jnp.argsort(ref_idx, -1)
    np.testing.assert_array_equal(idx, jnp.take_along_axis(ref_idx, order, -1))
    _close(gates, jnp.take_along_axis(ref_gates, order, -1), 1e-6)
    np.testing.assert_array_equal(counts, np.bincount(np.asarray(idx).ravel(), minlength=32))
    assert int(counts[2]) >= int(counts[5])
    _close(gates.sum(-1), np.ones(64), 1e-6)
    # both orders are the logits': the same experts, other gates
    np.testing.assert_array_equal(sig_idx, idx)
    assert float(np.abs(np.asarray(sig_gates) - np.asarray(gates)).max()) > 1e-3
    with pytest.raises(ValueError, match="router scoring"):
        route(x, w_r, 4, scoring="tanh")


def test_the_eight_shares_add_up(bench):
    """32 experts in 8 shares of 4: what the eight expert-parallel ranks'
    layers give adds up to the uncut reference's whole layer (no shared
    expert here), and every rank routes every token over all 32."""
    import jax
    from fedml_tpu.models import transformer as tfm

    whole_c = {**bench["config"], "num_experts": 32, "n_routed_experts": 32}
    w, tree = _weights(bench, config=whole_c)
    assert w["layer_1/moe/experts/w_gate"].shape[0] == 32
    x = _x(8)
    m = bench["ref"].parts(w, {}, whole_c, JOB)
    with jax.default_matmul_precision("highest"):
        whole, on_held = m["moe"](x, "layer_1/moe/")
        total = 0.0
        for rank in range(8):
            cfg = _cfg(bench, experts_held=4, first_expert=4 * rank)
            params = {**tree["layer_1"]["moe"], "experts": {
                k: v[4 * rank: 4 * rank + 4] for k, v in tree["layer_1"]["moe"]["experts"].items()}}
            y, sown = tfm.MoE(cfg).apply({"params": params}, x[None], mutable=["stats"])
            total = total + y[0]
            assert float(sown["stats"]["moe_assignments"]) == 64 * 4
    assert float(on_held.sum()) == 64 * 4          # uncut: every assignment is held
    _close(total, whole)


# -- the whole model -------------------------------------------------------------------
def test_model_loss_is_the_reference(bench):
    """Two layers, the head and the loss a chunk at a time, in float32: the
    reference's loss and its keys chosen and held assignments by layer."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models.transformer import Transformer

    ref, c = bench["ref"], bench["config"]
    w, tree = _weights(bench)
    tok, tgt = ref.batch_tokens(4, 0, 1, 64, c["vocab_size"])
    with jax.default_matmul_precision("highest"):
        losses, sown = jax.jit(lambda p: Transformer(_cfg(bench)).apply(
            {"params": p}, tok, targets=tgt, mutable=["stats"]))(tree)
        want, (kept, held) = jax.jit(lambda w: ref.row_loss(
            w, {}, jnp.asarray(tok[0]), jnp.asarray(tgt[0]), c, JOB))(w)
    np.testing.assert_allclose(losses.mean(), want, rtol=2e-5)
    stats = sown["stats"]
    for i in range(2):
        layer = stats[f"layer_{i}"]
        assert float(layer["attn"]["sparse_kept"]) == 2 * float(kept[i])      # summed over the 2 KV heads
        assert float(layer["attn"]["sparse_causal"]) == 2 * 64 * 65 / 2
        assert float(layer["moe"]["moe_held"]) == float(held[i])


@pytest.fixture(scope="module")
def first_steps(bench):
    """The cell's driver at rehearsal sizes, in process: ``fit``'s first three
    steps and the float32 reference's, with the float8 control."""
    import jax
    from fedml_tpu.ops import dsa, sparse_attention

    driver = bench["keye"].Driver({"name": CELL, "chips": 1}, bench["config"], bench["traffic"], 7,
                                  jax.devices()[:1])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sparse_attention, "CHUNK", 32)
        mp.setattr(dsa, "INDEX_CHUNK", 8)
        driver.build()
        base = jax.tree_util.tree_map(np.asarray, driver.trainer.params)
        driver.first_steps()
        after = jax.tree_util.tree_map(np.asarray, driver.trainer.params)
        return {"driver": driver, "base": base, "after": after, "reference": driver.reference(),
                "control": driver.reference(control="fp8")}


def test_model_follows_the_reference_and_the_control_does_not(first_steps, bench):
    """Loss of three steps, the first gradient's norm per adapter leaf and the
    adapters' change after three steps through ``LLMTrainer.fit``, against the
    float32 reference under the cell's rehearsal limits, which must refuse the
    float8 control by at least one number; both choose the same count of keys."""
    compare, limits = bench["compare"], bench["limits"]["rehearsal"]
    d, ref = first_steps["driver"], first_steps["reference"]
    ok, compared = compare.judge(d.gaps(d.readings, ref), limits)
    assert ok, compared
    ok, compared = compare.judge(d.gaps(first_steps["control"], ref), limits)
    assert not ok, compared
    kept = sum(min(t + 1, 16) for t in range(64))
    assert ref["chosen_by_layer"] == [kept, kept]
    assert d.readings["attended"] == [2 * 2 * kept, 2 * 2 * 64 * 65 / 2]     # 2 layers x 2 KV heads
    assert d.readings["held_in_step"] > 0 and len(ref["held_by_block"]) == 2
    assert d.readings["attention_sites"] == {"kernel": 0, "blockwise": 2}


def test_adapter_mode_leaves_the_base_bit_equal(first_steps):
    import jax

    d = first_steps["driver"]
    before, after = (jax.tree_util.tree_leaves(first_steps[k]) for k in ("base", "after"))
    assert len(before) == len(after) > 0
    for a, b in zip(before, after):
        assert a.dtype == b.dtype and a.dtype.name == "bfloat16"
        np.testing.assert_array_equal(a, b)
    assert sorted(d.trainer.lora) == sorted(f"layer_{i}/attn/w{n}/kernel" for i in (0, 1) for n in "qkvo")
    assert all(v > 0 for v in d.readings["change_norms"].values())


def test_sparse_spans_attributes_and_counter(first_steps):
    from fedml_tpu.obs import trace as obstrace

    steps = [s for s in obstrace.recent() if s.name == "llm.step" and "sparse_kept" in s.attrs
             and s.attrs["sparse_causal"] == 2 * 2 * 64 * 65 / 2]
    assert len(steps) >= 4
    for s in steps:
        assert s.attrs["sparse_kept"] == 2 * 2 * sum(min(t + 1, 16) for t in range(64))
        assert 0 < s.attrs["moe_held"] < s.attrs["moe_assignments"] == 2 * 64 * 4
    kept, causal = (obstrace.LLM_ATTENDED_KEYS.value(kind=k) for k in ("kept", "causal"))
    assert 0 < kept < causal


def test_the_indexer_takes_no_adapter_and_every_leaf_a_rule(bench):
    """No adapter target reaches the indexer, even one that names its kernels'
    own names; every new leaf is named by a sharding rule of its own."""
    import re

    import jax
    from jax.sharding import PartitionSpec as P
    from fedml_tpu.llm import lora as lora_lib
    from fedml_tpu.models.transformer import Transformer
    from fedml_tpu.parallel.sharding import TRANSFORMER_RULES, partition_specs

    params = jax.eval_shape(lambda: Transformer(_cfg(bench)).init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 64), np.int32)))["params"]
    for targets in (lora_lib.DEFAULT_TARGETS, r".*w(q|k)/kernel", r".*attn/.*/kernel"):
        lora = jax.eval_shape(lambda: lora_lib.init_lora(params, 4, jax.random.PRNGKey(1), targets))
        assert lora and not [p for p in lora if "indexer" in p], sorted(lora)
    flat = bench["compare"].flat(params)
    assert "layer_0/attn/indexer/k_norm/bias" in flat
    assert all(any(re.fullmatch(pattern, path) for pattern, _ in TRANSFORMER_RULES) for path in flat), \
        [p for p in flat if not any(re.fullmatch(pattern, p) for pattern, _ in TRANSFORMER_RULES)]
    specs = bench["compare"].flat(jax.tree_util.tree_map(
        lambda s: s, partition_specs(params), is_leaf=lambda x: isinstance(x, P)))
    assert specs["layer_0/attn/indexer/wq/kernel"] == P("data", None, None)
    assert specs["layer_0/attn/indexer/wk/kernel"] == specs["layer_0/attn/indexer/weights/kernel"] == P("data", None)
    assert tuple(specs["layer_0/attn/indexer/k_norm/bias"]) == (None,)


# -- the yardstick ---------------------------------------------------------------------
def test_required_work_counts_and_published_widths(bench):
    """The counts the configuration was cut with, the forward's parts the
    cell was sized with, and every published width as published."""
    import json

    bench["flops"].check()
    c = bench["full_config"]
    with open(os.path.join(BENCH, "configs", "keye_vl2_30b_a3b_d4_ep8.json")) as fh:
        assert json.load(fh)["source"].startswith("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B")
    published = {
        "hidden_size": 2048, "intermediate_size": 6144, "moe_intermediate_size": 768, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 4, "num_experts_per_tok": 8, "norm_topk_prob": True,
        "rope_theta": 10000000, "rms_norm_eps": 1e-06, "num_local_experts": 128, "decoder_sparse_step": 1,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    differ = {k for k, v in published.items() if c.get(k) != v}
    assert differ <= set(c["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}, differ
    assert c["router_experts"] == c["published"]["num_experts"] == 128 and c["num_experts"] == 16
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    tcfg = bench["keye"].transformer_config(c, 32768)
    assert (tcfg.n_routed_experts, tcfg.experts_held, tcfg.top_k, tcfg.router_scoring) == (128, 16, 8, "softmax")
    assert (tcfg.dsa_index_heads, tcfg.dsa_index_head_dim, tcfg.dsa_topk) == (16, 64, 2048)
    assert tcfg.mixer_types == ("dsa",) * 4 and bench["full_traffic"]["remat_policy"] == "full"


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


def test_sparse_attention_layer_compiles_for_the_chip_at_the_cells_size(bench, one_chip, monkeypatch):
    """One ``DSAttention`` at the cell's widths and 32,768 tokens, forward and
    the gradient to its input under the block's remat policy (the choice
    kept, not made again): the chip's compiler takes it with no sort, in
    under 2.5 GB of temporaries beside its operands."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops import dsa, sparse_attention

    monkeypatch.setattr(sparse_attention, "CHUNK", 512)
    monkeypatch.setattr(dsa, "INDEX_CHUNK", 128)
    cfg = bench["keye"].transformer_config(bench["full_config"], 32768)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x, pos = spec((1, 32768, 2048), jnp.bfloat16), spec((1, 32768), jnp.int32)
    mixer = tfm.DSAttention(cfg)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype),
                                               jnp.zeros(pos.shape, pos.dtype)))["params"]
    params = jax.tree_util.tree_map(lambda p: spec(p.shape, jnp.bfloat16), params)
    remat = lambda p, x, pos: jax.checkpoint(lambda p, x: mixer.apply({"params": p}, x, pos),
                                             policy=tfm.block_remat_policy(cfg))(p, x)
    loss = lambda p, x, pos: jnp.sum(remat(p, x, pos).astype(jnp.float32))
    with _no_compile_cache():
        compiled = jax.jit(jax.grad(loss, argnums=1)).lower(params, x, pos).compile()
    assert " sort(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9
