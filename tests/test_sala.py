"""MiniCPM-SALA through the normal path: the two mixers against the plain
reference (``benchmark/ref_sala.py``), adapter fine-tuning over a frozen base
in ``LLMTrainer``, and the federated adapter round on a hybrid model.

Tiny sizes (the configuration's ``rehearsal``: hidden 64, 4 x 16 heads, 2 KV
heads, kernel 4 / stride 2 / block 8 / top-k 2 / window 16 / ``dense_len`` 32,
sequences of 64) keep every branch alive: the sparse layer selects and drops
blocks, the lightning scan carries its state over chunks.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "minicpm_sala_d4.lora_16k"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (it keeps them importable by bare name) and
    the cell's files at their rehearsal sizes."""
    sys.path.insert(0, BENCH)
    try:
        import compare
        import flops_sala
        import ref_sala
        import sala
        from run import load_json

        config = load_json(BENCH, "configs", "minicpm_sala_d4.json")
        traffic = load_json(BENCH, "traffic", "lora_sft_16k_b1.json")
        limits = load_json(BENCH, "limits", CELL + ".json")
        yield {"compare": compare, "flops": flops_sala, "ref": ref_sala, "sala": sala,
               "config": {**config, **config["rehearsal"]}, "full_config": config,
               "traffic": {**traffic, **traffic["rehearsal"]}, "limits": limits}
    finally:
        sys.path.remove(BENCH)


def _qkv(seed, s, h, kv, d, b=1):
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (b, s, n, d), jnp.float32)
                 for i, n in enumerate((h, kv, kv)))


def _value_and_grads(fn, *args):
    import jax
    import jax.numpy as jnp

    probe = jax.random.normal(jax.random.PRNGKey(99), fn(*args).shape, jnp.float32)
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * probe), argnums=(0, 1, 2))(*args)


# -- lightning attention ---------------------------------------------------------
@pytest.mark.parametrize("chunk,seq", [(8, 64), (16, 64), (16, 50)])
def test_chunked_lightning_is_the_recurrence(chunk, seq, bench):
    """The chunked scan regroups the recurrence's sums: float32 in, equal to
    round-off (1e-5 of the output's scale), for two chunk sizes and a length
    that is no multiple of the chunk; input gradients likewise.  The
    reference's own chunked form is held to its recurrence too."""
    import jax
    from fedml_tpu.ops.lightning_attention import (decay_slopes, lightning_attention,
                                                  lightning_attention_recurrent)

    q, k, v = _qkv(0, seq, 4, 4, 16, b=2)
    slopes = decay_slopes(4)
    with jax.default_matmul_precision("highest"):
        want, want_g = _value_and_grads(lambda *a: lightning_attention_recurrent(*a, slopes), q, k, v)
        got, got_g = _value_and_grads(lambda *a: lightning_attention(*a, slopes, chunk=chunk), q, k, v)
        ref = bench["ref"]
        for row in range(2):
            theirs = ref.lightning_recurrent(q[row], k[row], v[row], ref.decay_slopes(4))
            np.testing.assert_allclose(
                lightning_attention(q, k, v, slopes, chunk=chunk)[row], theirs, atol=2e-5)
        if seq % chunk == 0:
            np.testing.assert_allclose(ref.lightning_chunked(q[0], k[0], v[0], slopes, chunk=chunk),
                                       ref.lightning_recurrent(q[0], k[0], v[0], slopes), atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(np.abs(b).max()))


def test_fast_heads_underflow_to_zero_not_to_inf():
    """32 heads: the fastest decays by exp(-0.84) a token, so lam^C is 0 in
    float32 over a chunk of 256; nothing is divided by it."""
    import jax.numpy as jnp
    from fedml_tpu.ops.lightning_attention import decay_slopes, lightning_attention

    q, k, v = _qkv(1, 512, 32, 32, 8)
    out = lightning_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                              decay_slopes(32), chunk=256)
    assert out.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


# -- block-sparse attention --------------------------------------------------------
def _select_kw(z, **kw):
    return dict(kernel_size=z["kernel_size"], kernel_stride=z["kernel_stride"],
                block_size=z["block_size"], topk=z["topk"], init_blocks=z["init_blocks"],
                window_size=z["window_size"], **kw)


@pytest.mark.parametrize("seq,chunk", [(64, 16), (128, 32)])
def test_sparse_layer_is_the_per_query_selection(seq, chunk, bench):
    """Past ``dense_len`` the blockwise masked pass equals the reference's
    selection written out per query (an explicit overlap table, a stable sort,
    a full row of masked scores): outputs and input gradients to float32
    round-off, and the keys kept to the last one, which is also what
    ``flops_sala.kept_keys`` counts from the shapes alone."""
    import jax
    from fedml_tpu.ops.sparse_attention import block_sparse_attention, select_blocks

    ref, c = bench["ref"], bench["config"]
    z = c["sparse_config"]
    assert seq > z["dense_len"]
    q, k, v = _qkv(2, seq, 4, 2, 16)

    def program(q, k, v):
        keep, _, _ = select_blocks(q, k, **_select_kw(z, q_chunk=chunk))
        return block_sparse_attention(q, k, v, keep, block_size=z["block_size"],
                                      q_chunk=chunk, k_chunk=chunk)

    with jax.default_matmul_precision("highest"):
        keep, kept, causal = select_blocks(q, k, **_select_kw(z, q_chunk=chunk))
        got, got_g = _value_and_grads(program, q, k, v)
        want, want_g = _value_and_grads(
            lambda q, k, v: ref.sparse_attention(q[0], k[0], v[0], z)[0][None], q, k, v)
        _, ref_kept, ref_causal = ref.sparse_attention(q[0], k[0], v[0], z)
    assert float(kept) == float(ref_kept) and float(causal) == float(ref_causal)
    assert float(kept) < float(causal), "the tiny sizes must make the selection drop blocks"
    per_head = bench["flops"].kept_keys(c, seq)
    assert (float(kept), float(causal)) == tuple(2 * x for x in per_head)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=1e-5 * max(1.0, float(np.abs(b).max())))


def test_block_attention_with_equal_widths_is_the_parents_program():
    """PR 33 gave ``block_sparse_attention`` a value width of its own.  With
    values as wide as the keys it must still be the program it was: the jaxpr
    of its output and three gradients at this file's sizes (two KV heads, a
    block mask by KV head, chunks of 16) is pinned by SHA-256, so outputs and
    gradients stay the same to the bit on any backend.  First taken on
    commit ca052ac; taken again when the pass came to cut the mask
    by query chunk and slice each key chunk's columns in place (the same
    products in the same order: outputs and gradients equal to the bit at
    these sizes on the CPU, the text not)."""
    import hashlib
    import re

    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.sparse_attention import block_sparse_attention

    q = jax.ShapeDtypeStruct((1, 64, 4, 16), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 64, 2, 16), jnp.bfloat16)
    keep = jax.ShapeDtypeStruct((1, 2, 64, 8), jnp.bool_)
    f = lambda q, k, v, keep: jnp.sum(block_sparse_attention(
        q, k, v, keep, block_size=8, q_chunk=16, k_chunk=16).astype(jnp.float32) ** 2)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, k, k, keep)))
    assert (hashlib.sha256(text.encode()).hexdigest(), len(text)) == (
        "e0097377eaea6f87ce16b1ecb271455675dd5d0d8a13c1018a74a25cb154ad29", 22018)


def test_blockwise_pass_reads_the_block_mask_in_place():
    """The pass cuts a bool block mask by query chunk alone and slices a key
    chunk's blocks where the pair is computed: the mask is moved by its
    leading axes only, in the forward and in the gradient, never relaid into
    chunk-pair order (a minor axis of ``k_chunk / block_size`` blocks)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.sparse_attention import block_sparse_attention

    from .conftest import minor_axes_moved, transposes_of

    q = jax.ShapeDtypeStruct((1, 256, 4, 16), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 256, 2, 16), jnp.bfloat16)
    keep = jax.ShapeDtypeStruct((1, 2, 256, 16), jnp.bool_)
    f = lambda q, k, v, keep: block_sparse_attention(q, k, v, keep, block_size=16, q_chunk=64, k_chunk=64)
    loss = lambda q, k, v, keep: jnp.sum(f(q, k, v, keep).astype(jnp.float32) ** 2)
    for jaxpr in (jax.make_jaxpr(f)(q, k, k, keep),
                  jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k, keep)):
        moved = transposes_of(jaxpr, jnp.bool_)
        assert moved, "the mask's query chunks are moved ahead by a transpose the walk must see"
        assert [m for m in moved if minor_axes_moved(m[1])] == []


def _top_k_form(score, candidate, topk):
    """The selection ``best_of`` replaced (PR 30), kept here as its oracle:
    ``lax.top_k`` (equal values: lower index first) and a one-hot of what it
    returned."""
    import jax
    import jax.numpy as jnp

    n = score.shape[-1]
    vals, idx = jax.lax.top_k(jnp.where(candidate, score, -1.0), min(topk, n))
    return ((idx[..., None] == jnp.arange(n)) & (vals[..., None] >= 0.0)).any(-2)


def _planted_scores(case: str):
    """(score (rows, n) float32, candidate (rows, n) bool, topk) of one case."""
    rng = np.random.default_rng(30)
    rows, n, topk = 48, 256, 64
    score = rng.random((rows, n), dtype=np.float32)
    candidate = rng.random((rows, n)) < 0.85
    if case == "all_equal":
        score[:] = np.float32(0.37)
        score[rows // 2:] = rng.random((rows - rows // 2, 1), dtype=np.float32)  # a value a row
    elif case == "ties_astride_topk":
        # a run of equal values that starts inside the best topk and ends
        # outside them: 40 above it, 50 equal, the rest below
        for r in range(rows):
            cols = rng.permutation(np.flatnonzero(candidate[r]))
            score[r] = rng.random(n, dtype=np.float32) * np.float32(0.4)
            score[r, cols[:40]] += np.float32(0.6)
            score[r, cols[40:90]] = np.float32(0.5)
    elif case == "few_distinct_values":
        score = (np.floor(score * 5) / 5).astype(np.float32)
    elif case == "zeros":
        score[:] = 0.0                                        # exactly +0.0 everywhere
        score[rows // 2:] = np.where(rng.random((rows - rows // 2, n)) < 0.8, 0.0,
                                     score[rows // 2:]).astype(np.float32)
    elif case == "tiny_and_large":
        score = (score * np.float32(2.0) ** rng.integers(-100, 4, (rows, n))).astype(np.float32)
    elif case == "fewer_candidates_than_topk":
        candidate = rng.random((rows, n)) < np.linspace(0.0, 0.3, rows)[:, None]
        candidate[1, :] = False
        candidate[2, :topk] = True                            # exactly topk candidates
        candidate[2, topk:] = False
    elif case == "topk_at_least_nblk":
        n, topk = 32, 64
        score, candidate = score[:, :n], candidate[:, :n]
    elif case == "topk_is_nblk":
        n = topk = 64
        score, candidate = score[:, :n], candidate[:, :n]
    elif case != "random":
        raise ValueError(case)
    return score, candidate, topk


@pytest.mark.parametrize("case", ["random", "all_equal", "ties_astride_topk", "few_distinct_values", "zeros",
                                  "tiny_and_large", "fewer_candidates_than_topk", "topk_at_least_nblk",
                                  "topk_is_nblk"])
def test_best_of_is_the_top_k_form(case):
    """The sort-free selection chooses, element for element, what ``top_k``
    chose: ties go to the lower index, a short row keeps every candidate."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.sparse_attention import best_of

    score, candidate, topk = _planted_scores(case)
    assert not np.signbit(score).any()
    got = np.asarray(jax.jit(best_of, static_argnums=2)(jnp.asarray(score), jnp.asarray(candidate), topk))
    want = np.asarray(_top_k_form(jnp.asarray(score), jnp.asarray(candidate), topk))
    np.testing.assert_array_equal(got, want)
    assert not (got & ~candidate).any()
    np.testing.assert_array_equal(got.sum(-1), np.minimum(candidate.sum(-1), topk))


@pytest.mark.parametrize("keys", ["random", "constant"])
@pytest.mark.parametrize("q_chunk", [16, 32])
def test_select_blocks_keeps_what_the_top_k_form_kept(q_chunk, keys, bench, monkeypatch):
    """The whole selection, at two chunk sizes: the mask with ``best_of`` is
    the mask with the ``top_k`` form in its place; constant keys make every
    visible compressed key score the same, so whole rows are ties."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops import sparse_attention as sa

    z = bench["config"]["sparse_config"]
    q, k, _ = _qkv(5, 128, 4, 2, 16, b=2)
    if keys == "constant":
        k = jnp.broadcast_to(k[:, :1], k.shape)
    with jax.default_matmul_precision("highest"):
        got = sa.select_blocks(q, k, **_select_kw(z, q_chunk=q_chunk))
        monkeypatch.setattr(sa, "best_of", _top_k_form)
        want = sa.select_blocks(q, k, **_select_kw(z, q_chunk=q_chunk))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert float(got[1]) == float(want[1]) < float(got[2])


def _primitives(jaxpr) -> set:
    """Names of every primitive of a jaxpr and of the jaxprs inside it."""
    import jax

    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def test_selection_at_the_cells_constants_never_sorts(bench, monkeypatch):
    """No ``sort`` / ``top_k`` / ``approx_top_k`` in the jaxpr of
    ``select_blocks`` at the cell's shapes: XLA:TPU lowers each to a sort of
    every row (50 ms a step at 16k tokens: ledger, PR 29), which a CPU-only
    check would never feel.  The walk is proven on the ``top_k`` form."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops import sparse_attention as sa

    c = bench["full_config"]
    with open(os.path.join(BENCH, "traffic", "lora_sft_16k_b1.json")) as fh:
        t = json.load(fh)
    z = c["sparse_config"]
    assert (z["topk"], z["block_size"], t["seq_len"] // z["block_size"]) == (64, 64, 256)
    q, k = (jax.ShapeDtypeStruct((t["batch_size"], t["seq_len"], n, c["head_dim"]), jnp.bfloat16)
            for n in (c["num_attention_heads"], c["num_key_value_heads"]))
    trace = lambda: _primitives(jax.make_jaxpr(
        lambda q, k: sa.select_blocks(q, k, **_select_kw(z, q_chunk=sa.CHUNK)))(q, k).jaxpr)
    sorts = {"sort", "top_k", "approx_top_k"}
    assert not trace() & sorts
    monkeypatch.setattr(sa, "best_of", _top_k_form)
    assert trace() & sorts == {"top_k"}


def test_sparse_layer_under_dense_len_is_causal_attention(bench):
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.ring_attention import dense_attention
    from fedml_tpu.ops.sparse_attention import block_sparse_attention

    q, k, v = _qkv(3, 32, 4, 2, 16, b=2)
    with jax.default_matmul_precision("highest"):
        got = block_sparse_attention(q, k, v, None, block_size=8, q_chunk=8, k_chunk=16)
        want = dense_attention(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True)
        theirs, kept, causal = bench["ref"].sparse_attention(q[0], k[0], v[0],
                                                             bench["config"]["sparse_config"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got[0], theirs, atol=1e-5)
    assert float(kept) == causal == 2 * 32 * 33 / 2


# -- the model ---------------------------------------------------------------------
def _tiny_cfg(bench, seq=64, **kw):
    return bench["sala"].transformer_config(bench["config"], seq, "dots",
                                            **{**bench["traffic"]["program"], **kw})


def test_adapters_on_the_activation_side_are_the_merged_weights(bench):
    """``x W + (x a) b`` through the ``lora`` collection is ``x (W + a b)``:
    the same logits as ``lora.merge`` gives, to float32 round-off, on a model
    with every kind of mixer (wo contracts heads x head_dim)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm import lora as lora_lib
    from fedml_tpu.models.transformer import Transformer

    cfg = dataclasses.replace(_tiny_cfg(bench), dtype=jnp.float32, logits_dtype=jnp.float32,
                              mixer_types=("minicpm4", "lightning-attn", "attention", "lightning-attn"))
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, cfg.vocab_size)
    params = jax.jit(lambda: model.init({"params": jax.random.PRNGKey(1)}, tokens)["params"])()
    lora = lora_lib.init_lora(params, 4, jax.random.PRNGKey(2))
    lora = {k: {"a": ab["a"], "b": 0.1 * jax.random.normal(jax.random.PRNGKey(3), ab["b"].shape)}
            for k, ab in lora.items()}
    assert lora["layer_0/attn/wo/kernel"]["a"].shape == (64, 4)
    with jax.default_matmul_precision("highest"):
        apply = jax.jit(model.apply)
        merged = apply({"params": lora_lib.merge(params, lora, alpha=8.0)}, tokens)
        beside = apply({"params": params, "lora": lora_lib.as_collection(lora, alpha=8.0)}, tokens)
        plain = apply({"params": params}, tokens)
    np.testing.assert_allclose(beside, merged, atol=2e-4)
    assert float(jnp.abs(beside - plain).max()) > 1e-2


@pytest.fixture(scope="module")
def first_steps(bench):
    """The cell's driver at rehearsal sizes, in process: ``fit``'s first three
    steps and the float32 reference's, with the float8 control."""
    import jax
    from fedml_tpu.obs import trace as obstrace
    from fedml_tpu.ops import lightning_attention, sparse_attention

    cell = {"name": CELL, "chips": 1}
    driver = bench["sala"].Driver(cell, bench["config"], bench["traffic"], 11, jax.devices()[:1])
    # the counter is the process's: what other models' steps fed it is set aside
    attended_before = {k: obstrace.LLM_ATTENDED_KEYS.value(kind=k) for k in ("kept", "causal")}
    with pytest.MonkeyPatch.context() as mp:
        # 64 tokens in chunks of 16, so that the step's scans carry their state across chunks
        mp.setattr(lightning_attention, "CHUNK", 16)
        mp.setattr(sparse_attention, "CHUNK", 16)
        driver.build()
        base = jax.tree_util.tree_map(np.asarray, driver.trainer.params)
        driver.first_steps()
    after = jax.tree_util.tree_map(np.asarray, driver.trainer.params)
    return {"driver": driver, "base": base, "after": after, "reference": driver.reference(),
            "control": driver.reference(control="fp8"), "attended_before": attended_before}


def test_model_follows_the_reference_and_the_control_does_not(first_steps, bench):
    """Loss of three steps, the first gradient's norm per adapter leaf and the
    adapters' change after three steps, against the float32 reference, under
    the cell's rehearsal limits (``benchmark/limits``: each sits between the
    program's largest sound CPU reading over seeds and the float8 control's
    smallest; the program differs from the reference by bfloat16 activations
    and products only, the control by float8 operands).  The same limits must
    refuse the control by at least one number."""
    compare, limits = bench["compare"], bench["limits"]["rehearsal"]
    d = first_steps["driver"]
    ok, compared = compare.judge(d.gaps(d.readings, first_steps["reference"]), limits)
    assert ok, compared
    ok, compared = compare.judge(d.gaps(first_steps["control"], first_steps["reference"]), limits)
    assert not ok, compared
    # both count the same kept keys, and fewer than causal attention's
    assert d.readings["attended"] == first_steps["reference"]["attended"]
    assert d.readings["attended"][0] < d.readings["attended"][1]


def test_adapter_mode_leaves_the_base_bit_equal(first_steps):
    import jax

    d = first_steps["driver"]
    before, after = (jax.tree_util.tree_leaves(first_steps[k]) for k in ("base", "after"))
    assert len(before) == len(after) > 0
    for a, b in zip(before, after):
        assert a.dtype == b.dtype and a.dtype.name == "bfloat16"
        np.testing.assert_array_equal(a, b)
    tr = d.trainer
    assert tr.n_params() == sum(x.size for x in before)  # the base, not the adapters
    assert all(x.dtype.name == "float32" for x in jax.tree_util.tree_leaves((tr.lora, tr.opt_state))
               if x.dtype.kind == "f")
    assert all(v > 0 for v in d.readings["change_norms"].values())


def test_adapter_spans_and_the_attended_keys_counter(first_steps, bench):
    from fedml_tpu.obs import trace as obstrace

    spans = obstrace.recent()
    by_id = {s.span_id: s for s in spans}
    (adapters,) = [s for s in spans if s.name == "llm.init.adapters"][-1:]
    assert by_id[adapters.parent_id].name == "llm.init"
    steps = [s for s in spans if s.name == "llm.step" and "sparse_kept" in s.attrs]
    assert len(steps) >= 4
    per_step = tuple(2 * x for x in bench["flops"].kept_keys(bench["config"], 64))
    assert {(s.attrs["sparse_kept"], s.attrs["sparse_causal"]) for s in steps} == {per_step}
    kept, causal = (obstrace.LLM_ATTENDED_KEYS.value(kind=k) - first_steps["attended_before"][k]
                    for k in ("kept", "causal"))
    assert kept >= len(steps) * per_step[0]
    assert abs(kept / causal - per_step[0] / per_step[1]) < 1e-6


def test_full_fine_tuning_is_still_the_default(bench):
    """``lora_rank=0``: every parameter trains in float32, as before, also on
    a hybrid model; the step reports what its sparse layer attended."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer

    from fedml_tpu.parallel import mesh as meshlib

    args = LLMTrainArgs(batch_size=1, seq_len=64, total_steps=4, warmup_steps=1)
    assert args.lora_rank == 0
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=jax.devices()[:1])
    tr = LLMTrainer(_tiny_cfg(bench), args, mesh=mesh)
    assert tr.lora is None and all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(tr.params))
    before = jax.tree_util.tree_map(np.asarray, tr.params)
    tok = np.random.default_rng(0).integers(0, 256, (1, 64), dtype=np.int32)
    hist = tr.fit(iter([(tok, np.roll(tok, -1, 1))] * 2), steps=2)
    assert len(hist) == 2 and hist[0]["sparse_kept"] < hist[0]["sparse_causal"]
    moved = [float(np.abs(np.asarray(a) - b).max()) for a, b in
             zip(jax.tree_util.tree_leaves(tr.params), jax.tree_util.tree_leaves(before))]
    assert min(moved) > 0


def test_gate_kernel_has_a_sharding_rule():
    from jax.sharding import PartitionSpec as P
    from fedml_tpu.parallel.sharding import partition_specs

    tree = {"layer_0": {"attn": {"wg": {"kernel": np.zeros((64, 4, 16))},
                                 "wq": {"kernel": np.zeros((64, 4, 16))}}}}
    specs = partition_specs(tree)
    assert specs["layer_0"]["attn"]["wg"]["kernel"] == specs["layer_0"]["attn"]["wq"]["kernel"] \
        == P("data", "model", None)


def test_fedllm_round_on_a_hybrid_model(bench, eight_devices):
    """``FedLLMSimulator`` builds the same ``Transformer`` from a configuration
    with mixer types and hands its base to the jitted client step as an
    argument: one round moves the adapters and nothing else."""
    import jax
    import fedml_tpu
    from fedml_tpu.arguments import Config
    from fedml_tpu.data import loader
    from fedml_tpu.llm.fedllm import FedLLMSimulator

    fcfg = Config(dataset="shakespeare", model="rnn", client_num_in_total=2, client_num_per_round=2,
                  comm_round=1, epochs=1, batch_size=4, learning_rate=5e-3, synthetic_train_size=16,
                  synthetic_test_size=8, partition_method="homo", frequency_of_the_test=0,
                  extra={"lora_r": 2})
    fedml_tpu.init(fcfg)
    ds = loader.load(fcfg)
    seq = ds.train_x.shape[1]
    tcfg = _tiny_cfg(bench, seq, vocab_size=ds.class_num, sparse_dense_len=seq // 2, loss_chunk=0)
    sim = FedLLMSimulator(fcfg, ds, tcfg)
    base = jax.tree_util.tree_map(np.asarray, sim.base_params)
    first = jax.tree_util.tree_map(np.asarray, sim.global_lora)
    out = sim.run_round()
    assert np.isfinite(out["train_loss"])
    for a, b in zip(jax.tree_util.tree_leaves(base), jax.tree_util.tree_leaves(sim.base_params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert any(float(np.abs(np.asarray(a) - b).max()) > 0 for a, b in
               zip(jax.tree_util.tree_leaves(sim.global_lora), jax.tree_util.tree_leaves(first)))
    # the base is an argument of the client step, not a constant closed into it
    x = ds.train_x[: sim._capacity]
    closed = jax.make_jaxpr(sim._make_client_step())(
        sim.global_lora, sim.base_params, x, x, np.int32(4), jax.random.PRNGKey(0))
    assert all(np.size(c) < 1024 for c in closed.consts)


def test_chunked_head_and_loss_is_the_whole_one(bench):
    """Given the targets the model returns the per-token loss, its head and
    softmax ``loss_chunk`` positions at a time: the same numbers as the loss
    of the whole logits matrix, and the same gradient."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax
    from fedml_tpu.models.transformer import Transformer

    cfg = dataclasses.replace(_tiny_cfg(bench, loss_chunk=16), dtype=jnp.float32,
                              logits_dtype=jnp.float32)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    params = jax.jit(lambda: model.init({"params": jax.random.PRNGKey(1)}, tokens)["params"])()

    def whole(p):
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(logits, targets).mean()

    def chunked(p):
        return model.apply({"params": p}, tokens, targets=targets).mean()

    (a, ga), (b, gb) = (jax.jit(jax.value_and_grad(f))(params) for f in (whole, chunked))
    np.testing.assert_allclose(a, b, rtol=1e-6)
    for x, y in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(x, y, atol=1e-6)


def test_required_work_counts(bench):
    """The parameter counts ISSUE 29 cut the configuration with (253.8M,
    285.2M, 601.7M, 1,711.1M), the 987,136 adapter parameters, and the two
    sums of a step's work agreeing."""
    bench["flops"].check()
    c = bench["full_config"]
    assert {k: c[k] for k in ("hidden_size", "intermediate_size", "vocab_size", "head_dim",
                              "num_attention_heads", "num_key_value_heads", "lightning_nh",
                              "lightning_head_dim")} == {
        "hidden_size": 4096, "intermediate_size": 16384, "vocab_size": 73448, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 2, "lightning_nh": 32,
        "lightning_head_dim": 128}
