"""Kimi-Linear's language model through the normal path: Kimi Delta Attention
(the gated delta rule with a decay per channel, ``ops/kda.py``) beside latent
attention without positions and a direct query, and sigmoid-routed expert
layers that choose by a selection bias, of which one expert-parallel rank is
held, against the plain reference (``benchmark/ref_kimi.py``), adapter
fine-tuning over a frozen base in ``LLMTrainer``, and the chip's compiler at
the cell's size.

Tiny sizes (the configuration's ``rehearsal``: hidden 64, KDA's 4 heads of
16, MLA's 4 heads of 16 + 8, 32 experts of which 8 are held, 4 a token, the
published five layers, sequences of 64 in KDA chunks of 32).
"""

import contextlib
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "kimi_linear_48b_a3b_d5_ep4.lora_16k"
JOB = {"lora_rank": 4, "lora_alpha": 8.0}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (it keeps them importable by bare name) and
    the cell's files at their rehearsal sizes."""
    sys.path.insert(0, BENCH)
    try:
        import compare
        import flops_kimi
        import kimi
        import ref_kimi
        from run import load_json

        config = load_json(BENCH, "configs", "kimi_linear_48b_a3b_d5_ep4.json")
        traffic = load_json(BENCH, "traffic", "lora_sft_16k_kda_b1.json")
        limits = load_json(BENCH, "limits", CELL + ".json")
        yield {"compare": compare, "flops": flops_kimi, "ref": ref_kimi, "kimi": kimi,
               "config": {**config, **config["rehearsal"]}, "full_config": config,
               "traffic": {**traffic, **traffic["rehearsal"]}, "full_traffic": traffic, "limits": limits}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """64 tokens in two KDA chunks of two sub-blocks each."""
    from fedml_tpu.ops import kda

    monkeypatch.setattr(kda, "CHUNK", 32)


def _cfg(bench, seq=64, config=None, **kw):
    """The tiny model in float32, so that it differs from the reference by
    the order of its sums alone."""
    import jax.numpy as jnp

    cfg = bench["kimi"].transformer_config(config or bench["config"], seq, "full",
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


# -- the chunked gated delta rule ------------------------------------------------------
def _rule_inputs(seed, s, band):
    """Random L2-normed q, k, values, log-decays per channel whose exp spans
    ``band`` (the fastest channel's decay a token, the slowest's) and
    write strengths in (0, 1), float32, 2 rows of 3 heads of 16."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    shape = (2, s, 3, 16)
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
        out = fn(*inputs)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * probe), argnums=(0, 1, 2, 3, 4))(*inputs)
    return out, grads


#: (tokens, chunk, decay band): several whole chunks with sub-blocks; a tail
#: the chunk does not divide; a chunk under one sub-block; a band from
#: nearly no decay (the state carries far) to a decay of 1e-4 a token
RULE_CASES = [(128, 64, (0.05, 0.999)), (150, 32, (0.5, 0.9999)), (40, 8, (1e-4, 0.99))]


@pytest.mark.parametrize("s,chunk,band", RULE_CASES)
def test_chunked_rule_is_the_token_recurrence(s, chunk, band):
    """Forward and the gradient to each of q, k, v, g, beta, in float32: the
    chunks reorder sums of products of at most one, so 2e-5 of the largest
    entry holds (one float32 rounding of a sum over a few hundred terms);
    the form that drops the state between chunks is 100 times further off."""
    from fedml_tpu.ops.kda import kda, kda_recurrent

    inputs = _rule_inputs(3, s, band)
    out, grads = _rule_and_grads(lambda *a: kda(*a, chunk=chunk), inputs)
    want, want_grads = _rule_and_grads(kda_recurrent, inputs)
    _close(out, want)
    for got, ref in zip(grads, want_grads):
        _close(got, ref)


def test_the_form_without_the_carried_state_fails_that_tolerance():
    """Each chunk from a zero state (what a pass that dropped ``S`` would
    give) is far outside the tolerance the chunked form meets, with a band
    in which the state carries across chunks."""
    import jax.numpy as jnp
    from fedml_tpu.ops.kda import kda, kda_recurrent

    s, chunk, band = RULE_CASES[0]
    inputs = _rule_inputs(3, s, band)
    cut = lambda *a: jnp.concatenate([kda(*(t[:, i: i + chunk] for t in a), chunk=chunk)
                                      for i in range(0, s, chunk)], axis=1)
    out, grads = _rule_and_grads(cut, inputs)
    want, want_grads = _rule_and_grads(kda_recurrent, inputs)
    gap = float(np.abs(np.asarray(out) - np.asarray(want)).max()) / float(np.abs(want).max())
    assert gap > 100 * 2e-5, gap
    with pytest.raises(AssertionError):
        for got, ref in zip(grads, want_grads):
            _close(got, ref)


def test_strong_decay_stays_finite():
    """A log-decay of -200 a channel and token (exp of it underflows to 0):
    nothing is divided by a decay and no exponent is positive, so the
    chunked form and its gradients stay finite and agree."""
    import jax.numpy as jnp
    from fedml_tpu.ops.kda import kda, kda_recurrent

    q, k, v, g, beta = _rule_inputs(4, 96, (0.05, 0.999))
    g = g.at[:, 40:60].set(-200.0)
    out, grads = _rule_and_grads(lambda *a: kda(*a, chunk=32), (q, k, v, g, beta))
    want, _ = _rule_and_grads(kda_recurrent, (q, k, v, g, beta))
    assert all(bool(jnp.isfinite(t).all()) for t in (out, *grads))
    _close(out, want)


# -- the three mixers' new forms ---------------------------------------------------------
def test_kda_mixer_is_the_reference(bench, monkeypatch):
    """The module (projections, three short convolutions, L2 norms, gates,
    the chunked rule, the gated output norm) against the reference's
    token-by-token layer, at a chunk that does not divide 64 tokens; it sows
    the mean log-decay a chunk lets through."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops import kda
    from fedml_tpu.ops.kda import chunk_decay

    monkeypatch.setattr(kda, "CHUNK", 24)
    w, tree = _weights(bench)
    x = _x(2)
    m = bench["ref"].parts(w, {}, bench["config"], JOB)
    with jax.default_matmul_precision("highest"):
        y, sown = tfm.KDA(_cfg(bench)).apply({"params": tree["layer_1"]["attn"]}, x[None],
                                                            None, mutable=["stats"])
        want = m["kda"](x, "layer_1/attn/")
    _close(y[0], want)
    p = {k: jnp.asarray(v) for k, v in w.items() if k.startswith("layer_1/attn/")}
    f = x @ p["layer_1/attn/wf_a/kernel"] @ p["layer_1/attn/wf_b/kernel"].reshape(16, 64)
    g = -jnp.exp(p["layer_1/attn/A_log"]).repeat(16) * jax.nn.softplus(f + p["layer_1/attn/dt_bias"])
    # the sum of g over the tokens, over (3 chunks x 4 heads x 16 channels)
    np.testing.assert_allclose(float(sown["stats"]["kda_chunk_decay"]), float(jnp.sum(g)) / (3 * 64), rtol=1e-5)
    assert float(chunk_decay(g.reshape(1, 64, 4, 16), 24)) == pytest.approx(float(jnp.sum(g)) / (3 * 64), rel=1e-6)


def test_mla_without_positions_and_with_a_direct_query_is_the_reference(bench):
    """``q_lora_rank`` 0 and ``mla_use_nope``: one ``wq`` and no RoPE, the
    reference's latent attention; positions then do not matter to the
    rotary part, and the low-rank query's leaves are not made."""
    import jax
    from fedml_tpu.models import transformer as tfm

    w, tree = _weights(bench)
    x = _x(3)
    cfg = _cfg(bench)
    params = tree["layer_3"]["attn"]
    assert sorted(params) == ["kv_a_norm", "wkv_a", "wkv_b", "wo", "wq"]
    m = bench["ref"].parts(w, {}, bench["config"], JOB)
    with jax.default_matmul_precision("highest"):
        y = tfm.MLAttention(cfg).apply({"params": params}, x[None], np.arange(64)[None])
        y_shifted = tfm.MLAttention(cfg).apply({"params": params}, x[None], 1000 + np.arange(64)[None])
        want = m["mla"](x, "layer_3/attn/")
    _close(y[0], want)
    np.testing.assert_array_equal(y, y_shifted)


def test_biased_router_is_the_reference_and_its_gates_the_unbiased_scores(bench):
    """The choice is the top 4 of ``s + b`` and the gates ``2.446 s / sum s``
    of the chosen: a bias that favours expert 3 puts it in every token's
    choice, and its gates are the unbiased scores', renormalised."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.moe import route

    z = bench["ref"].sizes(bench["config"])
    x = _x(6)
    w_r = jax.random.normal(jax.random.PRNGKey(7), (64, 32)) / 8
    bias = jnp.zeros(32).at[3].set(2.0).at[7].set(-0.01)
    with jax.default_matmul_precision("highest"):
        idx, gates, counts = route(x, w_r, z["k"], z["scale"], z["norm"], "sigmoid", bias)
        plain_idx, _, _ = route(x, w_r, z["k"], z["scale"], z["norm"], "sigmoid")
        ref_idx, ref_gates = bench["ref"].route(x, w_r, bias, z)
    order = jnp.argsort(ref_idx, -1)
    np.testing.assert_array_equal(idx, jnp.take_along_axis(ref_idx, order, -1))
    _close(gates, jnp.take_along_axis(ref_gates, order, -1), 1e-6)
    assert int(counts[3]) == 64 and (np.asarray(idx) != np.asarray(plain_idx)).any()
    s = jax.nn.sigmoid(x @ w_r)
    chosen = jnp.take_along_axis(s, idx, -1)
    _close(gates, z["scale"] * chosen / chosen.sum(-1, keepdims=True), 1e-6)


def test_biased_router_orders_negative_sums(bench):
    """Every bias below -1 makes every ``s + b`` negative: the choice is still
    the reference's top 4 of them (bits compared as plain non-negative floats
    would choose none), each token gets 4 distinct held experts, and the
    gates are the unbiased scores'."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.moe import route

    z = bench["ref"].sizes(bench["config"])
    x = _x(6)
    w_r = jax.random.normal(jax.random.PRNGKey(7), (64, 32)) / 8
    bias = -1.5 - jax.random.uniform(jax.random.PRNGKey(8), (32,)) * 0.05
    with jax.default_matmul_precision("highest"):
        idx, gates, counts = route(x, w_r, z["k"], z["scale"], z["norm"], "sigmoid", bias)
        ref_idx, ref_gates = bench["ref"].route(x, w_r, bias, z)
    order = jnp.argsort(ref_idx, -1)
    np.testing.assert_array_equal(idx, jnp.take_along_axis(ref_idx, order, -1))
    _close(gates, jnp.take_along_axis(ref_gates, order, -1), 1e-6)
    assert int(counts.sum()) == 64 * z["k"] and int(idx.max()) < 32
    assert all(len(set(row)) == z["k"] for row in np.asarray(idx).tolist())


def test_the_four_shares_add_up(bench):
    """32 experts in 4 shares of 8: what the four expert-parallel ranks'
    layers give, with the shared expert counted once, adds up to the uncut
    reference's whole layer; every rank routes every token over all 32 by
    the same biased choice."""
    import jax
    from fedml_tpu.models import transformer as tfm

    whole_c = {**bench["config"], "num_experts": 32}
    w, tree = _weights(bench, config=whole_c)
    assert w["layer_1/moe/experts/w_gate"].shape[0] == 32
    x = _x(8)
    m = bench["ref"].parts(w, {}, whole_c, JOB)
    moe = tree["layer_1"]["moe"]
    with jax.default_matmul_precision("highest"):
        whole, on_held = m["moe"](x, "layer_1/moe/")
        shared = tfm.MLP(_cfg(bench), 32).apply({"params": moe["shared"]}, x[None])[0]
        total = shared
        for rank in range(4):
            cfg = _cfg(bench, experts_held=8, first_expert=8 * rank)
            params = {**moe, "experts": {k: v[8 * rank: 8 * rank + 8] for k, v in moe["experts"].items()}}
            y, sown = tfm.MoE(cfg).apply({"params": params}, x[None], mutable=["stats"])
            total = total + y[0] - shared
            assert float(sown["stats"]["moe_assignments"]) == 64 * 4
    assert float(on_held.sum()) == 64 * 4          # uncut: every assignment is held
    _close(total, whole)


def test_selection_biases_even_the_load_of_their_calibration_row(bench):
    """``balanced_biases`` leaves each expert layer's load on its calibration
    row within a few tokens of even, where the biases as drawn leave it
    uneven (random weights' hidden states share a direction that each router
    reads as an offset per expert)."""
    import jax
    import jax.numpy as jnp

    ref, c = bench["ref"], bench["config"]
    z = ref.sizes(c)
    w = ref.init_weights(c, 5, dtype=jnp.float32)
    tok, _ = ref.batch_tokens(5, 2 ** 31 - 1, 1, c["calibration_tokens"], c["vocab_size"])
    m = ref.parts(w, {}, c, JOB)
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(w["embed/embedding"])[tok[0]]
        for p, kind, experts in bench["flops"].layers(c):
            h = h + m[kind](m["norm"](h, p + "attn_norm/scale"), p + "attn/")
            x = m["norm"](h, p + "mlp_norm/scale")
            if experts:
                bias = w[p + "moe/router/e_score_correction_bias"]
                s = jax.nn.sigmoid(x @ w[p + "moe/router/kernel"])
                load = lambda b: np.bincount(np.asarray(jax.lax.top_k(s + b, z["k"])[1]).ravel(), minlength=32)
                assert bias.std() > 3 * ref.BIAS_STD
                spread = lambda b: int(load(b).max() - load(b).min())
                assert spread(bias) <= 8 < spread(0 * bias)
                h = h + m["moe"](x, p + "moe/")[0]
            else:
                h = h + m["swiglu"](x, p + "mlp/")


# -- the whole model -------------------------------------------------------------------
def test_model_loss_is_the_reference(bench):
    """Five layers (KDA with the dense SwiGLU, then KDA, KDA, MLA, KDA with
    expert layers), the head and the loss a chunk at a time, in float32: the
    reference's loss and its held assignments by layer."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models.transformer import Transformer

    ref, c = bench["ref"], bench["config"]
    cfg = _cfg(bench)
    assert cfg.mixer_types == ("kda", "kda", "kda", "mla", "kda") and cfg.first_k_dense == 1
    w, tree = _weights(bench)
    tok, tgt = ref.batch_tokens(4, 0, 1, 64, c["vocab_size"])
    with jax.default_matmul_precision("highest"):
        losses, sown = jax.jit(lambda p: Transformer(cfg).apply(
            {"params": p}, tok, targets=tgt, mutable=["stats"]))(tree)
        want, held = jax.jit(lambda w: ref.row_loss(
            w, {}, jnp.asarray(tok[0]), jnp.asarray(tgt[0]), c, JOB))(w)
    np.testing.assert_allclose(losses.mean(), want, rtol=2e-5)
    stats = sown["stats"]
    assert float(held[0]) == 0 and "moe" not in stats["layer_0"]
    for i in range(1, 5):
        assert float(stats[f"layer_{i}"]["moe"]["moe_held"]) == float(held[i])
    assert "attn" not in stats["layer_3"] and "kda_chunk_decay" in stats["layer_4"]["attn"]


@pytest.fixture(scope="module")
def first_steps(bench):
    """The cell's driver at rehearsal sizes, in process: ``fit``'s first three
    steps and the float32 reference's, with the float8 control."""
    import jax
    from fedml_tpu.ops import kda

    driver = bench["kimi"].Driver({"name": CELL, "chips": 1}, bench["config"], bench["traffic"], 7,
                                  jax.devices()[:1])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda, "CHUNK", 32)
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
    float8 control by at least one number."""
    compare, limits = bench["compare"], bench["limits"]["rehearsal"]
    d, ref = first_steps["driver"], first_steps["reference"]
    ok, compared = compare.judge(d.gaps(d.readings, ref), limits)
    assert ok, compared
    ok, compared = compare.judge(d.gaps(first_steps["control"], ref), limits)
    assert not ok, compared
    assert len(ref["held_by_block"]) == 5 and ref["held_by_block"][0] == 0
    assert abs(d.readings["held_in_step"] - sum(ref["held_by_block"])) <= 4
    assert d.readings["attention_sites"] == {"kernel": 0, "blockwise": 1}


def test_adapter_mode_leaves_the_base_bit_equal(first_steps):
    import jax

    d = first_steps["driver"]
    before, after = (jax.tree_util.tree_leaves(first_steps[k]) for k in ("base", "after"))
    assert len(before) == len(after) > 0
    for a, b in zip(before, after):
        assert a.dtype == b.dtype and a.dtype.name == "bfloat16"
        np.testing.assert_array_equal(a, b)
    want = ({f"layer_{i}/attn/w{n}/kernel" for i in (0, 1, 2, 4) for n in "qkvo"}
            | {f"layer_3/attn/w{n}/kernel" for n in ("q", "kv_a", "kv_b", "o")})
    assert set(d.trainer.lora) == want
    assert all(v > 0 for v in d.readings["change_norms"].values())


def test_kda_spans_attributes_and_counter(first_steps):
    """``llm.step`` carries the KDA layers' ``kda_chunk_decay`` (four layers'
    means of the log-decay a chunk lets through, each below 0), and the step
    program's device ops sit under the new scopes."""
    from fedml_tpu.obs import scopes
    from fedml_tpu.obs import trace as obstrace

    steps = [s for s in obstrace.recent() if s.name == "llm.step" and "kda_chunk_decay" in s.attrs]
    assert len(steps) >= 4 and all(s.attrs["kda_chunk_decay"] < 0 for s in steps)
    assert all(0 < s.attrs["moe_held"] < s.attrs["moe_assignments"] == 4 * 64 * 4 for s in steps)
    assert first_steps["driver"].readings["kda_chunk_decay"] < 0
    names = {s for e in (scopes.scope_map("llm.step") or {}).values() for s in e["scopes"]}
    for scope in ("llm.mixer.kda", "llm.mixer.kda.conv", "llm.mixer.kda.gate", "llm.mixer.kda.core",
                  "llm.mixer.mla", "llm.moe.experts", "llm.moe.router"):
        assert scope in names, sorted(names)


def test_every_new_leaf_has_a_rule_and_no_gate_takes_an_adapter(bench):
    """Every leaf of the model is named by a sharding rule; the job's targets
    reach KDA's and MLA's projections and nothing of the gates."""
    import re

    import jax
    from fedml_tpu.llm import lora as lora_lib
    from fedml_tpu.models.transformer import Transformer
    from fedml_tpu.parallel.sharding import TRANSFORMER_RULES

    params = jax.eval_shape(lambda: Transformer(_cfg(bench)).init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 64), np.int32)))["params"]
    flat = bench["compare"].flat(params)
    assert "layer_1/moe/router/e_score_correction_bias" in flat and "layer_0/attn/conv_q" in flat
    assert all(any(re.fullmatch(pattern, path) for pattern, _ in TRANSFORMER_RULES) for path in flat), \
        [p for p in flat if not any(re.fullmatch(pattern, p) for pattern, _ in TRANSFORMER_RULES)]
    lora = jax.eval_shape(lambda: lora_lib.init_lora(params, 4, jax.random.PRNGKey(1),
                                                     bench["full_traffic"]["train_args"]["lora_targets"]))
    assert len(lora) == 4 * 4 + 4 and not [p for p in lora if re.search(r"/w(f|g|beta)", p)]


# -- the yardstick ---------------------------------------------------------------------
def test_required_work_counts_and_published_widths(bench):
    """The counts the configuration was cut with, and every number of the
    catalog's config as published but the three cuts ``reduced`` names."""
    import json

    bench["flops"].check()
    c = bench["full_config"]
    assert c["source"] == ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/"
                           "config.json")
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512, "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024, "moe_layer_freq": 1,
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_topk": True, "v_head_dim": 128, "vocab_size": 163840,
        "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                               "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
                                              23, 25, 26],
                               "num_heads": 32, "short_conv_kernel_size": 4}}
    differ = {k for k, v in published.items() if c.get(k, "absent") != v}
    assert differ == set(c["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}, differ
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (5, 64, 40960)
    assert c["router_experts"] == c["published"]["num_experts"] == 256
    assert c["vocab_size"] * 4 == c["published"]["vocab_size"]
    tcfg = bench["kimi"].transformer_config(c, 16384, **bench["full_traffic"]["program"])
    assert (tcfg.n_routed_experts, tcfg.experts_held, tcfg.top_k, tcfg.router_bias) == (256, 64, 8, True)
    assert (tcfg.kda_heads, tcfg.kda_head_dim, tcfg.kda_conv) == (32, 128, 4)
    assert (tcfg.q_lora_rank, tcfg.mla_use_nope, tcfg.routed_scaling_factor) == (0, True, 2.446)
    with open(os.path.join(BENCH, "traffic", "lora_sft_16k_kda_b1.json")) as fh:
        assert json.load(fh)["seq_len"] == 16384


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


@pytest.mark.parametrize("kind,limit_gb", [("kda", 6.0), ("mla", 2.5)])
def test_mixer_compiles_for_the_chip_at_the_cells_size(kind, limit_gb, bench, one_chip, monkeypatch):
    """One KDA and one latent-attention mixer at the cell's widths and 16,384
    tokens, forward and the gradient to its input under the block's remat:
    the chip's compiler takes each on the path one TPU device runs, KDA's
    chunked pass as the fused kernel pair of ``ops/pallas/kda.py`` (in the
    cell's chunks of 64) and the latent one on the flash kernel, each within
    its temporaries' bound (KDA's 4.37 GB: the layer's float32 convolutions
    and gates and the kernel's saved entry states, 512 MB; 5.67 GB on the
    ``lax`` scan, whose HIGHEST-precision products split their operands)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops import kda

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kda, "CHUNK", bench["flops"].CHUNK)     # the cell's chunk, as its work is counted
    cfg = bench["kimi"].transformer_config(bench["full_config"], 16384)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x, pos = spec((1, 16384, 2304), jnp.bfloat16), spec((1, 16384), jnp.int32)
    mixer = tfm.MIXERS[kind](cfg)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype),
                                               jnp.zeros(pos.shape, pos.dtype)))["params"]
    params = jax.tree_util.tree_map(lambda p: spec(p.shape, jnp.bfloat16), params)
    apply = lambda p, x, pos: mixer.apply({"params": p}, x, pos, mutable=["stats"])[0]
    remat = lambda p, x, pos: jax.checkpoint(lambda p, x: apply(p, x, pos),
                                             policy=tfm.block_remat_policy(cfg))(p, x)
    loss = lambda p, x, pos: jnp.sum(remat(p, x, pos).astype(jnp.float32))
    with _no_compile_cache():
        compiled = jax.jit(jax.grad(loss, argnums=1)).lower(params, x, pos).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and (("fedml_kda_bwd" in text) == (kind == "kda"))
    assert compiled.memory_analysis().temp_size_in_bytes < limit_gb * 1e9
