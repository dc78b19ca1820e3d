"""openPangu-Ultra-MoE through the normal path: latent attention, the expert
layer as one expert-parallel rank runs it, sandwich norms and the MTP module
against the plain reference (``benchmark/ref_pangu.py``), adapter fine-tuning
over a frozen base in ``LLMTrainer``, and the federated adapter round.

Tiny sizes (the configuration's ``rehearsal``: hidden 64, 4 heads of 16 | 8
with 16-wide values, latents 32 and 16, 32 experts of which 8 are held, 4 a
token, one dense and one expert layer and the MTP module, sequences of 64).
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "pangu_ultra_moe_d5_ep32.lora_8k"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules (it keeps them importable by bare name) and
    the cell's files at their rehearsal sizes."""
    sys.path.insert(0, BENCH)
    try:
        import compare
        import flops_pangu
        import pangu
        import ref_pangu
        from run import load_json

        config = load_json(BENCH, "configs", "pangu_ultra_moe_d5_ep32.json")
        traffic = load_json(BENCH, "traffic", "lora_sft_8k_b1.json")
        limits = load_json(BENCH, "limits", CELL + ".json")
        yield {"compare": compare, "flops": flops_pangu, "ref": ref_pangu, "pangu": pangu,
               "config": {**config, **config["rehearsal"]}, "full_config": config,
               "traffic": {**traffic, **traffic["rehearsal"]}, "limits": limits}
    finally:
        sys.path.remove(BENCH)


def _cfg(bench, seq=64, **kw):
    """The tiny model in float32, so that it differs from the reference by
    the order of its sums alone."""
    import jax.numpy as jnp

    cfg = bench["pangu"].transformer_config(bench["config"], seq, "full",
                                            **{**bench["traffic"]["program"], **kw})
    return dataclasses.replace(cfg, dtype=jnp.float32, logits_dtype=jnp.float32)


def _weights(bench, seed=5, config=None):
    """The reference's float32 draw of the base, flat and as the program's tree."""
    import jax.numpy as jnp
    from flax import traverse_util

    w = bench["ref"].init_weights(config or bench["config"], seed, dtype=jnp.float32)
    return w, traverse_util.unflatten_dict(w, sep="/")


JOB = {"lora_rank": 4, "lora_alpha": 8.0, "mtp_weight": 0.3}


def _x(seed, s=64, d=64):
    import jax

    return jax.random.normal(jax.random.PRNGKey(seed), (s, d))


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(got, want, atol=tol * max(1.0, float(np.abs(want).max())))


# -- latent attention --------------------------------------------------------------
@pytest.mark.parametrize("chunk,group", [(64, 32), (16, 32), (16, 2)])
def test_latent_attention_is_the_reference(chunk, group, bench, monkeypatch):
    """``MLAttention`` (two low-rank paths with their norms, a rotary key all
    heads share, 24-wide queries and keys over 16-wide values, blockwise, the
    heads in groups) against the reference's full rows of scores: output and
    the gradient to the input, in one chunk and in several."""
    import jax
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops import sparse_attention

    monkeypatch.setattr(sparse_attention, "CHUNK", chunk)
    monkeypatch.setattr(tfm, "MLA_HEAD_GROUP", group)
    monkeypatch.setattr(bench["ref"], "HEAD_BLOCK", group)   # the reference's own blocks of heads
    cfg = _cfg(bench)
    assert cfg.v_head_dim != cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    w, tree = _weights(bench)
    x = _x(0)
    pos = np.arange(64)[None]
    m = bench["ref"].parts(w, {}, bench["config"], JOB)
    probe = _x(1)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(lambda x: (tfm.MLAttention(cfg).apply(
            {"params": tree["layer_0"]["attn"]}, x[None], pos)[0] * probe).sum()))(x)
        want, want_g = jax.jit(jax.value_and_grad(lambda x: (m["mla"](x, "layer_0/attn/") * probe).sum()))(x)
    _close(got, want)
    _close(got_g, want_g)


def test_block_attention_takes_values_of_their_own_width(bench):
    """``block_sparse_attention`` with 24-wide keys and 16-wide values under a
    block mask that drops blocks: a plain masked softmax's output and three
    gradients."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.sparse_attention import block_sparse_attention

    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 64, h, d))
               for i, (h, d) in enumerate(((4, 24), (2, 24), (2, 16))))
    keep = jax.random.bernoulli(jax.random.fold_in(key, 9), 0.6, (1, 2, 64, 8))
    keep = keep | (jnp.arange(8)[None, :] == (jnp.arange(64) // 8)[:, None])   # a query's own block

    def plain(q, k, v):
        mask = jnp.repeat(keep, 8, axis=-1) & (jnp.arange(64)[:, None] >= jnp.arange(64)[None, :])
        logits = jnp.einsum("bqkgd,btkd->bkgqt", q.reshape(1, 64, 2, 2, 24), k) * 0.3
        p = jax.nn.softmax(jnp.where(mask[:, :, None], logits, -jnp.inf), -1)
        return jnp.einsum("bkgqt,btkd->bqkgd", p, v).reshape(1, 64, 4, 16)

    def blockwise(q, k, v):
        return block_sparse_attention(q, k, v, keep, block_size=8, q_chunk=16, k_chunk=16, scale=0.3)

    probe = jax.random.normal(jax.random.fold_in(key, 7), (1, 64, 4, 16))
    with jax.default_matmul_precision("highest"):
        got, want = (jax.value_and_grad(lambda *a: jnp.sum(f(*a) * probe), argnums=(0, 1, 2))(q, k, v)
                     for f in (blockwise, plain))
    _close(got[0], want[0])
    for a, b in zip(got[1], want[1]):
        _close(a, b)


# -- the expert layer ----------------------------------------------------------------
def _expert_kernels(seed, held=8, d=64, f=32):
    import jax

    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(jax.random.fold_in(key, i), shape) / np.sqrt(shape[1])
            for i, shape in enumerate(((held, d, f), (held, d, f), (held, f, d)))]


def _routing(case: str, t=64, k=4, first=8, held=8, n=32):
    """(idx (t, k) ascending, gates (t, k)) of one planted case."""
    rng = np.random.default_rng(33)
    gates = rng.random((t, k), dtype=np.float32) + 0.1
    if case == "random":
        idx = np.stack([np.sort(rng.choice(n, k, replace=False)) for _ in range(t)])
    elif case == "every_token_to_one_held_expert":   # expert first + 2, and three absent ones
        idx = np.tile(np.array([0, 1, first + 2, n - 1]), (t, 1))
    elif case == "every_token_to_four_held_experts":
        idx = np.tile(first + np.array([0, 3, 4, 7]), (t, 1))
    elif case == "no_token_held":
        idx = np.stack([np.sort(rng.choice(first, k, replace=False)) for _ in range(t)])
    else:
        raise ValueError(case)
    return idx.astype(np.int32), gates


@pytest.mark.parametrize("rows", [0, 8, 24])
@pytest.mark.parametrize("case", ["random", "every_token_to_one_held_expert",
                                  "every_token_to_four_held_experts", "no_token_held"])
def test_held_experts_part_is_exact_for_any_routing(case, rows, bench):
    """``expert_ffn`` against the reference's loop over the held experts under
    a 0/1 mask: the output and the gradients to the activations, the gates and
    the kernels, whatever the routing and however many rounds it takes (64
    tokens on one expert in rounds of 8 rows: eight rounds; no token held: none)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.moe import expert_ffn

    x, probe, w = _x(2), _x(3), _expert_kernels(4)
    idx, gates = _routing(case)
    with jax.default_matmul_precision("highest"):
        got, want = (jax.value_and_grad(lambda x, g, *w: jnp.sum(f(x, g, *w) * probe), argnums=(0, 1, 2, 3, 4))(
            x, jnp.asarray(gates), *w) for f in (
            lambda x, g, *w: expert_ffn(x, idx, g, *w, first_expert=8, rows=rows),
            lambda x, g, *w: bench["ref"].held_part(x, idx, g, *w, 8)))
    _close(got[0], want[0])
    for a, b in zip(got[1], want[1]):
        _close(a, b)
    if case == "no_token_held":
        assert float(got[0]) == 0.0 and all(float(jnp.abs(a).max()) == 0.0 for a in got[1])


def test_router_is_the_reference(bench):
    """Sigmoid scores over all 32, the 4 best without a sort, normalised gates
    times the scaling factor, ties to the lower index."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.moe import route

    z = bench["ref"].sizes(bench["config"])
    x = _x(6)
    w_r = jax.random.normal(jax.random.PRNGKey(7), (64, 32)) / 8
    w_r = w_r.at[:, 5].set(w_r[:, 2])            # experts 2 and 5 always tie
    with jax.default_matmul_precision("highest"):
        idx, gates, counts = route(x, w_r, z["k"], z["scale"], z["norm"])
        ref_idx, ref_gates = bench["ref"].route(x, w_r, z)
    order = jnp.argsort(ref_idx, -1)
    np.testing.assert_array_equal(idx, jnp.take_along_axis(ref_idx, order, -1))
    _close(gates, jnp.take_along_axis(ref_gates, order, -1), 1e-6)
    np.testing.assert_array_equal(counts, np.bincount(np.asarray(idx).ravel(), minlength=32))
    assert int(counts.sum()) == 64 * 4 and int(counts[2]) >= int(counts[5])
    _close(gates.sum(-1), np.full(64, 2.5), 1e-6)


def test_the_shares_add_up(bench):
    """32 experts in 4 shares of 8: what the four expert-parallel ranks'
    layers give, with the shared expert (which every rank computes alike)
    counted once, is the uncut reference's whole layer."""
    import jax
    from fedml_tpu.models import transformer as tfm

    ref = bench["ref"]
    whole_c = {**bench["config"], "n_routed_experts": 32}
    w, tree = _weights(bench, config=whole_c)
    assert w["layer_1/moe/experts/w_gate"].shape[0] == 32
    x = _x(8)
    m = ref.parts(w, {}, whole_c, JOB)
    with jax.default_matmul_precision("highest"):
        whole, on_held = m["moe"](x, "layer_1/moe/")
        shared = m["swiglu"](x, "layer_1/moe/shared/")
        total = 0.0
        for rank in range(4):
            cfg = _cfg(bench, experts_held=8, first_expert=8 * rank)
            params = {**tree["layer_1"]["moe"], "experts": {
                k: v[8 * rank: 8 * rank + 8] for k, v in tree["layer_1"]["moe"]["experts"].items()}}
            y, sown = tfm.MoE(cfg).apply({"params": params}, x[None], mutable=["stats"])
            total = total + (y[0] - shared)
            assert float(sown["stats"]["moe_assignments"]) == 64 * 4
    assert float(on_held.sum()) == 64 * 4          # uncut: every assignment is held
    _close(total + shared, whole)


# -- the block, the MTP module and the loss ------------------------------------------
@pytest.mark.parametrize("experts", [False, True])
def test_sandwich_block_is_the_reference(experts, bench):
    import jax
    from fedml_tpu.models import transformer as tfm

    cfg = _cfg(bench)
    w, tree = _weights(bench)
    layer = "layer_1" if experts else "layer_0"
    x = _x(9)
    m = bench["ref"].parts(w, {}, bench["config"], JOB)
    with jax.default_matmul_precision("highest"):
        got, sown = tfm.Block(cfg, mixer="mla", experts=experts).apply(
            {"params": tree[layer]}, x[None], np.arange(64)[None], mutable=["stats"])
        want, held = m["block"](x, layer + "/", experts)
    _close(got[0], want)
    assert "post_attn_norm" in tree[layer] and "post_mlp_norm" in tree[layer]
    assert float(sown.get("stats", {}).get("moe", {}).get("moe_held", 0.0)) == float(held)


def test_mtp_loss_is_the_reference_and_masks_the_last_position(bench):
    """Given the targets the model returns both losses per position, head and
    softmax a chunk at a time: the numbers and gradients of the whole logits
    matrix, the reference's two means, and nothing at the last position."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models.transformer import Transformer

    ref, c = bench["ref"], bench["config"]
    w, tree = _weights(bench)
    tok, tgt = ref.batch_tokens(4, 0, 1, 64, c["vocab_size"])

    def losses(cfg, p):
        main, after_next = Transformer(cfg).apply({"params": p}, tok, targets=tgt)
        return main.mean() + 0.3 * after_next.sum() / 63, (main, after_next)

    with jax.default_matmul_precision("highest"):
        (a, (main, after_next)), ga = jax.jit(jax.value_and_grad(
            lambda p: losses(_cfg(bench, loss_chunk=16), p), has_aux=True))(tree)
        (b, _), gb = jax.jit(jax.value_and_grad(
            lambda p: losses(_cfg(bench, loss_chunk=0), p), has_aux=True))(tree)
        want, (want_mtp, _) = jax.jit(lambda w: ref.row_loss(
            w, {}, jnp.asarray(tok[0]), jnp.asarray(tgt[0]), c, JOB))(w)
    assert main.shape == after_next.shape == (1, 64) and float(after_next[0, -1]) == 0.0
    assert float(after_next[0, :-1].min()) > 0.0
    np.testing.assert_allclose(a, b, rtol=1e-6)
    for x, y in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)):
        _close(x, y, 1e-5)
    np.testing.assert_allclose(a, want, rtol=2e-5)
    np.testing.assert_allclose(after_next.sum() / 63, want_mtp, rtol=2e-5)
    # without the targets the module is not run: logits only
    assert Transformer(_cfg(bench)).apply({"params": tree}, tok).shape == (1, 64, c["vocab_size"])


# -- the trainer ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def first_steps(bench):
    """The cell's driver at rehearsal sizes, in process: ``fit``'s first three
    steps and the float32 reference's, with the float8 control."""
    import jax
    from fedml_tpu.ops import sparse_attention

    cell = {"name": CELL, "chips": 1}
    driver = bench["pangu"].Driver(cell, bench["config"], bench["traffic"], 11, jax.devices()[:1])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sparse_attention, "CHUNK", 16)   # 64 tokens in four chunks
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
    d = first_steps["driver"]
    ok, compared = compare.judge(d.gaps(d.readings, first_steps["reference"]), limits)
    assert ok, compared
    ok, compared = compare.judge(d.gaps(first_steps["control"], first_steps["reference"]), limits)
    assert not ok, compared
    # the step's own count is the sum of the blocks', and 3 blocks' worth of 64 x 4 at most
    assert d.readings["held_in_step"] == sum(d.readings["held_by_block"])
    assert len(d.readings["held_by_block"]) == len(first_steps["reference"]["held_by_block"]) == 2
    assert 0 < d.readings["held_in_step"] < 2 * 64 * 4
    for p, r in zip(d.readings["mtp_losses"], first_steps["reference"]["mtp_losses"]):
        assert abs(p - r) / r < 1e-2


def test_adapter_mode_leaves_the_base_bit_equal(first_steps):
    import jax

    d = first_steps["driver"]
    before, after = (jax.tree_util.tree_leaves(first_steps[k]) for k in ("base", "after"))
    assert len(before) == len(after) > 0
    for a, b in zip(before, after):
        assert a.dtype == b.dtype and a.dtype.name == "bfloat16"
        np.testing.assert_array_equal(a, b)
    tr = d.trainer
    assert sorted(tr.lora) == sorted(
        f"{p}attn/{n}/kernel" for p in ("layer_0/", "layer_1/", "mtp/block/")
        for n in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))
    assert all(v > 0 for v in d.readings["change_norms"].values())


def test_expert_spans_attributes_and_counter(first_steps):
    from fedml_tpu.obs import trace as obstrace

    steps = [s for s in obstrace.recent() if s.name == "llm.step" and "moe_held" in s.attrs]
    assert len(steps) >= 4
    for s in steps:
        assert s.attrs["moe_assignments"] == 2 * 64 * 4            # an expert layer and the MTP module's
        assert 0 < s.attrs["moe_max_load"] <= s.attrs["moe_held"] < s.attrs["moe_assignments"]
    routed, held = (obstrace.LLM_EXPERT_TOKENS.value(kind=k) for k in ("routed", "held"))
    assert routed >= len(steps) * 2 * 64 * 4 and 0 < held < routed


def test_full_fine_tuning_trains_the_experts_too(bench):
    """``lora_rank=0``: every parameter trains in float32, the held experts'
    stacked kernels among them (the written-out backward gives their gradient
    where they are differentiated)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
    from fedml_tpu.parallel import mesh as meshlib

    cfg = bench["pangu"].transformer_config(bench["config"], 64, "full", loss_chunk=16)
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=jax.devices()[:1])
    tr = LLMTrainer(cfg, LLMTrainArgs(batch_size=1, seq_len=64, total_steps=4, warmup_steps=1), mesh=mesh)
    assert tr.lora is None and all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(tr.params))
    before = jax.tree_util.tree_map(np.asarray, tr.params)
    tok = np.random.default_rng(0).integers(0, 256, (1, 64), dtype=np.int32)
    hist = tr.fit(iter([(tok, np.roll(tok, -1, 1))] * 2), steps=2)
    assert len(hist) == 2 and hist[0]["mtp_loss"] > 0 and hist[0]["loss"] > hist[0]["mtp_loss"] * 0.3
    moved = {k: float(np.abs(np.asarray(a) - b).max()) for (k, a), b in zip(
        bench["compare"].flat(tr.params).items(), jax.tree_util.tree_leaves(before))}
    assert min(moved.values()) > 0, sorted(k for k, v in moved.items() if v == 0)


def test_adapters_on_the_latent_projections_and_no_stacked_expert_kernel(bench):
    import jax
    from fedml_tpu.llm import lora as lora_lib
    from fedml_tpu.models.transformer import Transformer

    cfg = _cfg(bench)
    tokens = np.zeros((1, 64), np.int32)
    params = jax.eval_shape(lambda: Transformer(cfg).init({"params": jax.random.PRNGKey(0)}, tokens))["params"]
    assert "mtp" in params                       # the module's parameters are made without the targets too
    lora = jax.eval_shape(lambda: lora_lib.init_lora(params, 4, jax.random.PRNGKey(1), lora_lib.MLA_TARGETS))
    assert len(lora) == 3 * 5
    shapes = {k: (v["a"].shape, v["b"].shape) for k, v in lora.items()}
    assert shapes["layer_1/attn/wq_b/kernel"] == ((32, 4), (4, 4 * 24))
    assert shapes["mtp/block/attn/wkv_b/kernel"] == ((16, 4), (4, 4 * 32))
    assert shapes["layer_0/attn/wo/kernel"] == ((4 * 16, 4), (4, 64))     # heads x v_head_dim -> hidden
    with pytest.raises(ValueError, match="stack of expert kernels"):
        lora_lib.init_lora(params, 4, jax.random.PRNGKey(1), r".*moe/experts/w_(gate|up|down)")


def test_new_kernels_have_sharding_rules(bench):
    """Every kernel the configuration adds is named by a rule of its own: none
    falls through to the replicate-by-default one by accident."""
    import re

    import jax
    from jax.sharding import PartitionSpec as P
    from fedml_tpu.models.transformer import Transformer
    from fedml_tpu.parallel.sharding import TRANSFORMER_RULES, partition_specs

    cfg = _cfg(bench)
    params = jax.eval_shape(lambda: Transformer(cfg).init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 64), np.int32)))["params"]
    flat = bench["compare"].flat(params)
    assert all(any(re.fullmatch(pattern, path) for pattern, _ in TRANSFORMER_RULES) for path in flat), \
        [p for p in flat if not any(re.fullmatch(pattern, p) for pattern, _ in TRANSFORMER_RULES)]
    specs = bench["compare"].flat(jax.tree_util.tree_map(
        lambda s: s, partition_specs(params), is_leaf=lambda x: isinstance(x, P)))
    assert specs["layer_0/attn/wq_a/kernel"] == specs["layer_0/attn/wkv_a/kernel"] == P("data", None)
    assert specs["layer_0/attn/wq_b/kernel"] == specs["layer_0/attn/wkv_b/kernel"] == P("data", "model", None)
    assert specs["layer_1/moe/experts/w_gate"] == P(None, "data", "model")
    assert specs["layer_1/moe/experts/w_down"] == P(None, "model", "data")
    assert specs["layer_1/moe/shared/w_up/kernel"] == specs["layer_0/mlp/w_up/kernel"] == P("data", "model")
    assert tuple(specs["layer_1/moe/router/kernel"]) == (None, None)
    assert specs["mtp/proj/kernel"] == P("model", "data")


def test_fedllm_round_on_the_model(bench, eight_devices):
    """``FedLLMSimulator`` builds the same ``Transformer`` (latent attention,
    an expert layer, sandwich norms; no targets inside the model, so the MTP
    module stays out of the client step) and runs it unchanged: one round
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
                  extra={"lora_r": 2, "lora_targets": lora_lib.MLA_TARGETS})
    fedml_tpu.init(fcfg)
    ds = loader.load(fcfg)
    tcfg = bench["pangu"].transformer_config(bench["config"], ds.train_x.shape[1], "full",
                                             vocab_size=ds.class_num)
    sim = FedLLMSimulator(fcfg, ds, tcfg)
    assert "layer_1/attn/wkv_b/kernel" in sim.global_lora
    base = jax.tree_util.tree_map(np.asarray, sim.base_params)
    first = jax.tree_util.tree_map(np.asarray, sim.global_lora)
    out = sim.run_round()
    assert np.isfinite(out["train_loss"])
    for a, b in zip(jax.tree_util.tree_leaves(base), jax.tree_util.tree_leaves(sim.base_params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert any(float(np.abs(np.asarray(a) - b).max()) > 0 for a, b in
               zip(jax.tree_util.tree_leaves(sim.global_lora), jax.tree_util.tree_leaves(first)))


# -- the yardstick --------------------------------------------------------------------
def test_required_work_counts(bench):
    """The parameter counts ISSUE 33 cut the configuration with (196.58M a
    mixer, 47.19M an expert, 623.25M an expert layer with 8 held, 4,150.4M in
    all, 4.84M adapter parameters) and the forward's parts it sized the cell
    with; every published width as published."""
    bench["flops"].check()
    c = bench["full_config"]
    # the published config.json's widths and constants, as the guide's catalog holds them
    published = {
        "hidden_size": 7680, "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "num_attention_heads": 128, "num_key_value_heads": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "rope_theta": 25600000, "rms_norm_eps": 1e-05, "num_nextn_predict_layers": 1,
        "sandwich_norm": True, "max_position_embeddings": 131072,
        "num_hidden_layers": 61, "first_k_dense_replace": 3, "n_routed_experts": 256, "vocab_size": 153600}
    differ = {k for k, v in published.items() if c.get(k) != v}
    assert differ <= set(c["reduced"]) == {"num_hidden_layers", "first_k_dense_replace",
                                           "n_routed_experts", "vocab_size"}, differ
    assert c["router_experts"] == c["published"]["n_routed_experts"] == 256
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    tcfg = bench["pangu"].transformer_config(c, 8192)
    assert (tcfg.n_routed_experts, tcfg.experts_held, tcfg.top_k, tcfg.first_k_dense) == (256, 8, 8, 1)
    from fedml_tpu.ops.moe import round_rows

    assert round_rows(8192, 8, 256) == 512     # twice an even share of 256 rows
