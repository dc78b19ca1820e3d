"""The step program of the configurations the benchmark already has is the
parent's to the character.

PR 29 gave ``TransformerConfig`` per-layer mixers, muP scalars and an adapter
mode.  A default configuration (and the Mistral-shaped one of cell
``mistral7b_d2.sft_2k``, here at its rehearsal sizes) must still trace to the
step it traced to before: the text of ``LLMTrainer``'s step jaxpr was taken
on the parent commit (9fb43aa) with this file's own ``step_text`` and its
SHA-256 is pinned below.  Named scopes are not part of that text.

PR 33 gave the blockwise attention of ``ops/sparse_attention.py`` a value
width of its own and ``Block``, ``Transformer`` and the trainer an expert
layer, sandwich norms and a second prediction head.  ``minicpm_sala_d4`` (the
configuration whose mixer that PR edits) at its rehearsal sizes in adapter
mode, as ``benchmark/sala.py`` builds it, is pinned the same way, taken on
that PR's parent commit (ca052ac).

PR 38 sent the plain ``Attention``'s unpacked rows through the blockwise entry
(``ops/sparse_attention.block_sparse_attention``: the fused flash kernel on one
TPU device, the ``lax`` pass here) where they built (b, h, s, s) scores whole:
``tiny_default`` and ``mistral_7b_d2_rehearsal`` build that module, so their
text changed by design and both pins were taken again on that PR's own tree;
``minicpm_sala_d4_rehearsal_adapters`` builds no ``Attention``, and its pin
stayed as it was then.

Since then the blockwise pass cuts its mask by query chunk alone and slices
each key chunk's columns where the pair is computed, where it relaid the
whole mask into chunk-pair order: the same products in the same order, so
one step of each of the three gives the earlier trained leaves and loss to
the bit on the CPU, but the text differs (a ``dynamic_slice`` a pair, a
``transpose`` of leading axes alone).  All three pins were taken again on
the tree that made that change.

Kimi Delta Attention's PR made ``causal_conv``'s bias optional, gave
``MLAttention`` a direct query projection and a form without positions, and
gave ``ops/moe.route`` a selection bias.  ``pangu_ultra_moe_d5_ep32`` (latent
attention with its low-rank query path and RoPE, the sigmoid router) and
``granite_4_0_h_micro_d10`` (``causal_conv`` with its bias, on the packed
step) at their rehearsal sizes in adapter mode, as ``benchmark/pangu.py`` and
``benchmark/granite.py`` build them, are pinned the same way, taken on that
PR's parent commit (fc2fe36).
"""

import hashlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (sha256 of the text, its length) on the commit the docstring names for each
PARENT = {
    "tiny_default": ("a678bc931553e6e880163f0c8aa70fd748705cd41fa506d2f97314514ef46dee", 203253),
    "mistral_7b_d2_rehearsal": (
        "8d58af5b2f3cf940192313ebb2e06602c19799d0b1494d914147871baf8c3b0e", 201908),
    "minicpm_sala_d4_rehearsal_adapters": (
        "227672b0e87f743adae87c959d3a50ae61caa873ae1330cf1902911597213cfc", 408507),
    "pangu_ultra_moe_d5_ep32_rehearsal_adapters": (
        "76886b4e0e4136f90be1a760638cba2548c903c64502f5a798aca051160243b2", 566825),
    "granite_4_0_h_micro_d10_rehearsal_adapters_packed": (
        "b83a9bea8da2d83c53881673404e248a3537ef6af29b4eb933dcd698d9ca1732", 337712),
}


def _configs():
    import jax.numpy as jnp
    from fedml_tpu.models.transformer import TransformerConfig

    with open(os.path.join(ROOT, "benchmark", "configs", "mistral_7b_d2.json")) as fh:
        c = json.load(fh)
    c = {**c, **c["rehearsal"]}
    # as benchmark/llm.py builds it
    mistral = TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], max_seq_len=32, rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], dtype=jnp.bfloat16, remat=True, remat_policy="dots",
        logits_dtype=jnp.bfloat16)
    return {"tiny_default": (TransformerConfig.tiny(vocab_size=256), 2, 16, {}),
            "mistral_7b_d2_rehearsal": (mistral, 4, 32, {}),
            "minicpm_sala_d4_rehearsal_adapters": _adapter_cell("sala", "minicpm_sala_d4", "lora_sft_16k_b1"),
            "pangu_ultra_moe_d5_ep32_rehearsal_adapters": _adapter_cell(
                "pangu", "pangu_ultra_moe_d5_ep32", "lora_sft_8k_b1"),
            "granite_4_0_h_micro_d10_rehearsal_adapters_packed": _adapter_cell(
                "granite", "granite_4_0_h_micro_d10", "lora_sft_32k_packed_b1") + (True,)}


def _adapter_cell(driver: str, config: str, traffic: str):
    """(cfg, batch, seq, the job's adapters) as ``benchmark/<driver>.py``
    builds the cell at its rehearsal sizes."""
    bench = os.path.join(ROOT, "benchmark")
    sys.path.insert(0, bench)
    try:
        import importlib

        module = importlib.import_module(driver)
    finally:
        sys.path.remove(bench)
    with open(os.path.join(bench, "configs", config + ".json")) as fh:
        c = json.load(fh)
    with open(os.path.join(bench, "traffic", traffic + ".json")) as fh:
        t = json.load(fh)
    c, t = {**c, **c["rehearsal"]}, {**t, **t["rehearsal"]}
    cfg = module.transformer_config(c, t["seq_len"], t["remat_policy"], **t["program"])
    job = {k: t["train_args"][k] for k in ("lora_rank", "lora_alpha", "lora_targets")}
    return cfg, t["batch_size"], t["seq_len"], job


def step_text(name: str) -> str:
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer

    from fedml_tpu.parallel import mesh as meshlib

    cfg, batch, seq, job, *packed = _configs()[name]
    # a batch of one row needs a mesh of one device (the default takes all eight)
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=jax.devices()[:1]) if batch == 1 else None
    tr = LLMTrainer(cfg, LLMTrainArgs(batch_size=batch, seq_len=seq, total_steps=10, warmup_steps=2,
                                      **job), mesh=mesh)
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    state = (tr.params, tr.opt_state) if tr.lora is None else (tr.lora, tr.opt_state, tr.params)
    segments = (tok,) if packed else ()     # a packed row's document ids: the third array
    text = str(jax.make_jaxpr(tr._make_train_step())(*state, tok, tok, *segments))
    return re.sub(r"0x[0-9a-f]+", "0x", text)  # addresses of callables differ from run to run


@pytest.mark.parametrize("name", sorted(PARENT))
def test_step_program_is_the_parents(name, eight_devices):
    text = step_text(name)
    sha, length = PARENT[name]
    assert (hashlib.sha256(text.encode()).hexdigest(), len(text)) == (sha, length)


# prints what to pin, on whatever tree it runs; the mesh's size is part of the text, so run
# it on the suite's devices: XLA_FLAGS=--xla_force_host_platform_device_count=8
if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    for n in sorted(PARENT):
        t = step_text(n)
        if len(sys.argv) > 1:
            with open(os.path.join(sys.argv[1], n + ".txt"), "w") as fh:
                fh.write(t)
        print(n, hashlib.sha256(t.encode()).hexdigest(), len(t))
