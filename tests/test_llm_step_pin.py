"""The step program of the configurations the benchmark already has is the
parent's to the character.

PR 29 gave ``TransformerConfig`` per-layer mixers, muP scalars and an adapter
mode.  A default configuration (and the Mistral-shaped one of cell
``mistral7b_d2.sft_2k``, here at its rehearsal sizes) must still trace to the
step it traced to before: the text of ``LLMTrainer``'s step jaxpr was taken
on the parent commit (9fb43aa) with this file's own ``step_text`` and its
SHA-256 is pinned below.  Named scopes are not part of that text.
"""

import hashlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (sha256 of the text, its length) on the parent commit
PARENT = {
    "tiny_default": ("a3479b82cda26460699053245d449ce75bb69fcc9ef314898a7d488d3c4928a4", 135677),
    "mistral_7b_d2_rehearsal": (
        "ce71fee9833b59eef19333cd00b85a55f3221a8b601b7ca876449abb61bfe953", 138446),
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
    return {"tiny_default": (TransformerConfig.tiny(vocab_size=256), 2, 16),
            "mistral_7b_d2_rehearsal": (mistral, 4, 32)}


def step_text(name: str) -> str:
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer

    cfg, batch, seq = _configs()[name]
    tr = LLMTrainer(cfg, LLMTrainArgs(batch_size=batch, seq_len=seq, total_steps=10, warmup_steps=2))
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    text = str(jax.make_jaxpr(tr._make_train_step())(tr.params, tr.opt_state, tok, tok))
    return re.sub(r"0x[0-9a-f]+", "0x", text)  # addresses of callables differ from run to run


@pytest.mark.parametrize("name", sorted(PARENT))
def test_step_program_is_the_parents(name, eight_devices):
    text = step_text(name)
    sha, length = PARENT[name]
    assert (hashlib.sha256(text.encode()).hexdigest(), len(text)) == (sha, length)


if __name__ == "__main__":  # prints what to pin, on whatever tree it runs
    import sys

    sys.path.insert(0, ROOT)
    for n in sorted(PARENT):
        t = step_text(n)
        if len(sys.argv) > 1:
            with open(os.path.join(sys.argv[1], n + ".txt"), "w") as fh:
                fh.write(t)
        print(n, hashlib.sha256(t.encode()).hexdigest(), len(t))
