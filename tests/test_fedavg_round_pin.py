"""The round program of the two FedAvg cells is the parent's to the character.

PR 31 took the fused-epilogue fork out of ``models/resnet.py``,
``models/model_hub.py``, ``fl/types.py`` and ``sim/engine.py``.  What the
benchmark's cells run (the hub's ``resnet20`` under FedAvg, bfloat16,
``step_mode=match``: ``benchmark/fedavg.py`` builds this ``Config``) must
still trace to the chunk it traced to before: the text of the scanned chunk's
jaxpr (``MeshSimulator._get_multi_round_fn``'s ``multi``) was taken on the
parent commit (3723cba) with this file's own ``chunk_text`` and its SHA-256 is
pinned below, for a small job of each kind: equal shards (the plain program of
``fedavg_r20.flagship``) and ragged Dirichlet shards (the bucketed program of
``fedavg_r20.cross_device``).  Named scopes are not part of that text.

Beside the pin: the hub's ``resnet20`` has the reference's variable tree (what
``benchmark/fedavg.py`` checks only on the chip), and the local step's modules
import no ``ops.pallas``.
"""

import ast
import hashlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (sha256 of the text, its length) on the parent commit
PARENT = {
    "equal_shards": ("4dd77e4f634db440cd56ac5cb9c8f159263e4cd8e4f8e9c5d998c8666e70b723", 530242),
    "ragged_shards": ("7d574143a196e117d7a7e3e2d919b19649e13e6502dbb30c4d1902454e5791dc", 620699),
}

#: what differs between the two kinds: (partition, clients, a round, batch, rounds a chunk, lane buckets)
JOBS = {
    "equal_shards": ("homo", 8, 4, 16, 2, 1),
    "ragged_shards": ("hetero", 32, 16, 16, 1, 4),
}


def chunk_text(name: str) -> str:
    import jax
    import jax.numpy as jnp

    import fedml_tpu
    from fedml_tpu.arguments import Config
    from fedml_tpu.data import loader
    from fedml_tpu.models import model_hub
    from fedml_tpu.sim.engine import MeshSimulator

    partition, clients, per_round, batch, rounds, buckets = JOBS[name]
    # as benchmark/fedavg.py builds it, on the loader's stand-in for CIFAR-10
    cfg = Config(
        dataset="cifar10", model="resnet20", federated_optimizer="FedAvg",
        client_num_in_total=clients, client_num_per_round=per_round,
        comm_round=10 ** 6, epochs=1, batch_size=batch,
        client_optimizer="sgd", learning_rate=0.03,
        partition_method=partition, partition_alpha=0.5,
        frequency_of_the_test=rounds, compute_dtype="bfloat16", step_mode="match",
        metrics_jsonl_path="", random_seed=7, mesh_shape="clients:1",
        synthetic_train_size=640, synthetic_test_size=32)
    fedml_tpu.init(cfg)
    ds = loader.load(cfg)
    sim = MeshSimulator(cfg, ds, model_hub.create(cfg, ds.class_num))
    assert sim._lane_buckets == buckets, (name, sim._lane_buckets)
    args = (sim.global_vars, sim.server_state, sim.client_states, sim.counts, *sim._data,
            jnp.int32(0), sim.root_key, sim.defense_history)
    text = str(jax.make_jaxpr(sim._get_multi_round_fn(rounds))(*args))
    return re.sub(r"0x[0-9a-f]+", "0x", text)  # addresses of callables differ from run to run


@pytest.mark.parametrize("name", sorted(PARENT))
def test_round_program_is_the_parents(name):
    text = chunk_text(name)
    sha, length = PARENT[name]
    assert (hashlib.sha256(text.encode()).hexdigest(), len(text)) == (sha, length)


def test_hub_resnet20_has_the_references_variable_tree():
    """Names and shapes of ``benchmark/ref_fedavg.init_weights``, whose leaves
    ``benchmark/fedavg.py`` puts in place of the program's own."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.arguments import Config
    from fedml_tpu.models import model_hub

    bench = os.path.join(ROOT, "benchmark")
    sys.path.insert(0, bench)
    try:
        import compare
        import ref_fedavg
    finally:
        sys.path.remove(bench)
    with open(os.path.join(bench, "configs", "resnet20_cifar10_fedavg.json")) as fh:
        c = json.load(fh)
    model = model_hub.create(Config(model=c["model"], compute_dtype=c["compute_dtype"]),
                             c["num_classes"])
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((2, *c["image_shape"])), train=True))
    want = ref_fedavg.leaf_shapes(c)
    assert {k: v.shape for k, v in compare.flat(variables).items()} == want
    assert all(v.dtype == jnp.float32 for v in jax.tree_util.tree_leaves(variables))
    assert sorted(ref_fedavg.init_weights(c, 7)) == sorted(want)


@pytest.mark.parametrize("module", ["fedml_tpu.models.resnet", "fedml_tpu.fl.local_sgd",
                                    "fedml_tpu.sim.engine"])
def test_local_step_imports_no_pallas_kernel(module):
    """The FedAvg cells' path (hub -> engine -> local step -> ResNet) is plain
    XLA: none of its modules imports ``ops.pallas``, at any level."""
    package = module.split(".")[:-1]  # what a relative import starts from
    with open(os.path.join(ROOT, *module.split(".")) + ".py") as fh:
        tree = ast.parse(fh.read())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - node.level + 1] if node.level else []
            name = ".".join(base + ([node.module] if node.module else []))
            imported += [name] + [f"{name}.{a.name}" for a in node.names]
    assert imported and not [n for n in imported if "ops.pallas" in n]


if __name__ == "__main__":  # prints what to pin, on whatever tree it runs
    import sys

    sys.path.insert(0, ROOT)
    for n in sorted(PARENT):
        t = chunk_text(n)
        if len(sys.argv) > 1:
            with open(os.path.join(sys.argv[1], n + ".txt"), "w") as fh:
                fh.write(t)
        print(n, hashlib.sha256(t.encode()).hexdigest(), len(t))
