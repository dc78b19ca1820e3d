"""The evaluation's batch follows the test set (``fl/local_sgd.py``
``eval_batch_size``), and what ``evaluate`` returns does not depend on it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.data.dataset import pad_eval_set
from fedml_tpu.fl.local_sgd import EVAL_STEP_ELEMENTS, eval_batch_size, make_eval_fn
from fedml_tpu.fl.types import HParams
from fedml_tpu.models.resnet import CifarResNet

CIFAR = 32 * 32 * 3 + 10  # a sample's input elements and its logits


# ---------------------------------------------------------------- the rule
@pytest.mark.parametrize("n_test, floor, batch, steps", [
    (10_000, 32, 504, 20),   # fedavg_r20.cross_device (PERF.md section 4)
    (1_024, 128, 512, 2),    # fedavg_r20.flagship
    (1_031, 32, 344, 3),
    (160, 32, 160, 1),
    (20, 32, 32, 1),         # a set under one batch stays at the floor
    (50_000, 256, 512, 98),
])
def test_rule_gives_the_cells_their_batches(n_test, floor, batch, steps):
    got = eval_batch_size(n_test, CIFAR, floor)
    assert got == batch
    padded = pad_eval_set(np.zeros((n_test, 1)), np.zeros(n_test), got)[0].shape[0]
    assert padded // got == steps and padded % got == 0


@pytest.mark.parametrize("n_test", [1, 33, 513, 4_097, 10_000, 123_457])
@pytest.mark.parametrize("elements", [61, CIFAR, 224 * 224 * 3 + 1000, 20 + 20 * 10_004])
def test_rule_covers_the_set_with_under_a_step_of_padding(n_test, elements):
    for floor in (32, 256):
        batch = eval_batch_size(n_test, elements, floor)
        steps = -(-n_test // batch)
        assert batch >= floor and batch % 8 == 0
        assert steps * batch >= n_test > (steps - 1) * batch
        assert steps <= -(-n_test // floor)  # no job evaluates in more steps than it did
        # a step stays under the cap (rounding to 8 aside) unless the floor holds it up
        assert batch == floor or (batch - 7) * elements <= EVAL_STEP_ELEMENTS


def test_rule_scales_with_the_sample_and_the_lanes():
    base = eval_batch_size(100_000, CIFAR, 8)
    assert base == 512
    assert abs(eval_batch_size(100_000, 16 * CIFAR, 8) - base / 16) <= 8
    assert abs(eval_batch_size(100_000, CIFAR, 8, lanes=16) - base / 16) <= 8
    # a token is small going in and a vocabulary wide coming out
    assert eval_batch_size(10_000, 20 + 20 * 10_004, 32) == 32


# ------------------------------------------------- the result, whatever the batch
@pytest.fixture(scope="module")
def scorer():
    """A small float32 ``CifarResNet`` whose running statistics are not the
    initial ones, and one jitted evaluation per (padded size, batch)."""
    model = CifarResNet(num_blocks=1, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    variables = jax.jit(lambda k: model.init(k, jnp.zeros((1, 32, 32, 3)), train=False))(key)
    leaves, treedef = jax.tree_util.tree_flatten(variables["batch_stats"])
    keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
    stats = jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.5 * jax.random.uniform(k, leaf.shape) for leaf, k in zip(leaves, keys)])
    variables = {"params": variables["params"], "batch_stats": stats}
    rng = np.random.RandomState(3)
    x = rng.randn(1_031, 32, 32, 3).astype(np.float32)
    y = rng.randint(0, 10, size=1_031).astype(np.int32)

    def score(n: int, batch: int) -> dict:
        px, py, n_valid = pad_eval_set(x[:n], y[:n], batch)
        fn = jax.jit(make_eval_fn(model, HParams(), batch_size=batch))
        return {k: float(v) for k, v in fn(variables, px, py, np.int32(n_valid)).items()}

    return score


@pytest.mark.parametrize("n_test", [20, 1_000, 1_024, 1_031])
def test_evaluate_does_not_depend_on_its_batch(scorer, n_test):
    old = 32  # what the clients' batch of 32 gave every job
    new = eval_batch_size(n_test, CIFAR, old)
    assert (new > old) == (n_test > old)
    a, b = scorer(n_test, old), scorer(n_test, new)
    assert a["test_acc"] == b["test_acc"]
    assert a["test_loss"] == pytest.approx(b["test_loss"], rel=1e-6)
    assert 0.0 < a["test_acc"] < 1.0 and np.isfinite(a["test_loss"])


# ------------------------------------------------------- the engine follows it
def test_simulator_evaluates_at_the_rules_batch_and_says_so(make_tiny_config):
    import fedml_tpu
    from fedml_tpu.obs import trace
    from fedml_tpu.runner import FedMLRunner

    n_test = 50_000  # of 60 features and 10 logits: three steps
    cfg = make_tiny_config(synthetic_test_size=n_test, batch_size=16)
    fedml_tpu.init(cfg)
    sim = FedMLRunner(cfg).runner
    elements = int(np.prod(sim._test[0].shape[1:])) + 10
    assert sim._eval_bs == eval_batch_size(n_test, elements, 32) == 16_672
    padded = sim._test[0].shape[0]
    assert padded % sim._eval_bs == 0 and padded - n_test < sim._eval_bs
    out = sim.evaluate()
    assert np.isfinite(out["test_loss"])
    span = [s for s in trace.recent() if s.name == "sim.eval"][-1]
    assert span.attrs["eval_batch"] == sim._eval_bs
    assert span.attrs["eval_steps"] == padded // sim._eval_bs
    assert span.attrs["round_idx"] == 0
