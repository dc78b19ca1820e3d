"""Lane buckets with a dynamic step bound (``sim/engine.py``,
``fl/local_sgd.py``): where ``step_mode="match"`` gives a population's clients
different step budgets, the round sorts its lanes by budget and runs them in
buckets whose step loop ends at the bucket's own longest client.  A faster program, not another result: everything here holds
the bucketed round to the plain one.
"""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import constants as C
from fedml_tpu.fl.local_sgd import make_local_train_fn, own_step_budget
from fedml_tpu.obs import trace as obstrace
from fedml_tpu.sim.engine import LANE_STEPS_KEY, REAL_COUNT_KEY, MeshSimulator

from .conftest import tiny_config

BATCH = 8
# 24 clients, step budgets 1-5 at batch 8
COUNTS = [5, 8, 12, 16, 20, 24, 27, 32, 36, 40, 3, 9, 17, 25, 33, 7, 15, 23, 31, 39, 1, 10, 19, 28]


def _sim(ragged=True, **overrides):
    import fedml_tpu
    from fedml_tpu.data import loader
    from fedml_tpu.models import model_hub

    cfg = tiny_config(**{**dict(
        client_num_in_total=len(COUNTS), client_num_per_round=20, batch_size=BATCH,
        comm_round=100, mesh_shape="clients:1"), **overrides})
    fedml_tpu.init(cfg)
    ds = loader.load(cfg)
    if ragged:
        starts = np.cumsum([0] + COUNTS[:-1])
        ds = dataclasses.replace(
            ds, client_idx=[np.arange(s, s + c) for s, c in zip(starts, COUNTS)])
    return MeshSimulator(cfg, ds, model_hub.create(cfg, ds.class_num))


def _round_args(sim):
    return (sim.global_vars, sim.server_state, sim.client_states, sim.counts, *sim._data,
            jnp.int32(sim.round_idx), sim.root_key, sim.defense_history)


def _one_round(sim, buckets):
    """One round of ``sim``'s round program built with ``buckets`` lane
    buckets, from the simulator's state as it stands (not advanced): what the
    program returns and what the fold was handed, every client its own row."""
    seen = {}
    fold = sim._server_path

    def spy(contribs, weights, sampled, *rest):
        seen.update(contribs=contribs, weights=weights, sampled=sampled)
        return fold(contribs, weights, sampled, *rest)

    kept = sim._lane_buckets
    sim._lane_buckets, sim._server_path = buckets, spy
    try:
        def fn(*args):
            gv, ss, cs, _, metrics = sim._make_round_fn()(*args)
            return {"global": gv, "server": ss, "client_states": cs, "metrics": metrics,
                    "folded": dict(seen)}
        return jax.device_get(jax.jit(fn)(*_round_args(sim)))
    finally:
        sim._lane_buckets = kept
        del sim._server_path


def _assert_trees_close(a, b, rtol=2e-6, atol=1e-7):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=atol)


@pytest.mark.parametrize("buckets", [2, 4])  # 10 sorted lanes a bucket (the rule here); 5
@pytest.mark.parametrize("optimizer", ["FedAvg", "FedProx", "SCAFFOLD", "FedNova"])
def test_bucketed_round_is_the_plain_round(optimizer, buckets):
    sim = _sim(federated_optimizer=optimizer, fedprox_mu=0.1)
    assert sim.hp.steps_per_epoch == 5 and sim._lanes == 20 and sim._lane_buckets == 2
    sim.run_rounds(1)  # SCAFFOLD: a round on, the control variates are no longer zero
    plain, bucketed = _one_round(sim, 1), _one_round(sim, buckets)
    lane_steps = bucketed["metrics"].pop(LANE_STEPS_KEY)
    assert LANE_STEPS_KEY not in plain["metrics"]
    # the fold was handed the same clients in the same order, each its own row
    np.testing.assert_array_equal(plain["folded"]["sampled"], bucketed["folded"]["sampled"])
    np.testing.assert_array_equal(plain["folded"]["weights"], bucketed["folded"]["weights"])
    budgets = own_step_budget(sim.hp, plain["folded"]["weights"].astype(np.int64))
    assert len(set(budgets.tolist())) > 2, "the cohort is not ragged"
    _assert_trees_close(plain["folded"]["contribs"], bucketed["folded"]["contribs"])
    _assert_trees_close(plain["client_states"], bucketed["client_states"])
    _assert_trees_close((plain["global"], plain["server"]), (bucketed["global"], bucketed["server"]))
    for key in ("num_steps", "num_samples", REAL_COUNT_KEY):
        assert plain["metrics"][key] == bucketed["metrics"][key], key
    assert plain["metrics"]["num_steps"] == pytest.approx(budgets.mean(), rel=1e-6)
    np.testing.assert_allclose(plain["metrics"]["train_loss"], bucketed["metrics"]["train_loss"], rtol=2e-6)
    # what the lanes computed: each bucket of lanes to its longest client
    per_bucket = 20 // buckets
    longest = np.sort(budgets)[::-1].reshape(buckets, per_bucket)[:, 0]
    assert lane_steps == per_bucket * longest.sum()
    assert budgets.sum() <= lane_steps < 20 * 5


def test_run_rounds_reports_the_buckets_lane_samples():
    sim = _sim()
    obstrace.clear_recent()
    rounds = sim.run_rounds(3)
    assert [sorted(r) for r in rounds] == [["num_samples", "num_steps", "train_loss"]] * 3
    (rr,) = [s for s in obstrace.recent() if s.name == "sim.run_rounds"]
    assert rr.attrs["lane_buckets"] == 2
    real, lane = rr.attrs["real_samples"], rr.attrs["lane_samples"]
    # fewer than every lane at full capacity, which a program without buckets computes
    assert real <= lane < 3 * 20 * 5 * BATCH and lane % (10 * BATCH) == 0


# -- who keeps the plain program ---------------------------------------------
def _rule(lanes, lane_multiple, steps_per_epoch, counts=COUNTS, n_real=None, step_mode="match",
          backend=C.SIMULATION_BACKEND_MESH, optimizer="FedAvg"):
    """``MeshSimulator._bucket_count`` on what it reads of a simulator."""
    from fedml_tpu.algorithms import create
    from fedml_tpu.fl.types import HParams

    hp = HParams(batch_size=BATCH, steps_per_epoch=steps_per_epoch, step_mode=step_mode)
    me = SimpleNamespace(hp=hp, backend=backend, _lanes=lanes, _lane_multiple=lane_multiple,
                         algorithm=create(tiny_config(federated_optimizer=optimizer), hp),
                         dataset=SimpleNamespace(n_clients=n_real or len(counts)))
    return MeshSimulator._bucket_count(me, np.asarray(counts))


@pytest.mark.parametrize("lanes,multiple,steps,want", [
    (100, 1, 5, 10),   # the cross_device cell: a bucket twice as wide as an epoch is long
    (100, 4, 5, 5),    # the next width that fills four chips alike and divides the lanes: 20
    (100, 1, 23, 2),   # long epochs: few loops to start
    (1000, 1, 5, 100),
    (20, 1, 5, 2),
    (24, 4, 5, 2),
    (12, 4, 5, 1),     # 4 lanes are too narrow, 12 are one bucket: the plain program
    (131, 1, 5, 1),    # nothing divides 131 lanes
    (4, 1, 7, 1),      # a cohort smaller than an epoch is long
])
def test_bucket_count_rule(lanes, multiple, steps, want):
    got = _rule(lanes, multiple, steps)
    assert got == want and lanes % (got * multiple) == 0


@pytest.mark.parametrize("why,kwargs", [
    ("equal budgets", dict(counts=[9, 12, 16, 10])),
    ("the stack's zero-count pad rows are no clients", dict(counts=[9, 12, 16, 10, 0, 0], n_real=4)),
    ("steps are not masked", dict(step_mode="fixed")),
    ("the SP host loop", dict(backend=C.SIMULATION_BACKEND_SP)),
    ("no step loop to bound", dict(optimizer="FedSGD")),
])
def test_plain_program_where_buckets_buy_nothing(why, kwargs):
    assert _rule(100, 1, 5, **kwargs) == 1, why


def test_equal_budgets_build_the_plain_program():
    """Homogeneous shards: no bucket, no extra output, no loop with a trip
    count that is a value — the text of the round program is the one the
    simulator built before buckets existed (PERF.md, PR 27: compared with the
    parent commit's, equal to the character)."""
    sim = _sim(ragged=False)
    assert sim._lane_buckets == 1
    text = str(jax.make_jaxpr(sim._make_round_fn())(*_round_args(sim)))
    assert "while[" not in text and text.count("scan[") == 1
    sorts = text.count("sort[")  # the sampling rule's own permutation
    obstrace.clear_recent()
    sim.run_rounds(2)
    (rr,) = [s for s in obstrace.recent() if s.name == "sim.run_rounds"]
    assert rr.attrs["lane_buckets"] == 1
    assert rr.attrs["lane_samples"] == 2 * sim._lanes * sim.hp.steps_per_epoch * BATCH
    # and the ragged population's own program does hold the loop, and the
    # sort by budget with its inverse
    ragged = _sim()
    text = str(jax.make_jaxpr(ragged._make_round_fn())(*_round_args(ragged)))
    assert text.count("while[") == 1 and text.count("sort[") == sorts + 2
    assert "custom_vmap" not in text  # the loop's own vmap rule has run: lanes inside the loop


# -- the step loop -------------------------------------------------------------
def _local_train(grad_hook=None, **hp_overrides):
    from fedml_tpu.fl.types import HParams
    from fedml_tpu.models import model_hub

    cfg = tiny_config(batch_size=BATCH)
    model = model_hub.create(cfg, 10)
    hp = HParams(**{**dict(batch_size=BATCH, steps_per_epoch=5, learning_rate=0.1), **hp_overrides})
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 60), jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(2), (40,), 0, 10)
    variables = jax.jit(lambda k: model.init({"params": k, "dropout": k}, x[:BATCH], train=True))(
        jax.random.PRNGKey(0))
    fn = make_local_train_fn(model, hp, grad_hook=grad_hook)
    return fn, (variables, x, y, jnp.int32(12), jax.random.PRNGKey(3))


def test_no_bound_is_the_static_scan():
    """With no bound ``local_train`` is the scan over ``epochs *
    steps_per_epoch`` steps it always was (its text compared with the parent
    commit's, PERF.md PR 27): one scan of 5, no loop bounded by a value; and
    the bounded loop run to the end gives the same client."""
    fn, args = _local_train()
    text = str(jax.make_jaxpr(fn)(*args))
    assert "while[" not in text and text.count("scan[") == 1 and "length=5" in text
    plain = jax.jit(fn)(*args)
    again = jax.jit(lambda *a: fn(*a, None, None))(*args)
    for a, b in zip(jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    full = jax.jit(lambda *a: fn(*a, None, jnp.int32(5)))(*args)
    _assert_trees_close(plain, full)
    assert float(full[1]["num_steps"]) == float(plain[1]["num_steps"]) == 2.0


@pytest.mark.parametrize("bound", [2, 3, 5])
def test_a_bound_of_b_runs_b_iterations(bound):
    calls = []

    def count_steps(grads, ctx):
        jax.debug.callback(lambda: calls.append(1))
        return grads

    fn, args = _local_train(grad_hook=count_steps)
    plain = jax.jit(fn)(*args)
    jax.effects_barrier()
    assert len(calls) == 5
    del calls[:]
    # the bound is an argument of the compiled program, not a shape
    bounded = jax.jit(lambda b, *a: fn(*a, None, b))
    out = bounded(jnp.int32(bound), *args)
    jax.effects_barrier()
    assert len(calls) == bound
    assert float(out[1]["num_steps"]) == 2.0  # the client's own budget: ceil(12 / 8)
    _assert_trees_close(plain, out)


def test_a_bound_is_one_trip_count_for_all_lanes():
    """Under a vmap over lanes the loop stays outside and runs the vmapped
    step: the lanes come out as they do one by one, and a bound of each
    lane's own is refused."""
    fn, (variables, x, y, _, key) = _local_train()
    counts = jnp.array([12, 33, 5], jnp.int32)  # budgets 2, 5, 1
    keys = jax.random.split(key, 3)
    lanes = jax.jit(jax.vmap(lambda c, k, b: fn(variables, x, y, c, k, None, b), in_axes=(0, 0, None)))
    out = lanes(counts, keys, jnp.int32(5))
    assert out[1]["num_steps"].tolist() == [2.0, 5.0, 1.0]
    for i in range(3):
        alone = jax.jit(fn)(variables, x, y, counts[i], keys[i])
        _assert_trees_close(alone, jax.tree_util.tree_map(lambda a: a[i], out))
    with pytest.raises(ValueError, match="one trip count"):
        jax.vmap(lambda c, k, b: fn(variables, x, y, c, k, None, b))(counts, keys, jnp.array([2, 5, 1]))


def test_a_bound_needs_masked_steps():
    fn, args = _local_train(step_mode="fixed")
    with pytest.raises(ValueError, match="step_bound"):
        jax.jit(lambda *a: fn(*a, None, jnp.int32(2)))(*args)


# -- a mesh --------------------------------------------------------------------
def test_four_devices_match_one(eight_devices):
    one = _sim(federated_optimizer="SCAFFOLD", client_num_per_round=24)
    four = _sim(federated_optimizer="SCAFFOLD", client_num_per_round=24, mesh_shape="clients:4")
    assert four._lane_multiple == 4 and four._lane_buckets == one._lane_buckets == 2
    assert (four._lanes // four._lane_buckets) % four._lane_multiple == 0
    a, b = one.run_rounds(2), four.run_rounds(2)
    for ra, rb in zip(a, b):
        assert ra["num_steps"] == rb["num_steps"] and ra["num_samples"] == rb["num_samples"]
        np.testing.assert_allclose(ra["train_loss"], rb["train_loss"], rtol=1e-5)
    _assert_trees_close(jax.device_get((one.global_vars, one.server_state, one.client_states)),
                        jax.device_get((four.global_vars, four.server_state, four.client_states)),
                        rtol=1e-5, atol=1e-6)
    # a cohort that does not fill the mesh: pad lanes ride in the buckets and
    # are dropped before the fold
    padded = _sim(client_num_per_round=22, mesh_shape="clients:4")
    plain = _sim(client_num_per_round=22)
    assert padded._lanes == 24 and padded._lane_buckets == 2 and plain._lanes == 22
    for ra, rb in zip(plain.run_rounds(2), padded.run_rounds(2)):
        assert ra["num_steps"] == rb["num_steps"] and ra["num_samples"] == rb["num_samples"]
    _assert_trees_close(jax.device_get(plain.global_vars), jax.device_get(padded.global_vars),
                        rtol=1e-5, atol=1e-6)
