"""Runtime trace sanitizer (ISSUE 20): transfer-guarded steady-state rounds,
compile attribution, annotated host boundaries — and the tier-1 gate that
runs the flagship round loop + the async fold path under the guard and
requires zero disallowed transfers and zero post-warmup recompiles."""

import contextlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from fedml_tpu.analysis import tracesan
from fedml_tpu.analysis.tracesan import (
    ENV_FLAG,
    ENV_REPORT,
    active,
    install,
    maybe_install_from_env,
    uninstall,
)

from .conftest import tiny_config

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def san():
    """An installed sanitizer, torn down afterwards (never leaks into the
    rest of the suite)."""
    was_active = active()
    s = install()
    yield s
    if was_active is None:
        uninstall()


def _load(cfg):
    import fedml_tpu
    from fedml_tpu.data import loader
    from fedml_tpu.models import model_hub

    fedml_tpu.init(cfg)
    ds = loader.load(cfg)
    model = model_hub.create(cfg, ds.class_num)
    return ds, model


def _upload_msg(rank, params, n_samples=16.0, version=0):
    from fedml_tpu.comm.message import Message
    from fedml_tpu.cross_silo import message_define as md

    msg = Message(md.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, rank, 0)
    msg.add_params(md.MSG_ARG_KEY_MODEL_PARAMS, params)
    msg.add_params(md.MSG_ARG_KEY_NUM_SAMPLES, float(n_samples))
    msg.add_params(md.MSG_ARG_KEY_ROUND_INDEX, int(version))
    return Message.decode(msg.encode())


# -- gating --------------------------------------------------------------------

def test_env_unset_is_a_strict_noop(monkeypatch):
    monkeypatch.delenv(ENV_FLAG, raising=False)
    assert maybe_install_from_env() is None
    assert active() is None
    assert isinstance(tracesan.round_guard(3), contextlib.nullcontext)
    assert isinstance(tracesan.allow("x"), contextlib.nullcontext)


def test_module_import_is_jax_free():
    """The default path must not even import jax from the module: the env
    check plus null context managers are the entire unset behavior."""
    code = (
        "import sys\n"
        "import fedml_tpu.analysis.tracesan as t\n"
        "assert 'jax' not in sys.modules, 'module import pulled in jax'\n"
        "import contextlib\n"
        "assert isinstance(t.round_guard(2), contextlib.nullcontext)\n"
        "assert isinstance(t.allow('s'), contextlib.nullcontext)\n"
        "assert 'jax' not in sys.modules, 'inactive cms pulled in jax'\n"
        "print('NOOP_OK')\n"
    )
    res = subprocess.run([sys.executable, "-c", code], cwd=str(REPO_ROOT),
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stderr[-3000:]
    assert "NOOP_OK" in res.stdout


# -- guard semantics -----------------------------------------------------------

def test_round_guard_blocks_implicit_transfers(san):
    import jax
    import jax.numpy as jnp

    # warmup round (below the default warmup_rounds=1): transfers legal
    with san.round_guard(0):
        jnp.sin(np.ones(3)).block_until_ready()
    # steady round: the same implicit h2d must raise AND be recorded
    with pytest.raises(jax.errors.JaxRuntimeError, match="isallowed"):
        with san.round_guard(5):
            jnp.sin(np.ones(4)).block_until_ready()
    rep = san.report()
    assert rep["guarded_rounds"] >= 1
    kinds = [v["kind"] for v in rep["violations"]]
    assert "disallowed_transfer" in kinds
    viol = next(v for v in rep["violations"] if v["kind"] == "disallowed_transfer")
    assert viol["round"] == 5
    # after the guard exits the process is back to normal
    jnp.sin(np.ones(5)).block_until_ready()


def test_allow_reopens_the_guard_and_counts(san):
    import jax.numpy as jnp

    with san.round_guard(7):
        with tracesan.allow("test_boundary"):
            jnp.asarray(np.arange(6.0)).block_until_ready()
        with tracesan.allow("test_boundary"):
            jnp.asarray(np.arange(6.0) + 1.0).block_until_ready()
    rep = san.report()
    assert rep["allowed_sites"]["test_boundary"] == 2
    assert [v for v in rep["violations"] if v["kind"] == "disallowed_transfer"] == []


def test_explicit_device_get_stays_legal_under_guard(san):
    import jax
    import jax.numpy as jnp

    x = jnp.arange(8.0)
    with san.round_guard(3):
        host = jax.device_get(x)  # explicit: the guard's whole point
    assert host.shape == (8,)
    assert san.report()["guarded_rounds"] >= 1


def test_steady_compile_is_attributed_and_flagged(san):
    import jax
    import jax.numpy as jnp

    # the persistent compilation cache only absorbs big programs; still,
    # force a REAL backend compile so the monitoring event is guaranteed
    x = jnp.arange(11.0)  # staged (and its arange compiled) outside the guard
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with san.round_guard(4):
            # no host operands (a python literal would itself trip the
            # guard): x*x's first compile is the steady-phase event
            jnp.arctan(x * x).block_until_ready()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    rep = san.report()
    steady = [v for v in rep["violations"] if v["kind"] == "steady_compile"]
    assert steady, f"no steady compile recorded: {rep['compiles']}"
    assert steady[0]["round"] == 4
    assert rep["compiles"].get("steady", 0) >= 1
    # attribution: the innermost fedml_tpu frame is this test's caller chain
    # (no package frame on the stack -> '<outside-package>' is acceptable)
    assert steady[0]["site"]


def test_install_is_idempotent_and_uninstall_deactivates():
    was = active()
    s1 = install()
    s2 = install()
    assert s1 is s2
    if was is None:
        uninstall()
        assert active() is None
        assert isinstance(tracesan.round_guard(1), contextlib.nullcontext)


# -- env-gated end-to-end (subprocess): conftest-style install + report dump ---

def test_env_gated_install_and_report_dump(tmp_path):
    report = tmp_path / "tracesan.json"
    code = (
        "import numpy as np\n"
        "from fedml_tpu.analysis.tracesan import maybe_install_from_env, active\n"
        "san = maybe_install_from_env()\n"
        "assert san is not None and active() is san\n"
        "import jax, jax.numpy as jnp\n"
        "from fedml_tpu.analysis import tracesan\n"
        "x = jnp.arange(8.0)\n"
        "with tracesan.round_guard(0):\n"
        "    jnp.sum(x).block_until_ready()\n"
        "with tracesan.round_guard(3):\n"
        "    with tracesan.allow('smoke'):\n"
        "        jnp.asarray(np.ones(3)).block_until_ready()\n"
        "try:\n"
        "    with tracesan.round_guard(4):\n"
        "        jnp.add(x, np.ones(8)).block_until_ready()\n"
        "except jax.errors.JaxRuntimeError:\n"
        "    pass\n"
        "else:\n"
        "    raise SystemExit('implicit transfer was not blocked')\n"
        "print('RUN_OK')\n"
    )
    env = {**os.environ, ENV_FLAG: "1", ENV_REPORT: str(report),
           "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, "-c", code], cwd=str(REPO_ROOT),
                         capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-3000:]
    assert "RUN_OK" in res.stdout
    assert report.exists(), "report was not dumped at interpreter exit"
    rep = json.loads(report.read_text())
    assert rep["guarded_rounds"] == 2
    assert rep["allowed_sites"] == {"smoke": 1}
    assert sum(rep["compiles"].values()) >= 1, "compile listener saw nothing"
    kinds = [v["kind"] for v in rep["violations"]]
    assert "disallowed_transfer" in kinds


def test_tracesan_marker_is_registered_and_populated():
    """`-m tracesan` must collect the gate — an empty selection would pass
    vacuously and silently disarm it."""
    res = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/test_tracesan.py",
         "-m", "tracesan", "--collect-only", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        cwd=str(REPO_ROOT), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-1000:]
    collected = [l for l in res.stdout.splitlines() if "::" in l]
    assert len(collected) >= 1, "tracesan marker collects nothing"


# -- the tier-1 gate: flagship round loop + async fold under the guard ---------

@pytest.mark.tracesan
def test_tracesan_gate_flagship_rounds_and_async_fold(eight_devices):
    """≥3 steady-state FedAvg rounds (the compiled mesh chunk path) plus the
    buffered-async streamed fold, all under ``transfer_guard('disallow')``:
    zero disallowed transfers, zero post-warmup recompiles.  A violation
    here means the hot path regressed — fix the staging/annotation, do not
    relax this test."""
    import jax

    was_active = active()
    san = install()
    try:
        from fedml_tpu.sim.engine import MeshSimulator

        cfg = tiny_config(comm_round=4)
        ds, model = _load(cfg)
        sim = MeshSimulator(cfg, ds, model)
        out = []
        for _ in range(4):  # round 0 warms up; rounds 1-3 run guarded
            out.extend(sim.run_rounds(1))
        assert len(out) == 4 and all(np.isfinite(list(m.values())).all()
                                     for m in out)

        # async-server fold path: decode real wire frames into the streamed
        # accumulator.  Round 0 fold warms the per-leaf programs; the
        # steady-round folds must then be transfer-silent outside the
        # annotated fold_ingest boundary.
        from fedml_tpu.cross_silo import build_aggregator

        cfg2 = tiny_config(extra={"streaming_aggregation": True})
        ds2, model2 = _load(cfg2)
        agg = build_aggregator(cfg2, ds2, model2)
        assert agg.stream_mode
        base = jax.device_get(agg.global_vars)
        msgs = {cid: _upload_msg(cid, base) for cid in (1, 2, 3, 4)}
        with san.round_guard(0):
            assert agg.fold(1, msgs[1], 16.0, False)
        with san.round_guard(5):
            for cid in (2, 3, 4):
                assert agg.fold(cid, msgs[cid], 16.0, False)
        agg.aggregate(0)

        rep = san.report()
        assert rep["violations"] == [], (
            "trace-hygiene violations in the flagship round loop:\n"
            + json.dumps(rep["violations"], indent=1))
        assert rep["guarded_rounds"] >= 4, rep  # 3 sim rounds + 1 fold round
        assert rep["compiles"].get("steady", 0) == 0, rep["compiles"]
        # non-vacuity: the annotated boundaries actually fired
        assert rep["allowed_sites"].get("round_metrics", 0) >= 3, rep
        assert rep["allowed_sites"].get("fold_ingest", 0) >= 3, rep
    finally:
        if was_active is None:
            uninstall()


def test_round_guard_is_silent_on_the_bucketed_chunk(eight_devices):
    """A ragged population's chunk sorts its cohort and runs it in lane
    buckets whose step loop ends at a value of the program (sim/engine.py).
    All of that is on the device: rounds whose cohorts, and so whose trip
    counts, differ are one compiled program and no transfer."""
    import dataclasses

    was_active = active()
    san = install()
    try:
        from fedml_tpu.sim.engine import MeshSimulator

        cfg = tiny_config(comm_round=5, client_num_in_total=24, client_num_per_round=20,
                          batch_size=8, mesh_shape="clients:2")
        ds, model = _load(cfg)
        counts = [3, 40, 9, 17, 25, 33, 8, 12, 20, 28, 36, 5,
                  7, 15, 23, 31, 39, 1, 10, 19, 27, 32, 16, 24]
        starts = np.cumsum([0] + counts[:-1])
        ds = dataclasses.replace(
            ds, client_idx=[np.arange(s, s + c) for s, c in zip(starts, counts)])
        sim = MeshSimulator(cfg, ds, model)
        assert sim._lane_buckets == 2
        guarded_before = san.report()["guarded_rounds"]
        out = []
        for _ in range(5):  # round 0 warms up; rounds 1-4 run guarded
            out.extend(sim.run_rounds(1))
        assert len({m["num_steps"] for m in out}) > 1, "the cohorts' budgets did not differ"
        rep = san.report()
        assert rep["violations"] == [], json.dumps(rep["violations"], indent=1)
        assert rep["guarded_rounds"] - guarded_before >= 4, rep
        assert rep["compiles"].get("steady", 0) == 0, rep["compiles"]
    finally:
        if was_active is None:
            uninstall()


def test_default_path_is_bitwise_pinned(eight_devices):
    """Training with the sanitizer installed must be BITWISE the default
    run: the guard observes, it never reorders or re-places a computation
    on the guarded path."""
    import jax

    from fedml_tpu.sim.engine import MeshSimulator

    def run(with_san):
        cfg = tiny_config(comm_round=2)
        ds, model = _load(cfg)
        if with_san:
            install()
        try:
            sim = MeshSimulator(cfg, ds, model)
            sim.run_rounds(1)
            sim.run_rounds(1)
            return jax.device_get(sim.global_vars)
        finally:
            if with_san:
                uninstall()

    was_active = active()
    if was_active is not None:
        uninstall()
    try:
        plain = run(False)
        guarded = run(True)
    finally:
        if was_active is not None:
            install()
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(guarded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
