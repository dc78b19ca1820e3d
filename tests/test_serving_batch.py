"""Continuous-batching serving fleet (ISSUE 11): micro-batcher coalescing /
deadline-flush / backpressure semantics, hot-swap-under-load with zero
dropped requests, canary rollback on an injected regression, AOT-warm worker
restart, and the flag-unset bit-identical default path for the publish hook."""

import json
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from .conftest import tiny_config


class StubPredictor:
    """Deterministic predictor stand-in: every output row is ``value`` (so a
    result names the version that produced it), with injectable delay /
    exception / NaN regression."""

    def __init__(self, value, max_batch=8, delay_s=0.0, fail=False, nan=False):
        self.value = float(value)
        self.max_batch = max_batch
        self.delay_s = delay_s
        self.fail = fail
        self.nan = nan
        self.calls = 0
        self.rows_seen = []

    def predict_rows(self, x):
        self.calls += 1
        self.rows_seen.append(int(np.asarray(x).shape[0]))
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail:
            raise RuntimeError("injected predictor failure")
        fill = np.nan if self.nan else self.value
        return np.full((np.asarray(x).shape[0], 2), fill, np.float32)


def _batcher(pred, **kw):
    from fedml_tpu.serving.batcher import MicroBatcher

    return MicroBatcher(pred, **kw)


def _await(cond, what, deadline_s=5.0):
    """Poll the batcher's own state instead of sleeping a guessed interval."""
    end = time.monotonic() + deadline_s
    while not cond():
        assert time.monotonic() < end, f"timed out waiting for {what}"
        time.sleep(0.002)


# ---------------------------------------------------------------------------
# micro-batcher semantics
# ---------------------------------------------------------------------------

def test_batcher_coalesces_concurrent_requests():
    """N concurrent single-row submits must land in FEWER predictor calls
    than requests (the whole point), with per-request results intact."""
    pred = StubPredictor(7.0, max_batch=8, delay_s=0.01)
    b = _batcher(pred, max_batch=8, max_queue=64, flush_ms=20.0)
    try:
        futs = []
        threads = [threading.Thread(
            target=lambda: futs.append(b.submit(np.zeros((1, 4)))))
            for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        outs = [f.wait(10.0) for f in futs]
        assert len(outs) == 16
        for out in outs:
            assert out.shape == (1, 2) and float(out[0, 0]) == 7.0
        assert pred.calls < 16, f"no coalescing: {pred.calls} calls"
        assert max(pred.rows_seen) > 1
        # latency accounting rode the futures
        assert all(f.total_s >= f.queue_s >= 0.0 for f in futs)
    finally:
        b.stop()


def test_deadline_flush_never_waits_for_full_batch():
    """A lone request dispatches within ~flush_ms, not when the batch fills
    (there is nothing else coming — waiting would be unbounded latency)."""
    pred = StubPredictor(1.0, max_batch=32)
    b = _batcher(pred, max_batch=32, flush_ms=10.0)
    try:
        t0 = time.monotonic()
        out = b.submit(np.zeros((1, 4))).wait(5.0)
        elapsed = time.monotonic() - t0
        assert float(out[0, 0]) == 1.0
        assert elapsed < 2.0, f"lone request waited {elapsed}s for a full batch"
    finally:
        b.stop()


def test_backpressure_queue_overflow_is_explicit():
    """Admission past max_queue raises QueueOverflow with a positive
    retry-after hint — bounded memory, explicit 503, never silent growth."""
    from fedml_tpu.serving.batcher import QueueOverflow

    pred = StubPredictor(1.0, max_batch=1, delay_s=0.5)
    b = _batcher(pred, max_batch=1, max_queue=2, flush_ms=0.0)
    try:
        b.submit(np.zeros((1, 4)))  # occupies the device
        _await(lambda: pred.calls == 1 and b.stats()["queue_depth"] == 0,
               "the dispatcher to pick it up")
        b.submit(np.zeros((1, 4)))
        b.submit(np.zeros((1, 4)))
        with pytest.raises(QueueOverflow) as exc:
            for _ in range(4):  # the queue bound must hold
                b.submit(np.zeros((1, 4)))
        assert exc.value.retry_after_s > 0
        stats = b.stats()
        assert stats["rejected"] >= 1
        # oversized request is a 400-class error, not an overflow
        with pytest.raises(ValueError):
            b.submit(np.zeros((9, 4)))
    finally:
        b.stop()


def test_http_backpressure_maps_to_503_retry_after(eight_devices):
    """Through the HTTP runner: a full admission queue answers 503 with a
    Retry-After header; a well-formed request answers 200 + version."""
    from fedml_tpu.serving.inference import FedMLInferenceRunner
    from fedml_tpu.serving.publisher import HotSwapController

    pred = StubPredictor(3.0, max_batch=1, delay_s=1.0)
    ctl = HotSwapController(pred, version=5)
    b = _batcher(pred, controller=ctl, max_batch=1, max_queue=1, flush_ms=0.0)
    runner = FedMLInferenceRunner(pred, port=0, batcher=b, stats_fn=b.stats)
    port = runner.run(block=False)
    try:
        def post():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/predict",
                data=json.dumps({"inputs": [[0.0] * 4]}).encode(),
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=10.0)

        first = threading.Thread(target=lambda: post().read())
        first.start()
        _await(lambda: pred.calls == 1 and b.stats()["queue_depth"] == 0,
               "the dispatcher to take the first request")
        threading.Thread(target=lambda: post().read(), daemon=True).start()
        _await(lambda: b.stats()["queue_depth"] == b.stats()["max_queue"],
               "the second request to fill the queue")
        with pytest.raises(urllib.error.HTTPError) as exc:
            post()
        assert exc.value.code == 503
        assert int(exc.value.headers["Retry-After"]) >= 1
        body = json.loads(exc.value.read())
        assert body["error"] == "overloaded" and body["retry_after_s"] > 0
        first.join(timeout=10.0)
        out = json.loads(post().read())
        assert out["version"] == 5 and out["outputs"][0][0] == 3.0
    finally:
        runner.stop()
        b.stop()


# ---------------------------------------------------------------------------
# hot swap + canary
# ---------------------------------------------------------------------------

def test_hot_swap_under_load_zero_dropped_requests():
    """Continuous submits while the version flips v1 -> v2: every request
    resolves (zero drops), every output is attributable to exactly one
    version, and the route eventually serves only v2."""
    from fedml_tpu.serving.publisher import HotSwapController

    v1, v2 = StubPredictor(1.0), StubPredictor(2.0)
    ctl = HotSwapController(v1, version=1)
    b = _batcher(v1, controller=ctl, max_batch=4, max_queue=128, flush_ms=0.5)
    results, errors = [], []
    stop = threading.Event()

    def load():
        while not stop.is_set():
            try:
                out = b.submit(np.zeros((1, 4))).wait(10.0)
                results.append(float(out[0, 0]))
            except Exception as e:  # any drop fails the test
                errors.append(e)

    threads = [threading.Thread(target=load) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.1)
        ctl.offer(2, v2)  # the hot swap, mid-load
        deadline = time.time() + 5.0
        while ctl.version != 2 and time.time() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        b.stop()
    assert not errors, errors
    assert set(results) <= {1.0, 2.0}
    assert 2.0 in results, "new version never served"
    assert ctl.version == 2 and ctl.swaps == 1
    assert results[-1] == 2.0, "stable route did not converge on v2"


@pytest.mark.parametrize("regression", ["fail", "nan", "latency"])
def test_canary_rollback_on_injected_regression(regression):
    """A canary that raises, emits non-finite outputs, or regresses latency
    past the factor must roll back: the stable version keeps serving, zero
    requests are dropped (failed canary batches re-execute on stable), and
    the bad version is remembered as rejected."""
    from fedml_tpu.serving.publisher import HotSwapController

    stable = StubPredictor(1.0, delay_s=0.001)
    bad = StubPredictor(
        9.0,
        delay_s=0.25 if regression == "latency" else 0.0,
        fail=regression == "fail",
        nan=regression == "nan")
    ctl = HotSwapController(stable, version=1, canary_fraction=0.5,
                            canary_min_batches=4)
    b = _batcher(stable, controller=ctl, max_batch=2, max_queue=256,
                 flush_ms=0.0)
    try:
        ctl.offer(2, bad)
        outs = []
        deadline = time.time() + 20.0
        while ctl.stats()["canary_version"] is not None and time.time() < deadline:
            outs.append(float(b.submit(np.zeros((1, 4))).wait(10.0)[0, 0]))
        stats = ctl.stats()
        assert stats["rollbacks"] == 1, stats
        assert stats["served_version"] == 1, stats
        assert 2 in stats["rejected_versions"], stats
        assert not ctl.wants_version(2), "rejected version must never re-offer"
        # zero dropped AND zero poisoned results: fail/nan canary batches
        # fell back to stable, latency canary answers are still v-bad's
        # (slow but correct) — callers never see NaN or an exception
        expected = {1.0} if regression in ("fail", "nan") else {1.0, 9.0}
        assert set(outs) <= expected, set(outs)
        assert all(np.isfinite(o) for o in outs)
    finally:
        b.stop()


def test_canary_promotes_healthy_version():
    from fedml_tpu.serving.publisher import HotSwapController

    stable, fresh = StubPredictor(1.0), StubPredictor(2.0)
    ctl = HotSwapController(stable, version=1, canary_fraction=0.5,
                            canary_min_batches=3)
    b = _batcher(stable, controller=ctl, max_batch=2, flush_ms=0.0)
    try:
        ctl.offer(2, fresh)
        deadline = time.time() + 20.0
        while ctl.version != 2 and time.time() < deadline:
            b.submit(np.zeros((1, 4))).wait(10.0)
        stats = ctl.stats()
        assert stats["served_version"] == 2 and stats["swaps"] == 1, stats
        assert stats["rollbacks"] == 0, stats
    finally:
        b.stop()


class _LabelPredictor:
    """Predicts the class carried in feature 0 — or a constant wrong class.
    Numerically healthy either way (finite, fast): only the labeled eval
    batch can tell the good one from the bad one."""

    def __init__(self, wrong=False):
        self.wrong = wrong

    def predict_rows(self, x):
        x = np.asarray(x)
        logits = np.zeros((x.shape[0], 2), np.float32)
        cls = np.zeros(x.shape[0], int) if self.wrong \
            else x[:, 0].round().astype(int)
        logits[np.arange(x.shape[0]), cls] = 1.0
        return logits


def test_canary_rollback_on_eval_accuracy_regression():
    """ISSUE 19 satellite: the labeled eval batch folds into the health
    score — a canary that is numerically healthy (no errors, no latency
    regression) but WRONG on held-out data rolls back; an accurate
    candidate still promotes.  Without the eval batch the same wrong
    canary sails through, proving the accuracy factor is load-bearing."""
    from fedml_tpu.serving.publisher import HotSwapController

    ex = np.array([[0.0, 1.0], [1.0, 0.0], [0.0, 0.0], [1.0, 1.0]], np.float32)
    ey = np.array([0, 1, 0, 1])
    good, bad = _LabelPredictor(), _LabelPredictor(wrong=True)

    ctl = HotSwapController(good, version=1, canary_fraction=0.5,
                            canary_min_batches=2, regress_threshold=0.6,
                            eval_batch=(ex, ey))
    stats = ctl.stats()
    assert stats["stable_eval_acc"] == 1.0, stats
    # wrong canary: every canary batch reports healthy, yet the eval factor
    # (acc 0.5 vs stable 1.0) drags the score under the threshold
    ctl.offer(2, bad)
    assert ctl.stats()["canary_eval_acc"] == 0.5, ctl.stats()
    for _ in range(2):
        ctl.observe_batch(2, ok=True, execute_s=0.001, is_canary=True)
    stats = ctl.stats()
    assert stats["rollbacks"] == 1 and stats["served_version"] == 1, stats
    assert 2 in stats["rejected_versions"], stats
    assert stats["stable_eval_acc"] == 1.0  # stable's score survives rollback
    # accurate candidate: same healthy batches, promotes
    ctl.offer(3, _LabelPredictor())
    for _ in range(2):
        ctl.observe_batch(3, ok=True, execute_s=0.001, is_canary=True)
    stats = ctl.stats()
    assert stats["served_version"] == 3 and stats["swaps"] == 1, stats
    assert stats["stable_eval_acc"] == 1.0, stats
    # control: no eval batch -> the wrong canary promotes (nothing else
    # about it regresses), which is exactly the gap the satellite closes
    blind = HotSwapController(good, version=1, canary_fraction=0.5,
                              canary_min_batches=2, regress_threshold=0.6)
    blind.offer(2, bad)
    for _ in range(2):
        blind.observe_batch(2, ok=True, execute_s=0.001, is_canary=True)
    assert blind.stats()["served_version"] == 2, blind.stats()


@pytest.mark.locksan
def test_hot_swap_e2e_publisher_to_worker(tmp_path, eight_devices):
    """The full publication channel under load: ModelPublisher commits
    versions the way the training server does, an in-process ServingWorker
    bootstraps from the manifest, serves HTTP predicts through the
    micro-batcher, and hot-swaps each version — zero dropped requests."""
    import jax
    import jax.numpy as jnp

    import fedml_tpu
    from fedml_tpu.models import model_hub
    from fedml_tpu.serving.publisher import ModelPublisher
    from fedml_tpu.serving.worker import ServingWorker

    cfg = tiny_config()
    fedml_tpu.init(cfg)
    model = model_hub.create(cfg, 10)
    base = jax.device_get(model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 32)), train=True))
    pub = ModelPublisher(str(tmp_path / "pub"), keep=3)
    pub.publish(0, base, meta={"model": "lr"})

    worker = ServingWorker("lr", 10, publish_dir=str(tmp_path / "pub"),
                           max_batch=8, flush_ms=1.0, poll_s=0.01,
                           bootstrap_timeout_s=30.0)
    port = worker.start(block=False)
    ok, dropped = [0], [0]
    stop = threading.Event()

    def load():
        body = json.dumps({"inputs": [[0.0] * 32]}).encode()
        while not stop.is_set():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/predict", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=10.0) as r:
                    json.loads(r.read())
                ok[0] += 1
            except Exception:
                dropped[0] += 1

    threads = [threading.Thread(target=load) for _ in range(2)]
    try:
        for t in threads:
            t.start()
        for version in (1, 2, 3):
            scaled = jax.tree_util.tree_map(
                lambda a, f=1.0 + 0.1 * version: (np.asarray(a) * f).astype(
                    np.asarray(a).dtype) if np.asarray(a).dtype.kind == "f"
                else a, base)
            pub.publish(version, scaled)
            deadline = time.time() + 10.0
            while worker.served_version < version and time.time() < deadline:
                time.sleep(0.01)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        stats = worker.stats()
        worker.stop()
    assert dropped[0] == 0 and stats["errored"] == 0, (dropped, stats)
    assert ok[0] > 0
    assert stats["served_version"] == 3, stats
    assert stats["swaps"] >= 2, stats  # >= 2 distinct hot swaps under load
    # version pruning: keep=3 retains the newest files, manifest intact
    files = sorted(p.name for p in (tmp_path / "pub").glob("params-*.wire"))
    assert len(files) <= 3 and "params-v00000003.wire" in files


# ---------------------------------------------------------------------------
# AOT-warm worker restart
# ---------------------------------------------------------------------------

def test_aot_warm_worker_restart(tmp_path, eight_devices):
    """First predictor construction populates the program store (misses >
    0); a 'restarted' worker over the same store deserializes — warm hits >
    0, misses == 0 — and its outputs are bitwise the cold run's."""
    import jax
    import jax.numpy as jnp

    import fedml_tpu
    from fedml_tpu.core.aot import AOT_HITS, AOT_MISSES, ProgramStore
    from fedml_tpu.models import model_hub
    from fedml_tpu.serving.inference import JaxPredictor

    cfg = tiny_config()
    fedml_tpu.init(cfg)
    model = model_hub.create(cfg, 10)
    variables = jax.device_get(model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 32)), train=True))
    x = np.linspace(0, 1, 2 * 32).reshape(2, 32).astype(np.float32)

    m0, h0 = AOT_MISSES.value(), AOT_HITS.value()
    cold = JaxPredictor(model, variables, max_batch=8,
                        aot_store=ProgramStore(str(tmp_path / "aot")),
                        feature_shape=(32,), model_name="lr")
    cold.warm()
    assert AOT_MISSES.value() - m0 > 0, "cold run must populate the store"
    cold_out = cold.predict_rows(x)

    m1, h1 = AOT_MISSES.value(), AOT_HITS.value()
    warm = JaxPredictor(model, variables, max_batch=8,
                        aot_store=ProgramStore(str(tmp_path / "aot")),
                        feature_shape=(32,), model_name="lr")
    warm.warm()
    assert AOT_MISSES.value() - m1 == 0, "warm restart re-traced"
    assert AOT_HITS.value() - h1 > 0, "warm restart never hit the store"
    np.testing.assert_array_equal(cold_out, warm.predict_rows(x))


# ---------------------------------------------------------------------------
# publish hook: default path + satellite flags
# ---------------------------------------------------------------------------

def _run_cs(run_id, extra=None):
    import fedml_tpu
    from fedml_tpu.comm.inproc import InProcRouter
    from fedml_tpu.cross_silo import build_client, build_server
    from fedml_tpu.data import loader
    from fedml_tpu.models import model_hub

    cfg = tiny_config(training_type="cross_silo", client_num_in_total=2,
                      client_num_per_round=2, comm_round=2, batch_size=16,
                      synthetic_train_size=128, synthetic_test_size=64,
                      frequency_of_the_test=0, run_id=run_id,
                      extra=dict(extra or {}))
    fedml_tpu.init(cfg)
    ds = loader.load(cfg)
    model = model_hub.create(cfg, ds.class_num)
    InProcRouter.reset(run_id)
    clients = [build_client(cfg, ds, model, rank=r, backend="INPROC")
               for r in (1, 2)]
    for c in clients:
        c.run_in_thread()
    server = build_server(cfg, ds, model, backend="INPROC")
    try:
        history = server.run_until_done(timeout=120.0)
    finally:
        for c in clients:
            c.finish()
    return server, history


def test_publish_hook_flag_unset_is_bit_identical(tmp_path, eight_devices):
    """extra.model_publish_dir unset -> no publisher object, zero publish
    writes, and the aggregation result is bitwise the published run's (the
    hook only OBSERVES the round, never perturbs it)."""
    import jax

    pub_dir = tmp_path / "pub"
    server_off, hist_off = _run_cs("pub_off")
    assert server_off.publisher is None
    server_on, hist_on = _run_cs("pub_on", extra={"model_publish_dir": str(pub_dir)})
    assert server_on.publisher is not None
    assert not list(tmp_path.glob("**/params-*.wire")) or pub_dir.exists()
    # versions 0 (bootstrap), 1, 2 published; manifest commits the last
    manifest = json.loads((pub_dir / "MANIFEST.json").read_text())
    assert manifest["version"] == 2
    assert (pub_dir / manifest["path"]).exists()
    # flag-off: not a single publish artifact anywhere
    assert not (tmp_path / "pub_off").exists()
    for a, b in zip(jax.tree_util.tree_leaves(server_off.aggregator.global_vars),
                    jax.tree_util.tree_leaves(server_on.aggregator.global_vars)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [h["round"] for h in hist_off] == [h["round"] for h in hist_on]


def test_published_artifact_matches_server_global(tmp_path, eight_devices):
    """The manifest-referenced params file decodes to exactly the server's
    final global tree (the artifact a hot-swapping worker will serve)."""
    from fedml_tpu.comm import wire
    from fedml_tpu.cross_silo import message_define as md

    server, _ = _run_cs("pub_art", extra={"model_publish_dir": str(tmp_path / "p")})
    manifest = json.loads((tmp_path / "p" / "MANIFEST.json").read_text())
    with open(tmp_path / "p" / manifest["path"], "rb") as f:
        published = wire.decode_pytree(f.read())
    import jax

    host = jax.device_get(server.aggregator.global_vars)
    flat_pub = wire.flatten_with_skeleton({md.MSG_ARG_KEY_MODEL_PARAMS: published})[1]
    flat_srv = wire.flatten_with_skeleton({md.MSG_ARG_KEY_MODEL_PARAMS: host})[1]
    for a, b in zip(flat_pub, flat_srv):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_worker_cli_feature_dim_flag():
    """The docstring has advertised --feature-dim since the seed; the
    argparse surface must actually define it (satellite), and the parser
    must accept both scalar and conv-shaped specs."""
    from fedml_tpu.serving.worker import parse_feature_dim

    assert parse_feature_dim("32") == (32,)
    assert parse_feature_dim("32,32,3") == (32, 32, 3)
    assert parse_feature_dim(None) is None
    assert parse_feature_dim("") is None
    import os
    from pathlib import Path

    res = subprocess.run(
        [sys.executable, "-m", "fedml_tpu.serving.worker", "--help"],
        capture_output=True, text=True, timeout=120,
        cwd=str(Path(__file__).parent.parent),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr[-2000:]
    for flag in ("--feature-dim", "--publish-dir", "--canary-fraction",
                 "--aot-dir", "--max-queue"):
        assert flag in res.stdout, f"{flag} missing from worker CLI"


def test_worker_feature_dim_overrides_inference(eight_devices):
    """An explicit feature shape warms a predictor whose tree gives no
    inferable input shape (the conv-model gap the satellite closes)."""
    from fedml_tpu.serving.worker import _infer_feature_shape

    # a conv-ish tree (4-d kernel) defeats inference...
    conv_tree = {"params": {"Conv_0": {"kernel": np.zeros((3, 3, 3, 8))}}}
    assert _infer_feature_shape(conv_tree) is None
    # ...but an explicit shape lets the predictor warm before serving
    import jax
    import jax.numpy as jnp

    import fedml_tpu
    from fedml_tpu.models import model_hub
    from fedml_tpu.serving.inference import JaxPredictor

    cfg = tiny_config()
    fedml_tpu.init(cfg)
    model = model_hub.create(cfg, 10)
    variables = jax.device_get(model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 32)), train=True))
    pred = JaxPredictor(model, variables, max_batch=4, feature_shape=(32,))
    pred.warm()  # would no-op (and first request would pay the compile)
    assert pred.predict_rows(np.zeros((1, 32), np.float32)).shape == (1, 10)
