"""The program's own spans and counters (ISSUE 26): the ring in
``obs/trace.py``, the span trees of ``LLMTrainer.fit`` and of
``MeshSimulator.run_rounds`` / ``evaluate``, the XLA build counters behind the
one ``jax.monitoring`` listener, ``fedml_sim_samples_total``, and the reader
``benchmark/program_spans.py`` on a synthetic events-plus-spans fixture."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.obs import trace as obstrace
from fedml_tpu.obs.registry import REGISTRY
from fedml_tpu.obs.trace import traced

from .conftest import tiny_config

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "benchmark"))
import program_spans  # noqa: E402


def _names(spans, name):
    return [s for s in spans if s.name == name]


# ------------------------------------------------------------------ the ring
def test_ring_keeps_finished_spans_and_is_bounded():
    obstrace.clear_recent()
    for i in range(obstrace.RING_SIZE + 10):
        with traced("t.ring", i=i):
            pass
    kept = obstrace.recent()
    assert len(kept) == obstrace.RING_SIZE
    assert kept[0].attrs["i"] == 10 and kept[-1].attrs["i"] == obstrace.RING_SIZE + 9
    obstrace.clear_recent()
    assert obstrace.recent() == []


def test_span_notes_counters_at_enter_and_their_growth():
    c = REGISTRY.counter("fedml_test_spans_total", "test only")
    c.inc(3)
    sunk = []
    with traced("t.counters", counters=(c.name,), sink=sunk.append) as span:
        c.inc(2)
    assert span.attrs["counters"] == {c.name: [3.0, 2.0]}
    assert sunk[0]["counters"] == {c.name: [3.0, 2.0]}  # the sink sees them too
    assert obstrace.recent()[-1] is span


def test_traced_costs_microseconds():
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with traced("t.cost"):
            pass
    assert (time.perf_counter() - t0) / n < 100e-6


# ------------------------------------------------------- the XLA build counter
@pytest.mark.parametrize("tracesan_on", [False, True])
def test_fresh_jit_counts_one_compile_through_one_listener(tracesan_on):
    from jax._src import monitoring

    from fedml_tpu.analysis import tracesan

    was = tracesan.active()
    if tracesan_on:
        tracesan.install()
    obstrace.install_xla_listener()
    obstrace.install_xla_listener()
    ours = [fn for fn in monitoring.get_event_duration_listeners()
            if getattr(fn, "__module__", "").startswith("fedml_tpu")]
    assert ours == [obstrace._on_duration]
    compiles = REGISTRY.get("fedml_xla_compiles_total")
    loads = REGISTRY.get("fedml_xla_cache_loads_total")
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        x = jnp.arange(7.0) + (1.0 if tracesan_on else 2.0)
        fresh = jax.jit(lambda v: jnp.tanh(v * 3.0) - v)
        before = compiles.value(), loads.value()
        with traced("t.compile", counters=obstrace.XLA_COUNTERS) as span:
            fresh(x).block_until_ready()
        assert (compiles.value(), loads.value()) == (before[0] + 1, before[1])
        assert span.attrs["counters"]["fedml_xla_compiles_total"] == [before[0], 1.0]
        assert span.attrs["counters"]["fedml_xla_compile_seconds_total"][1] > 0
        fresh(x).block_until_ready()
        assert compiles.value() == before[0] + 1
        if tracesan_on:
            assert sum(tracesan.active().report()["compiles"].values()) >= 1
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        if tracesan_on and was is None:
            tracesan.uninstall()


def test_cache_load_is_not_counted_as_a_compile():
    compiles = REGISTRY.get("fedml_xla_compiles_total")
    loads = REGISTRY.get("fedml_xla_cache_loads_total")
    load_s = REGISTRY.get("fedml_xla_cache_load_seconds_total")
    before = compiles.value(), loads.value(), load_s.value()
    # what jax 0.9.0 records for a program found in the persistent cache
    obstrace._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    obstrace._on_duration("/jax/core/compile/backend_compile_duration", 0.26)
    assert (compiles.value(), loads.value()) == (before[0], before[1] + 1)
    assert load_s.value() == pytest.approx(before[2] + 0.25)
    obstrace._on_duration("/jax/core/compile/backend_compile_duration", 0.5)
    assert compiles.value() == before[0] + 1


def test_first_call_counts_tracing_and_lowering_and_a_second_call_neither():
    obstrace.install_xla_listener()
    trace_s = REGISTRY.get("fedml_xla_trace_seconds_total")
    lower_s = REGISTRY.get("fedml_xla_lower_seconds_total")
    assert {trace_s.name, lower_s.name} <= set(obstrace.XLA_COUNTERS)
    inner = jax.jit(lambda v: jnp.tanh(v) * 2.0)
    fresh = jax.jit(lambda v: inner(v) - v)  # a jit traced inside another's trace
    x = jnp.arange(5.0)
    with traced("t.trace", counters=obstrace.XLA_COUNTERS) as span:
        fresh(x).block_until_ready()
    grew = {k: v[1] for k, v in span.attrs["counters"].items()}
    assert 0 < grew[trace_s.name] <= span.duration_s and 0 < grew[lower_s.name] <= span.duration_s
    before = trace_s.value(), lower_s.value()
    fresh(x).block_until_ready()
    assert (trace_s.value(), lower_s.value()) == before
    # what jax 0.9.0 records: a nested trace reports first and lies inside its caller's
    time.sleep(0.02)  # the real traces above ended before the planted ones start
    obstrace._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.002)
    obstrace._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.008)
    assert trace_s.value() == pytest.approx(before[0] + 0.008)
    obstrace._on_duration("/jax/core/compile/jaxpr_trace_duration", 1e-6)  # after it: its own
    obstrace._on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5)
    assert (trace_s.value(), lower_s.value()) == pytest.approx((before[0] + 0.008 + 1e-6, before[1] + 0.5))


# ------------------------------------------------------------- LLMTrainer.fit
@pytest.fixture(scope="module")
def tiny_trainer():
    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
    from fedml_tpu.models.transformer import TransformerConfig
    from fedml_tpu.obs.metrics import MetricsLogger
    from fedml_tpu.parallel import mesh as meshlib

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
                            d_ff=64, max_seq_len=16)
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=jax.devices()[:1])
    obstrace.clear_recent()
    tr = LLMTrainer(cfg, LLMTrainArgs(batch_size=2, seq_len=16, total_steps=10), mesh=mesh,
                    logger=MetricsLogger(stdout=False))
    return tr, obstrace.recent()


def _batches(n):
    for i in range(n):
        t = np.random.default_rng(i).integers(0, 64, (2, 16)).astype(np.int32)
        yield t, np.roll(t, -1, 1)


def test_llm_init_spans(tiny_trainer):
    _, spans = tiny_trainer
    init = _names(spans, "llm.init")
    assert len(init) == 1
    for name in ("llm.init.params", "llm.init.opt"):
        (child,) = _names(spans, name)
        assert child.parent_id == init[0].span_id


@pytest.mark.parametrize("n_batches,steps,want", [(3, None, 3), (5, 2, 2)])
def test_fit_span_tree(tiny_trainer, n_batches, steps, want):
    tr, _ = tiny_trainer
    obstrace.clear_recent()
    hist = tr.fit(_batches(n_batches), steps=steps)
    spans = obstrace.recent()
    assert len(hist) == want
    by_id = {s.span_id: s for s in spans}
    (fit,) = _names(spans, "llm.fit")
    assert set(fit.attrs["counters"]) == set(obstrace.XLA_COUNTERS)
    steps_ = _names(spans, "llm.step")
    assert [s.attrs["step"] for s in steps_] == [h["step"] for h in hist]
    assert len(_names(spans, "llm.next_batch")) == want + 1  # the last one ends the loop
    for name, parent in (("llm.next_batch", "llm.fit"), ("llm.step", "llm.fit"),
                         ("llm.log", "llm.fit"), ("llm.h2d", "llm.step"),
                         ("llm.dispatch", "llm.step"), ("llm.sync", "llm.step")):
        mine = _names(spans, name)
        assert len(mine) == (want + 1 if name == "llm.next_batch" else want), name
        for s in mine:
            par = by_id[s.parent_id]
            assert par.name == parent
            assert par.start_mono <= s.start_mono and s.end_mono <= par.end_mono
    # step_time_s is what llm.step times: inside the span, and all but all of it
    for h, s in zip(hist, steps_):
        assert h["step_time_s"] <= s.duration_s
        assert s.duration_s - h["step_time_s"] < 5e-3
        kids = [c for c in spans if c.parent_id == s.span_id]
        assert sum(c.duration_s for c in kids) <= s.duration_s


# ------------------------------------------------------ run_rounds / evaluate
@pytest.fixture(scope="module")
def tiny_sim():
    import fedml_tpu
    from fedml_tpu.runner import FedMLRunner

    obstrace.clear_recent()
    # a ragged population and a cohort that does not fill the 2-device mesh
    cfg = fedml_tpu.init(tiny_config(partition_method="hetero", client_num_in_total=16,
                                     client_num_per_round=15, comm_round=100, epochs=2,
                                     mesh_shape="clients:2"))
    sim = FedMLRunner(cfg).runner
    return sim, obstrace.recent()


def test_entry_and_sim_init_spans(tiny_sim):
    _, spans = tiny_sim
    by_id = {s.span_id: s for s in spans}
    assert len(_names(spans, "entry.init")) == 1
    (runner,) = _names(spans, "entry.runner")
    (init,) = _names(spans, "sim.init")
    assert by_id[init.parent_id] is runner
    for part in ("stack_clients", "place_data", "model_init", "eval_fn"):
        (s,) = _names(spans, "sim.init." + part)
        assert s.parent_id == init.span_id


def test_run_rounds_span_tree_and_sample_counts(tiny_sim):
    sim, _ = tiny_sim
    counter = REGISTRY.get("fedml_sim_samples_total")
    before = counter.value(kind="real"), counter.value(kind="lane")
    start = sim.round_idx
    obstrace.clear_recent()
    rounds = sim.run_rounds(3)
    ev = sim.evaluate()
    spans = obstrace.recent()
    by_id = {s.span_id: s for s in spans}
    # what run_rounds and evaluate return is what they returned before
    assert [sorted(r) for r in rounds] == [["num_samples", "num_steps", "train_loss"]] * 3
    assert sorted(ev) == ["test_acc", "test_loss"]
    want = {"sim.run_rounds": None, "sim.stage": "sim.run_rounds", "sim.chunk": "sim.run_rounds",
            "sim.chunk_compile": "sim.stage", "sim.dispatch": "sim.chunk",
            "sim.metrics_sync": "sim.chunk", "sim.eval": None,
            "sim.eval.dispatch": "sim.eval", "sim.eval.sync": "sim.eval"}
    for name, parent in want.items():
        (s,) = _names(spans, name)
        if parent is None:
            assert s.parent_id is None
        else:
            par = by_id[s.parent_id]
            assert par.name == parent
            assert par.start_mono <= s.start_mono and s.end_mono <= par.end_mono
    (rr,) = _names(spans, "sim.run_rounds")
    assert rr.attrs["rounds"] == 3 and rr.attrs["start_round"] == start
    assert set(rr.attrs["counters"]) == set(obstrace.XLA_COUNTERS)
    # real: the sampled clients' own counts, replayed from the sampling rule
    cfg = sim.cfg
    counts = np.array([len(ix) for ix in sim.dataset.client_idx])
    root = jax.random.PRNGKey(cfg.random_seed)
    # lane: what the lanes computed, summed on the device: 15 clients padded
    # with client 0 to the 2-device mesh, sorted by step budget, each bucket of
    # lanes run to its own longest client
    lanes, buckets = sim._lanes, sim._lane_buckets
    assert (lanes, buckets) == (16, 2) and rr.attrs["lane_buckets"] == 2
    real = lane = 0
    for r in range(start, start + 3):
        perm = jax.random.permutation(jax.random.fold_in(root, r), cfg.client_num_in_total)
        cohort = counts[np.asarray(perm[:cfg.client_num_per_round])]
        real += int(cohort.sum()) * cfg.epochs
        budgets = cfg.epochs * -(-np.append(cohort, counts[0]) // cfg.batch_size)
        longest = np.sort(budgets)[::-1].reshape(buckets, lanes // buckets)[:, 0]
        lane += int(longest.sum()) * (lanes // buckets) * cfg.batch_size
    assert (rr.attrs["real_samples"], rr.attrs["lane_samples"]) == (real, lane)
    # every lane at full capacity is what a program without buckets computes
    old_formula = lanes * -(-sim.capacity // cfg.batch_size) * cfg.batch_size * cfg.epochs * 3
    assert 0 < real <= lane <= old_formula
    assert counter.value(kind="real") == before[0] + real
    assert counter.value(kind="lane") == before[1] + lane
    # a second chunk of the same length builds nothing
    sim.run_rounds(3)
    rr2 = _names(obstrace.recent(), "sim.run_rounds")[-1]
    assert rr2.attrs["counters"]["fedml_xla_compiles_total"][1] == 0
    assert not _names(obstrace.recent()[len(spans):], "sim.chunk_compile")


def test_single_round_entry_counts_samples_and_returns_its_keys(tiny_sim):
    sim, _ = tiny_sim
    counter = REGISTRY.get("fedml_sim_samples_total")
    before = counter.value(kind="lane")
    m = sim.run_round()
    assert sorted(m) == ["num_samples", "num_steps", "train_loss"]
    assert counter.value(kind="lane") > before


# ------------------------------------------- the reader, on a planted fixture
class _S:
    """What the reader takes from an ``obs.trace.Span``."""

    def __init__(self, name, sid, parent, start_s, dur_s, **attrs):
        self.name, self.span_id, self.parent_id = name, sid, parent
        self.start_mono, self.end_mono, self.attrs = start_s, start_s + dur_s, attrs


OFFSET_S = 1234.5  # program clock minus trace clock
STEP_NS = 10e6


def _llm_fixture(n_setup=2, n_window=3, lag_ns=4e3):
    """``n_setup`` steps of an earlier fit, then a window fit of ``n_window``
    steps of 10 ms on the trace's clock: next_batch 1 ms, h2d 1 ms, dispatch
    1 ms, sync 6 ms, log 0.5 ms, the loop's own 0.5 ms; the device is busy but
    for [2.2, 2.7) ms (under h2d), [3.5, 4.0) (dispatch), [8.5, 9.0) (sync),
    [9.1, 9.4) (log) and [0.2, 0.6) (next_batch) of each step."""
    spans, events, sid = [], [], [0]
    counters = {"fedml_xla_compiles_total": [7.0, 0.0], "fedml_xla_cache_loads_total": [5.0, 0.0],
                "fedml_xla_compile_seconds_total": [3.5, 0.0],
                "fedml_xla_cache_load_seconds_total": [2.5, 0.0]}

    def add(name, parent, start_ns, dur_ns, **attrs):
        sid[0] += 1
        spans.append(_S(name, sid[0], parent, OFFSET_S + start_ns / 1e9, dur_ns / 1e9, **attrs))
        return sid[0]

    def fit(t0, n, in_trace):
        root = add("llm.fit", None, t0, n * STEP_NS + 0.1e6, counters=counters)
        for k in range(n):
            t = t0 + k * STEP_NS
            p = t + lag_ns  # the program's spans open that much after the benchmark's
            add("llm.next_batch", root, p, 1e6)
            step = add("llm.step", root, p + 1e6, 8e6, step=k)
            add("llm.h2d", step, p + 2e6, 1e6)
            add("llm.dispatch", step, p + 3e6, 1e6)
            add("llm.sync", step, p + 4e6, 4.9e6)
            add("llm.log", root, p + 9e6, 0.5e6)
            if in_trace:
                events.append({"plane": "/host:CPU", "line": "python", "name": "bench.llm_step",
                               "start_ns": t + 1e6, "dur_ns": 9e6})
                busy = [(0.0, 0.2e6), (0.6e6, 2.2e6), (2.7e6, 3.5e6), (4.0e6, 8.5e6),
                        (9.0e6, 9.1e6), (9.4e6, 10e6)]
                for a, b in busy:
                    events.append({"plane": "/device:TPU:0", "line": "XLA Ops", "name": "fusion.1",
                                   "start_ns": t + a, "dur_ns": b - a, "category": "fusion:kLoop"})
        add("llm.next_batch", root, t0 + n * STEP_NS, 0.05e6)

    add("llm.init", None, -900e6, 400e6)
    fit(-500e6, n_setup, in_trace=False)
    fit(0.0, n_window, in_trace=True)
    events.append({"plane": "/host:CPU", "line": "python", "name": "bench.window",
                   "start_ns": 0.0, "dur_ns": n_window * STEP_NS})
    return spans, events


def _reduce(spans, events, attempted):
    return program_spans.reduce(program_spans.as_records(spans), {"attempted": attempted}, events)


def test_reader_recovers_the_planted_offset_and_picks_the_last_n():
    spans, events = _llm_fixture()
    st = _reduce(spans, events, 3)
    assert st["shift_ns"] == pytest.approx((OFFSET_S * 1e9) + 4e3, abs=1.0)
    assert [t["attrs"]["step"] for t in st["tops"]["llm.step"]] == [0, 1, 2]
    assert st["root"]["name"] == "llm.fit"
    assert min(r["start_ns"] for r in st["window"]) == pytest.approx(OFFSET_S * 1e9, abs=1.0)
    # set-up is what ended before: llm.init and the earlier fit's 2 steps
    assert sorted({r["name"] for r in st["setup"]}) == sorted(
        {"llm.init", "llm.fit", "llm.next_batch", "llm.step", "llm.h2d", "llm.dispatch",
         "llm.sync", "llm.log"})
    assert len([r for r in st["setup"] if r["name"] == "llm.step"]) == 2


def test_reader_self_time_subtracts_children_and_steps_add_up():
    spans, events = _llm_fixture()
    st = _reduce(spans, events, 3)
    assert len(st["pieces"]) == 3
    p = st["pieces"][0]
    want = {"llm.next_batch": 1e6, "llm.h2d": 1e6, "llm.dispatch": 1e6, "llm.sync": 4.9e6,
            "llm.log": 0.5e6, "llm.step.self": 8e6 - 6.9e6, "loop.self": 10e6 - 9.5e6}
    assert p["by_span_ns"] == pytest.approx(want, abs=1.0)
    assert p["host_and_device_ns"] == pytest.approx(STEP_NS, abs=1.0)
    ctx = {"window": {"attempted": 3}, "events": events, "cell": {"name": "t"},
           program_spans._STATE: st}
    assert program_spans.piece_host_ms(ctx, {"less": ["llm.sync"], "q": 0.5}) == pytest.approx(5.1)
    assert program_spans.piece_host_ms(ctx, {"less": ["llm.sync"], "q": "max"}) == pytest.approx(5.1)


def test_reader_idle_shares_add_up_to_the_device_idle_share():
    import bench_trace

    spans, events = _llm_fixture()
    st = _reduce(spans, events, 3)
    ctx = {program_spans._STATE: st}
    named = ["llm.h2d", "llm.dispatch", "llm.sync"]
    shares = [program_spans.idle_share(ctx, {"spans": [n]}) for n in named]
    rest = program_spans.idle_share(ctx, {"other_than": named})
    # the gap at [8.5, 9.0) ms of a step lies under llm.sync to 8.9 and under
    # llm.step's own time after it: a gap is split over the spans it crosses
    assert shares == pytest.approx([5.0, 5.0, 4.0])
    assert rest == pytest.approx(3.0 + 4.0 + 1.0)  # llm.log, llm.next_batch, llm.step
    assert st["idle"]["by_span_ns"]["llm.step"] == pytest.approx(3 * 0.1e6)
    busy = bench_trace.busy(events)
    assert sum(shares) + rest == pytest.approx(100.0 * (1 - busy["busy_s"] / busy["window_s"]))


def test_reader_counters_and_set_up():
    spans, events = _llm_fixture()
    ctx = {program_spans._STATE: _reduce(spans, events, 3)}
    at = program_spans.counter_at_window_start
    assert at(ctx, {"counter": "fedml_xla_compiles_total"}) == 7.0
    assert at(ctx, {"counter": "fedml_xla_cache_load_seconds_total"}) == 2.5
    assert program_spans.window_counter_growth(
        ctx, {"counters": ["fedml_xla_compiles_total", "fedml_xla_cache_loads_total"]}) == 0.0
    assert program_spans.setup_span_s(ctx, {"spans": ["llm.init", "entry.init"]}) == pytest.approx(0.4)
    assert program_spans.window_attr_ratio(
        ctx, {"span": "sim.run_rounds", "num": "real_samples", "den": "lane_samples"}) is None


@pytest.mark.parametrize("break_it", ["one_anchor_less", "span_outside_its_anchor", "too_few_steps"])
def test_reader_gives_none_on_mismatched_anchors(break_it):
    spans, events = _llm_fixture()
    attempted = 3
    if break_it == "one_anchor_less":
        events.remove(next(e for e in events if e["name"] == "bench.llm_step"))
    elif break_it == "span_outside_its_anchor":
        last = [s for s in spans if s.name == "llm.step"][-1]
        last.end_mono += 5e-3  # 5 ms past the end of its bench.llm_step
    else:
        attempted = 6
    st = _reduce(spans, events, attempted)
    if break_it == "too_few_steps":
        assert st is None
        return
    assert st["shift_ns"] is None and st["idle"] is None and st["why_no_clock"]
    ctx = {program_spans._STATE: st}
    assert program_spans.idle_share(ctx, {"spans": ["llm.sync"]}) is None
    # durations and counters need no clock
    assert program_spans.piece_host_ms(ctx, {"less": ["llm.sync"], "q": 0.5}) is not None
    assert program_spans.counter_at_window_start(ctx, {"counter": "fedml_xla_compiles_total"}) == 7.0


def test_reader_on_the_program_s_own_ring_of_a_sim_window(tiny_sim, tmp_path, monkeypatch):
    """End to end on the CPU: real spans of two chunks with their evaluates,
    anchors made from them with a planted offset, and no device in the trace."""
    sim, _ = tiny_sim
    obstrace.clear_recent()
    sim.run_rounds(3), sim.evaluate()   # an earlier chunk: set-up
    for _ in range(2):
        sim.run_rounds(3), sim.evaluate()
    recs = program_spans.as_records(obstrace.recent())
    events = []
    for name, anchor in (("sim.run_rounds", "bench.run_rounds"), ("sim.eval", "bench.evaluate")):
        for r in [r for r in recs if r["name"] == name][-2:]:
            events.append({"plane": "/host:CPU", "line": "python", "name": anchor,
                           "start_ns": r["start_ns"] - 77e9 - 3e3, "dur_ns": r["dur_ns"] + 6e3})
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    ctx = {"window": {"attempted": 2}, "events": events, "cell": {"name": "tiny.cell"}}
    st = program_spans.load(ctx)
    assert st["shift_ns"] == pytest.approx(77e9 + 3e3, abs=1.0)
    assert len(st["pieces"]) == 2 and len(st["tops"]["sim.eval"]) == 2
    share = program_spans.window_attr_ratio(
        ctx, {"span": "sim.run_rounds", "num": "real_samples", "den": "lane_samples"})
    assert 0 < share < 100
    host = program_spans.piece_host_ms(ctx, {"less": ["sim.metrics_sync", "sim.eval.sync"], "q": 0.5})
    whole = program_spans.piece_host_ms(ctx, {"less": [], "q": 0.5})
    assert 0 < host < whole
    assert program_spans.window_counter_growth(
        ctx, {"counters": ["fedml_xla_compiles_total", "fedml_xla_cache_loads_total"]}) == 0
    assert program_spans.setup_span_s(ctx, {"spans": ["sim.eval"]}) > 0
    assert (tmp_path / "chiprun_out" / "bench" / "spans.tiny.cell.json").exists()


def test_reader_returns_none_for_a_program_without_the_ring(monkeypatch):
    monkeypatch.delattr(obstrace, "recent")
    ctx = {"window": {"attempted": 2}, "events": [], "cell": {"name": "t"}}
    assert program_spans.load(ctx) is None
    assert program_spans.piece_host_ms(ctx, {"less": [], "q": 0.5}) is None
    assert program_spans.idle_share(ctx, {"spans": ["llm.sync"]}) is None
    assert program_spans.counter_at_window_start(ctx, {"counter": "fedml_xla_compiles_total"}) is None
    assert program_spans.window_counter_growth(ctx, {"counters": []}) is None
    assert program_spans.setup_span_s(ctx, {"spans": ["llm.init"]}) is None
