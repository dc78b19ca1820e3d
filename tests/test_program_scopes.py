"""Device time by the program's own phases (ISSUE 37): ``obs/scopes.py``
(what the step, chunk and eval programs publish, and the map from HLO
instruction to named scope read off their text) and the reader
``benchmark/program_scopes.py`` on synthetic events joined with a real map."""

import gc
import json
import os
import sys
import weakref

import jax
import numpy as np
import pytest

from fedml_tpu.obs import scopes, trace as obstrace

from .conftest import tiny_config

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "benchmark"))
import program_scopes  # noqa: E402

BUILDS = ("fedml_xla_compiles_total", "fedml_xla_cache_loads_total")


def _last_map_span(program):
    return [s for s in obstrace.recent()
            if s.name == "obs.scope_map" and s.attrs["program"] == program][-1]


def _passes_by_scope(m):
    out = {}
    for e in m.values():
        for s in e["scopes"]:
            out.setdefault(s, set()).add(e["pass"])
    return out


@pytest.fixture(scope="module")
def compiled_here():
    """The persistent cache's key leaves the metadata out, so a program loaded
    from an entry that another commit wrote carries that commit's scopes: the
    programs whose maps this file reads are compiled in this process."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _tiny_trainer():
    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
    from fedml_tpu.models.transformer import TransformerConfig
    from fedml_tpu.obs.metrics import MetricsLogger
    from fedml_tpu.parallel import mesh as meshlib

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
                            d_ff=64, max_seq_len=16, remat=True, remat_policy="dots")
    mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=jax.devices()[:1])
    return LLMTrainer(cfg, LLMTrainArgs(batch_size=2, seq_len=16, total_steps=10), mesh=mesh,
                      logger=MetricsLogger(stdout=False))


def _batches(n):
    for i in range(n):
        t = np.random.default_rng(i).integers(0, 64, (2, 16)).astype(np.int32)
        yield t, np.roll(t, -1, 1)


# ----------------------------------------------------------- the step program
@pytest.fixture(scope="module")
def step_map(compiled_here):
    """The map of a tiny trainer's step after two steps, built while the
    trainer lives, with the span that built it and what was noted."""
    tr = _tiny_trainer()
    tr.fit(_batches(2))
    program, args = scopes._noted["llm.step"]
    assert program is tr._train_step
    leaves = jax.tree_util.tree_leaves(args)  # what is noted holds no device array
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)
    assert leaves[0].sharding == jax.tree_util.tree_leaves(tr.params)[0].sharding
    return scopes.scope_map("llm.step"), _last_map_span("llm.step"), (program, args)


def _map_after_the_trainer_is_gone():
    tr = _tiny_trainer()
    tr.fit(_batches(2))
    dead = weakref.ref(tr)
    del tr
    gc.collect()
    assert dead() is None  # nothing noted holds the trainer, and the map needs none
    return scopes.scope_map("llm.step"), _last_map_span("llm.step")


@pytest.mark.parametrize("trainer_alive", [True, False])
def test_llm_step_map_names_its_scopes_and_builds_nothing(trainer_alive, step_map):
    m, span = step_map[:2] if trainer_alive else _map_after_the_trainer_is_gone()
    assert [span.attrs["counters"][c][1] for c in BUILDS] == [0, 0]
    assert span.attrs["ops"] == len(m) and span.attrs["text_bytes"] > 10_000
    assert span.attrs["scoped_ops"] == sum(1 for e in m.values() if e["scope"])
    by = _passes_by_scope(m)
    assert by["llm.optimizer"] == {""}
    for scope in ("llm.head_loss", "llm.mlp", "llm.mixer.attention", "llm.mixer.attention.core"):
        assert {"fwd", "bwd"} <= by[scope], scope
    assert "remat" in by["llm.mixer.attention.core"]  # the blocks are checkpointed
    # every op of a mixer's core is an op of the mixer, and of the gradient
    for e in m.values():
        if "llm.mixer.attention.core" in e["scopes"]:
            assert e["scopes"][:2] == ["llm.fwd_bwd", "llm.mixer.attention"]
    if not trainer_alive:
        assert scopes.scope_map("llm.step") is m  # built once
        assert _last_map_span("llm.step") is span


# ------------------------------------------------- the chunk and eval programs
def test_sim_chunk_and_eval_maps(compiled_here):
    import fedml_tpu
    from fedml_tpu.runner import FedMLRunner

    cfg = fedml_tpu.init(tiny_config(partition_method="hetero", client_num_in_total=16,
                                     client_num_per_round=15, comm_round=100, epochs=2,
                                     mesh_shape="clients:2"))
    sim = FedMLRunner(cfg).runner
    sim.run_rounds(2)
    sim.evaluate()
    chunk, ev = scopes.scope_map("sim.chunk"), scopes.scope_map("sim.eval")
    for program in ("sim.chunk", "sim.eval"):
        assert [_last_map_span(program).attrs["counters"][c][1] for c in BUILDS] == [0, 0], program
    by = _passes_by_scope(chunk)
    assert {"fl.gather", "fl.local_sgd", "fl.fold"} <= set(by)
    assert {"fwd", "bwd"} <= by["fl.local_sgd"]
    assert set(_passes_by_scope(ev)) == {"fl.eval"}
    # the two programs name some of their instructions alike: why the reader takes a span
    assert set(chunk) & set(ev)
    # a second chunk length is a second program: the newest is the one published
    sim.run_rounds(1)
    assert scopes.scope_map("sim.chunk") is not chunk


# ------------------------------------------------------------------ the parser
_FWD_BWD = "jit(<lambda>)/llm.fwd_bwd/"
LINES = {
    "jvp": ('%fusion.600 = bf16[4,2048,32000]{2,1,0:T(8,128)(2,1)} fusion(%copy-done.94, %p.1), kind=kOutput, '
            'calls=%fused_computation.2, metadata={op_name="' + _FWD_BWD
            + 'jvp(Transformer)/llm.head_loss/lm_head/dot_general" stack_frame_id=203}',
            "fusion.600", ["llm.fwd_bwd", "llm.head_loss"], "fwd"),
    "transpose_of_jvp": (
        'ROOT %fusion.140 = (f32[4096]{0:T(1024)}, bf16[4,2048,4096]{2,1,0}) fusion(%fusion.514), kind=kOutput, '
        'calls=%fused_computation.225, metadata={op_name="' + _FWD_BWD
        + 'transpose(jvp(Transformer))/llm.head_loss/lm_head/dot_general" stack_frame_id=203}, backend_config={"a":["1"]}',
        "fusion.140", ["llm.fwd_bwd", "llm.head_loss"], "bwd"),
    "vmap": ('%reduce.7 = f32[8,10]{1,0} reduce(%x, %c), dimensions={1}, to_apply=%region_1.2, metadata={op_name='
             '"jit(multi)/while/body/closed_call/fl.local_sgd/while/body/closed_call/vmap()/while/body/'
             'vmap(transpose(jvp(LogisticRegression)))/Dense_0/reduce_sum" source_file="m.py" source_line=3}',
             "reduce.7", ["fl.local_sgd"], "bwd"),
    "checkpoint_recomputed_with_a_doubled_scope": (
        '%multiply_reduce_fusion.1 = bf16[4,2048,14336]{2,1,0} fusion(%a, %b), kind=kLoop, calls=%fused_computation.9, '
        'metadata={op_name="' + _FWD_BWD + 'transpose(jvp(Transformer))/llm.fwd_bwd/jvp(Transformer)/checkpoint/'
        'rematted_computation/layer_1/mlp/llm.mlp/jit(silu)"}',
        "multiply_reduce_fusion.1", ["llm.fwd_bwd", "llm.mlp"], "remat"),
    "checkpoint_backward": (
        '%fusion.151 = bf16[4096,14336]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.7, '
        'metadata={op_name="' + _FWD_BWD + 'transpose(jvp(Transformer))/llm.fwd_bwd/jvp(Transformer)/checkpoint/'
        'layer_0/mlp/llm.mlp/w_gate/dot_general"}',
        "fusion.151", ["llm.fwd_bwd", "llm.mlp"], "bwd"),
    "a_wrapped_scope": ('%select.4 = s32[2,16]{1,0} select(%lt, %add, %t), metadata={op_name="' + _FWD_BWD
                        + 'jvp(llm.head_loss)/jit(_take)/jit(_where)/select_n"}',
                        "select.4", ["llm.fwd_bwd", "llm.head_loss"], "fwd"),
    "nested_scopes": ('%convolution_reduce-precision_fusion = bf16[1,8,128]{2,1,0} fusion(%q), kind=kOutput, '
                      'calls=%fused_computation.3, metadata={op_name="' + _FWD_BWD + 'jvp(Transformer)/layer_0/attn/'
                      'llm.mixer.mamba/llm.mixer.mamba.ssd/jit(_forward)/dot_general"}',
                      "convolution_reduce-precision_fusion",
                      ["llm.fwd_bwd", "llm.mixer.mamba", "llm.mixer.mamba.ssd"], "fwd"),
    "outside_a_gradient": ('%add.9 = f32[32]{0} add(%a, %b), metadata={op_name="jit(<lambda>)/llm.optimizer/add"}',
                           "add.9", ["llm.optimizer"], ""),
    "custom_call": ('%custom-call.3 = s32[8192]{0:T(1024)} custom-call(%param_1.13), custom_call_target='
                    '"AssumeGatherIndicesInBound", operand_layout_constraints={s32[8192]{0:T(1024)}}, metadata={op_name="'
                    + _FWD_BWD + 'jvp(Transformer)/embed/jit(_take)/gather" stack_frame_id=59}',
                    "custom-call.3", ["llm.fwd_bwd"], "fwd"),
    "no_metadata": ('%copy.367 = f32[4096,32,128]{2,1,0:T(8,128)} copy(%get-tuple-element.274), sharding={replicated}, '
                    'backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":[]}}',
                    "copy.367", [], ""),
    "a_parameter": ("%params__embed.1 = f32[64,32]{1,0} parameter(0), sharding={replicated}, "
                    "metadata={op_name=\"params[\\'embed\\'][\\'embedding\\']\"}",
                    "params__embed.1", [], ""),
}


@pytest.mark.parametrize("case", sorted(LINES))
def test_parser_on_recorded_lines(case):
    line, name, want_scopes, want_pass = LINES[case]
    text = f"HloModule m\n\nENTRY %main.1 (p: f32[2]) -> f32[2] {{\n  {line}\n}}\n"
    (got_name, entry), = scopes.parse_hlo_text(text).items()
    assert got_name == name
    assert (entry["scopes"], entry["pass"]) == (want_scopes, want_pass)
    assert entry["scope"] == (want_scopes[-1] if want_scopes else "")
    if case != "no_metadata":
        assert entry["op_name"] and entry["op_name"] in line


def test_parser_leaves_a_fusions_inside_to_the_fusion():
    text = "\n".join([
        "HloModule m", "",
        "%fused_computation.1 (param_0: f32[2]) -> f32[2] {",
        '  %param_0 = f32[2]{0} parameter(0)',
        '  ROOT %add.1 = f32[2]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/llm.mlp/add"}',
        "}", "",
        "%region_0.1 (a: f32[], b: f32[]) -> f32[] {",
        '  ROOT %add.2 = f32[] add(%a, %b), metadata={op_name="jit(f)/llm.optimizer/reduce_sum"}',
        "}", "",
        "ENTRY %main.3 (p: f32[2]) -> f32[2] {",
        "  %p = f32[2]{0} parameter(0)",
        '  ROOT %fusion.1 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, '
        'metadata={op_name="jit(f)/llm.mlp/add"}',
        "}"])
    m = scopes.parse_hlo_text(text)
    assert sorted(m) == ["add.2", "fusion.1", "p"]
    assert m["fusion.1"]["scope"] == "llm.mlp"


def test_device_seconds_by_scope_pass_and_expression():
    m = {n: scopes.parse_op_name(o) for n, o in {
        "a": "jit(f)/fl.local_sgd/vmap(jvp(ResNet))/BatchNorm_0/mul",
        "b": "jit(f)/fl.local_sgd/vmap(transpose(jvp(ResNet)))/BatchNorm_11/mul",
        "c": "jit(f)/fl.fold/reduce_sum", "d": ""}.items()}
    secs = {"a": 1.0, "b": 2.0, "c": 4.0, "d": 8.0, "not_in_the_map": 16.0}
    assert scopes.device_seconds_by(secs, m, "scope") == {"fl.local_sgd": 3.0, "fl.fold": 4.0, "": 24.0}
    assert scopes.device_seconds_by(secs, m, "pass") == {"fwd": 1.0, "bwd": 2.0, "": 28.0}
    assert scopes.device_seconds_by(secs, m, r"/BatchNorm_\d+/") == {
        "/BatchNorm_0/": 1.0, "/BatchNorm_11/": 2.0, "": 28.0}


# ------------------------------------------- the reader, on synthetic events
class _Text:
    """What ``scope_map`` takes from a compiled program."""

    def __init__(self, *instructions, no_text=False):
        self.text = None if no_text else "ENTRY %main (p: f32[2]) -> f32[2] {\n" + "".join(
            f'  %{name} = f32[2]{{0}} add(%p, %p), metadata={{op_name="{op_name}"}}\n'
            for name, op_name in instructions) + "}\n"

    def as_text(self):
        return self.text


def _op(name, start_ms, dur_ms):
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name, "category": "fusion:kLoop",
            "start_ns": start_ms * 1e6, "dur_ns": dur_ms * 1e6}


def _host(name, start_ms, dur_ms):
    return {"plane": "/host:CPU", "line": "python", "name": name,
            "start_ns": start_ms * 1e6, "dur_ns": dur_ms * 1e6}


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(program_scopes, "ROOT", str(tmp_path))
    return tmp_path / "chiprun_out" / "bench"


def _ctx(events, cell="t.scopes"):
    return {"events": events, "cell": {"name": cell}}


def test_reader_shares_on_the_real_step_map_add_up(out_dir, step_map):
    scopes.note_program("llm.step", *step_map[2])  # the newest step noted may be another test's
    m = scopes.scope_map("llm.step")
    assert m == step_map[0]
    # every instruction of the map runs for its own number of milliseconds, and one the map lacks
    events = [_host("bench.window", 0.0, 1e6)]
    t = 1.0
    for i, name in enumerate(sorted(m)):
        events.append(_op(name, t, 1.0 + i % 7))
        t += 10.0
    events.append(_op("fusion.of_another_program", t, 9.0))
    ctx = _ctx(events)
    share = lambda **args: program_scopes.scope_share(ctx, {"program": "llm.step", **args})
    leaves = {e["scope"] for e in m.values()} - {""}
    at_leaf = {s: sum(e["dur_ns"] for e in events if m.get(e["name"], {}).get("scope") == s) for s in leaves}
    total = sum(e["dur_ns"] for e in events if "category" in e)
    # a scope with no scope below it: its prefix share is its leaf share
    for s in ("llm.optimizer", "llm.mlp", "llm.mixer.attention.core", "llm.head_loss"):
        assert share(scope=s) == pytest.approx(100.0 * at_leaf[s] / total), s
    assert share(scope="llm.mixer") == pytest.approx(
        100.0 * (at_leaf["llm.mixer.attention"] + at_leaf["llm.mixer.attention.core"]) / total)
    by_pass = [share(scope="llm.fwd_bwd", **{"pass": p}) for p in ("fwd", "remat", "bwd")]
    assert sum(by_pass) == pytest.approx(share(scope="llm.fwd_bwd"))
    assert share(scope="llm.fwd_bwd", **{"pass": ["remat", "bwd"]}) == pytest.approx(by_pass[1] + by_pass[2])
    scoped = share(scope="llm", other_than=["llm.fwd_bwd"])
    assert scoped == pytest.approx(100.0 * sum(v for s, v in at_leaf.items() if s != "llm.fwd_bwd") / total)
    # the record: seconds by innermost scope, with what has none, are the program's device seconds
    with open(out_dir / "scopes.t.scopes.json") as fh:
        rec = json.load(fh)
    (prog,) = rec["programs"]
    assert prog["program"] == "llm.step" and prog["span"] is None
    assert rec["window_device_seconds"] == pytest.approx(total / 1e9)
    assert prog["device_seconds"] == pytest.approx(total / 1e9)
    assert prog["unmapped_seconds"] == pytest.approx(9e-3)
    cells = prog["seconds_by_scope_and_pass"]
    assert sum(v for row in cells.values() for v in row.values()) == pytest.approx(prog["device_seconds"], rel=1e-9)
    for s in leaves:
        assert sum(row.get(s, 0.0) for row in cells.values()) == pytest.approx(at_leaf[s] / 1e9), s
    assert 100.0 * sum(sum(row.get(s, 0.0) for row in cells.values()) for s in [*leaves, ""]) / (total / 1e9) \
        == pytest.approx(100.0)
    assert len(prog["heaviest"]) == program_scopes.HEAVIEST
    assert prog["heaviest"][0] == {"op": "fusion.of_another_program", "seconds": pytest.approx(9e-3),
                                   "scope": "", "pass": "", "op_name": ""}
    assert prog["heaviest"][1]["seconds"] == pytest.approx(7e-3)
    loose = prog["heaviest_unscoped"]
    assert len(loose) == program_scopes.UNSCOPED and not any(r["scope"] for r in loose)
    assert loose[0]["op"] == "fusion.of_another_program"
    assert [prog["map_cost"]["counters"][c][1] for c in BUILDS] == [0, 0]


def test_reader_keeps_two_programs_with_a_colliding_name_apart(out_dir):
    scopes.note_program("t.chunk", _Text(("fusion.1", "jit(multi)/fl.local_sgd/mul"),
                                         ("fusion.2", "jit(multi)/fl.fold/add")))
    scopes.note_program("t.eval", _Text(("fusion.1", "jit(eval_fn)/fl.eval/mul")))
    events = [_host("bench.window", 0.0, 100.0)]
    for k in range(2):  # run_rounds [0, 30) then evaluate [30, 40), twice
        t0 = 50.0 * k
        events += [_host("bench.run_rounds", t0, 30.0), _host("bench.evaluate", t0 + 30.0, 10.0),
                   _op("fusion.1", t0 + 1.0, 20.0), _op("fusion.2", t0 + 22.0, 6.0),
                   _op("fusion.1", t0 + 31.0, 4.0)]
    ctx = _ctx(events, "t.two")
    chunk = {"program": "t.chunk", "span": "bench.run_rounds"}
    ev = {"program": "t.eval", "span": "bench.evaluate"}
    assert program_scopes.scope_share(ctx, {**chunk, "scope": "fl.local_sgd"}) == pytest.approx(100 * 40 / 60)
    assert program_scopes.scope_share(ctx, {**chunk, "scope": "fl.fold"}) == pytest.approx(100 * 12 / 60)
    assert program_scopes.scope_share(ctx, {**ev, "scope": "fl.eval"}) == pytest.approx(100 * 8 / 60)
    assert program_scopes.scope_share(ctx, {**chunk, "match": r"fl\.\w+/mul"}) == pytest.approx(100 * 40 / 60)
    with open(out_dir / "scopes.t.two.json") as fh:
        rec = json.load(fh)
    assert [(p["program"], p["span"], round(p["device_seconds"], 9)) for p in rec["programs"]] == [
        ("t.chunk", "bench.run_rounds", 0.052), ("t.eval", "bench.evaluate", 0.008)]
    # without the span the evaluation's fusion.1 would count as the chunk's
    assert program_scopes.scope_share(_ctx(events, "t.two"), {"program": "t.chunk", "scope": "fl.local_sgd"}) \
        == pytest.approx(100 * 48 / 60)


@pytest.mark.parametrize("why", ["no_map_published", "no_op_matched", "under_half_named", "no_text"])
def test_reader_gives_none(why, out_dir, capfd):
    scopes.note_program("t.step", _Text(("fusion.1", "jit(f)/llm.mlp/mul")))
    events = [_host("bench.window", 0.0, 100.0), _op("fusion.1", 1.0, 4.0)]
    args = {"program": "t.step", "scope": "llm.mlp"}
    assert program_scopes.scope_share(_ctx(events), args) == pytest.approx(100.0)
    if why == "no_map_published":
        args["program"] = "t.nobody_noted_this"
    elif why == "no_op_matched":
        args["scope"] = "llm.mixer"
    elif why == "under_half_named":
        events.append(_op("fusion.9", 10.0, 4.1))
    else:
        scopes.note_program("t.step", _Text(no_text=True))
    assert program_scopes.scope_share(_ctx(events), args) is None
    said = capfd.readouterr().err
    if why == "under_half_named":
        assert "the map names 0.0040 of 0.0081 device seconds" in said
    elif why != "no_op_matched":
        assert "published no scope map" in said
