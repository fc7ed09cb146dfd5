"""The program names its device work: scopes on the model's regions and the
train step, names on the three flash kernels (metadata only), and
util/profiling.device_regions reduces a profiler trace plus the compiled
step's HLO text to a table by region. All on the CPU: the v5e side is a
recorded trace (benchmark/fixtures) and hand-made events."""

import ast
import contextlib
import json
import os
import re

import pytest

from ray_tpu.util import profiling
from ray_tpu.util.profiling import KERNELS, REGIONS, UNATTRIBUTED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
DASH = "—"


def _step_text(jax, **config):
    """The compiled train step of a tiny GPT on one CPU device, as HLO.
    With reference attention: the scopes are the same, and the interpreted
    flash kernel would be most of the compile (its names: the jaxpr test)."""
    import jax.numpy as jnp
    import optax
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.train_step import init_train_state, make_train_step

    accum = config.pop("accum_steps", 0)
    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=2,
                    d_ff=128, max_seq=32, attention="reference",
                    remat_policy="full", **config)
    mesh = build_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    optimizer = optax.adamw(1e-3)
    state = init_train_state(lambda: gpt_init(jax.random.PRNGKey(0), cfg),
                             optimizer, mesh, "dp")
    batch = {"tokens": jnp.zeros(((accum,) if accum else ()) + (4, 33),
                                 jnp.int32)}
    return make_train_step(
        lambda p, b: gpt_loss(p, b, cfg, mesh=mesh), optimizer, mesh, "dp",
        sample_params=state.params, accum_steps=accum
    ).lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def dense_text(jax_cpu):
    return _step_text(jax_cpu)


# (a) the vocabulary is the names the code opens, and every region of it is
# on some op of a compiled step

def _calls(path, attribute):
    """The calls of `<anything>.<attribute>(..)` in a source file."""
    with open(os.path.join(REPO, *path.split("/"))) as f:
        tree = ast.parse(f.read())
    return [node for node in ast.walk(tree) if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == attribute]


def _strings(node, path):
    """The strings a name's expression can be: a literal, either arm of
    `a if .. else b`, or ops/attention.py's _kernel_name over its cases."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, ast.IfExp):
        return _strings(node.body, path) | _strings(node.orelse, path)
    if isinstance(node, ast.Call) and getattr(node.func, "id", "") \
            == "_kernel_name" and path.endswith("ops/attention.py"):
        from ray_tpu.ops.attention import _kernel_name
        (kernel,) = _strings(node.args[0], path)
        return {_kernel_name(kernel, window, selected)
                for window in (None, 128) for selected in (None, object())}
    raise AssertionError(
        f"{path}:{node.lineno}: a name this test cannot read off the code; "
        "give it a literal, or teach _strings the function that makes it")


def test_the_vocabulary_is_the_names_the_code_opens(jax_cpu):
    """profiling.KERNELS and .REGIONS are a copy the traced run is read by
    (benchmark/worker.py): a kernel or a scope missing there has no row,
    and its metric is absent. Exactly the `name=`s of ray_tpu/ops/*.py's
    pallas_calls and the scopes models/gpt.py and train/train_step.py
    open, both ways."""
    ops = sorted("ray_tpu/ops/" + name for name
                 in os.listdir(os.path.join(REPO, "ray_tpu", "ops"))
                 if name.endswith(".py"))
    kernels = set()
    for path in ops:
        for call in _calls(path, "pallas_call"):
            names = [k.value for k in call.keywords if k.arg == "name"]
            assert names, f"{path}:{call.lineno}: a pallas_call of no name"
            kernels |= _strings(names[0], path)
    regions = set()
    for path in ("ray_tpu/models/gpt.py", "ray_tpu/train/train_step.py"):
        for call in _calls(path, "named_scope"):
            regions |= _strings(call.args[0], path)
    assert len(set(KERNELS)) == len(KERNELS)
    assert len(set(REGIONS)) == len(REGIONS)
    assert kernels == set(KERNELS), kernels ^ set(KERNELS)
    assert regions == set(REGIONS), regions ^ set(REGIONS)
    # a name on both sides would be counted as a region and as a kernel
    assert not set(KERNELS) & set(REGIONS)


# The regions a dense step has. (A family's own scopes are held by its
# `scopes` hook, tests/helpers/families.py: attn_latent and moe_shared in
# tests/test_latent_moe_model.py, on a step that has a latent block and a
# shared expert; conv and conv_mix: tests/test_conv_gqa_model.py; attn_window
# and attn_gate: tests/test_window_attention_model.py; attn_index: tests/
# test_selected_attention_model.py; kda and kda_core: tests/
# test_linear_attention_model.py; moe, moe_route and grad_accum: below)
@pytest.mark.parametrize("region", [
    "embed", "attn_proj", "attn_core", "attn_out", "mlp", "norm", "head",
    "loss_and_grad", "optimizer"])
def test_dense_step_names_region(dense_text, region):
    names = re.findall(r'op_name="([^"]*)"', dense_text)
    assert any(profiling._last_of(n, REGIONS) == region for n in names)


@pytest.fixture(scope="module")
def moe_accumulating_text(jax_cpu):
    return _step_text(jax_cpu, n_experts=2, accum_steps=2)


@pytest.mark.parametrize("region", ["moe", "moe_route", "grad_accum"])
def test_moe_accumulating_step_names_region(moe_accumulating_text, region):
    names = re.findall(r'op_name="([^"]*)"', moe_accumulating_text)
    assert any(profiling._last_of(n, REGIONS) == region for n in names)


@pytest.mark.parametrize("phase", ["forward", "backward", "recompute"])
def test_dense_step_names_phase(dense_text, phase):
    """Direction and recomputation are readable from the same op_name:
    jvp( forward, transpose( backward, rematted_computation under
    remat_policy="full"."""
    names = re.findall(r'op_name="([^"]*)"', dense_text)
    mlp = {profiling._phase(n) for n in names
           if profiling._last_of(n, REGIONS) == "mlp"}
    assert phase in mlp


# (the window kernels' names: tests/test_window_attention.py; the names
# under a selection: tests/test_selected_attention.py)
@pytest.mark.parametrize("kernel",
                         ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
def test_flash_kernels_carry_their_names(jax_cpu, kernel):
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 2, 32, 16), jnp.bfloat16)
    jaxpr = str(jax_cpu.make_jaxpr(jax_cpu.grad(
        lambda q: flash_attention(q, q, q).astype(jnp.float32).sum()))(q))
    assert f"name={kernel}" in jaxpr


# (b) names cost nothing: the step with the scopes is the step without

def _without_metadata(text):
    """The module's instructions without every metadata={..} and without
    the tables of file names and stack frames that metadata indexes."""
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:.+\n)*", "", text, flags=re.M)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", text)


def test_scopes_leave_the_compiled_step_unchanged(jax_cpu, dense_text,
                                                  monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax_cpu, "named_scope",
                        lambda name: contextlib.nullcontext())
    # the persistent cache's key leaves metadata out: with it on, this
    # compile would be handed the executable of the one with the scopes
    was_on = jax_cpu.config.jax_enable_compilation_cache
    jax_cpu.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        bare = _step_text(jax_cpu)
    finally:
        jax_cpu.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
    # as a scope on an op_name path: the bare word may stand in the stack
    # frames' table as a function's name (a trace cached by an earlier test
    # of this process, e.g. the embedding lookup's, keeps its frames)
    scope = "jit(_step)/loss_and_grad"
    assert scope in dense_text and scope not in bare
    assert _without_metadata(dense_text) == _without_metadata(bare)


# (c) the reduction, on hand-made events and on the recorded v5e trace

MS = 1_000_000   # ns
HLO = """HloModule jit__step, is_scheduled=true

%body (p: f32[8]) -> f32[8] {
  %dot.1 = f32[8]{0} dot(%p, %p), metadata={op_name="jit(_step)/loss_and_grad/jvp(head)/while/body/dot_general"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(_step)/loss_and_grad/jvp(mlp)/mul" stack_frame_id=3}
  %flash_fwd.2 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/loss_and_grad/transpose(jvp(loss_and_grad))/jvp()/checkpoint/rematted_computation/attn_core/flash_fwd/pallas_call"}
  %flash_bwd_dq.3 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/loss_and_grad/transpose(jvp(loss_and_grad))/jvp()/checkpoint/attn_core/flash_bwd_dq/pallas_call"}
  %while.4 = f32[8]{0} while(%a), body=%body, metadata={op_name="jit(_step)/loss_and_grad/jvp(head)/while"}
  %all-reduce.5 = f32[8]{0} all-reduce(%a), metadata={op_name="jit(_step)/loss_and_grad/transpose(jvp(mlp))/dot_general"}
  %copy.6 = f32[8]{0} copy(%a)
  %copy.8 = f32[8]{0} copy(%flash_fwd.2)
  %fusion.9 = (f32[8]{0}, f32[8]{0}) fusion(%a), kind=kLoop, calls=%body
  ROOT %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(_step)/optimizer/add"}
}
"""


def _event(name, opcode, start_ms, end_ms):
    return (f"%{name} = f32[8]{{0:T(8)}} {opcode}(%a)", start_ms * MS,
            end_ms * MS)


# one chip, a window of 100 ms: ops back to back from 0 to 60 (the while
# spans its body's dot), a gap of 10 ms, two ops, a gap of 2 ms, one op, a
# gap of 8 ms, and a kernel that runs on past the window's end
HAND_MADE = {
    "devices": {0: [
        _event("fusion.1", "fusion", 0, 10),
        _event("flash_fwd.2", "custom-call", 10, 20),
        _event("flash_bwd_dq.3", "custom-call", 20, 40),
        _event("while.4", "while", 40, 60), _event("dot.1", "dot", 45, 55),
        _event("all-reduce.5", "all-reduce", 70, 75),
        _event("copy.6", "copy", 75, 80),
        _event("fusion.7", "fusion", 82, 87),
        _event("flash_fwd.2", "custom-call", 95, 120)]},
    "host": [(profiling.STRETCH_SPAN, 0, 100 * MS),
             ("host:dispatch", 0, 58 * MS),
             ("train:report", 58 * MS, 72 * MS),
             ("host:wait_step", 72 * MS, 100 * MS)]}

EXPECTED = {
    "window_s": 0.1, "busy_s": 0.08, "busy_pct": 80.0,
    # the last region on the path wins (attn_core under loss_and_grad);
    # the while's own time is 20 - 10 of its body's dot, both `head`
    "rows": [["attn_core", "backward", 0.02, 20.0, 1],
             ["head", "forward", 0.02, 20.0, 2],
             ["attn_core", "recompute", 0.015, 15.0, 2],
             ["mlp", "forward", 0.01, 10.0, 1],
             ["mlp", "backward", 0.005, 5.0, 1],
             [UNATTRIBUTED, DASH, 0.005, 5.0, 1],
             ["optimizer", DASH, 0.005, 5.0, 1]],
    # the second flash_fwd is clipped at the window's end: 10 + 5 ms
    "kernels": [["flash_bwd_dq", "backward", 0.02, 1, 0.02],
                ["flash_fwd", "recompute", 0.015, 2, 0.0075]],
    "collectives": [["mlp", 0.005]],
    # 60-70 lies mostly under train:report; 80-82 is too short to name
    # across the two clocks; 87-95 under host:wait_step
    "idle_gaps": [["train:report", 0.01], ["host:wait_step", 0.008],
                  ["short gaps", 0.002]]}


def _assert_same(got, want):
    if isinstance(want, list):
        assert len(got) == len(want), (got, want)
        for g, w in zip(got, want):
            _assert_same(g, w)
    elif isinstance(want, float):
        assert got == pytest.approx(want), (got, want)
    else:
        assert got == want


@pytest.mark.parametrize("part", sorted(EXPECTED))
def test_hand_made_trace_gives_exact_table(part):
    table = profiling.device_regions(HAND_MADE, HLO)["median"]
    _assert_same(table[part], EXPECTED[part])


def test_instruction_without_metadata_inherits_a_region_not_a_kernel():
    """What the compiler left without metadata takes the op_name of the
    computation it calls, else of its first operand that has one: its time
    goes to that region, but it is not a call of the kernel it copies."""
    named, inherits = profiling._instruction_op_names(HLO)
    assert inherits == {"copy.8": named["flash_fwd.2"],
                        "fusion.9": named["dot.1"]}
    assert "copy.6" not in named
    trace = dict(HAND_MADE, devices={0: [
        _event("flash_fwd.2", "custom-call", 10, 20),
        _event("copy.8", "copy", 20, 30), _event("fusion.9", "fusion", 30, 35)]})
    table = profiling.device_regions(trace, HLO)["median"]
    _assert_same(table["rows"], [["attn_core", "recompute", 0.02, 20.0, 2],
                                 ["head", "forward", 0.005, 5.0, 1]])
    _assert_same(table["kernels"],
                 [["flash_fwd", "recompute", 0.01, 1, 0.01]])


def test_median_over_chips_and_per_chip():
    """A second chip that idles twice as long in the collective: the
    median of two chips is their mean, and each chip keeps its table."""
    second = [(n, a, b + 5 * MS) if "all-reduce" in n else (n, a, b)
              for n, a, b in HAND_MADE["devices"][0]]
    out = profiling.device_regions(
        dict(HAND_MADE, devices={0: HAND_MADE["devices"][0], 1: second}), HLO)
    assert sorted(out["per_chip"]) == ["0", "1"]
    assert out["per_chip"]["1"]["collectives"] == [
        ["mlp", pytest.approx(0.01)]]
    assert out["median"]["collectives"] == [["mlp", pytest.approx(0.0075)]]
    assert "1.3 ms" in out["clock_skew_note"]


def test_v5e_fixture_without_scopes_is_all_unattributed():
    """benchmark/fixtures/trace.xplane.pb was recorded from a program with
    no scopes: all of its busy time (fixtures/expected.json, worked out by
    hand) lands under `unattributed`, and its long gaps under the host
    spans the benchmark's own reduction names."""
    with open(os.path.join(FIXTURES, "expected.json")) as f:
        expected = json.load(f)
    out = profiling.device_regions(
        os.path.join(FIXTURES, "trace.xplane.pb"),
        "HloModule jit_chain\n\nENTRY %main () -> f32[] {\n}\n")
    table = out["median"]
    assert list(out["per_chip"]) == ["0"]
    assert table["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert [r[:2] for r in table["rows"]] == [[UNATTRIBUTED, DASH]]
    assert table["rows"][0][2] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert table["rows"][0][4] == 18
    assert table["kernels"] == [] and table["collectives"] == []
    assert table["idle_gaps"][0][0] == "host:batch_wait"


def test_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError, match="no TPU plane"):
        profiling.device_regions({"devices": {}, "host": []}, HLO)


# (d) one clock with the flight recorder's spans

WALL_S = 1_790_000_000.0        # the trace's zero on time.time()
DATED = dict(HAND_MADE, host=[HAND_MADE["host"][0]],
             wall_offset_ns=WALL_S * 1e9)


def _wall_span(name, start_ms, end_ms):
    return {"name": name, "start": WALL_S + start_ms / 1e3,
            "end": WALL_S + end_ms / 1e3}


def test_a_dated_trace_reports_its_offset_and_spans_name_its_gaps():
    """The hand-made trace without its host spans, dated: flight-recorder
    spans on the wall clock are shifted onto the trace's base and name the
    gaps the trace's own spans named."""
    out = profiling.device_regions(DATED, HLO, spans=[
        _wall_span("train:report", 58, 72), _wall_span("train:loop", 72, 100)])
    assert out["wall_clock_offset_s"] == pytest.approx(WALL_S)
    _assert_same(out["median"]["idle_gaps"], [
        ["train:report", 0.01], ["train:loop", 0.008], ["short gaps", 0.002]])
    assert profiling.device_regions(HAND_MADE, HLO)[
        "wall_clock_offset_s"] is None
    with pytest.raises(ValueError, match="wall clock"):
        profiling.device_regions(HAND_MADE, HLO, spans=[
            _wall_span("train:loop", 72, 100)])


def test_v5e_fixture_gap_is_named_by_a_span_shifted_onto_its_base():
    """The recorded trace predates the note: given an offset, a wall-clock
    span over its longest gap names it in place of host:batch_wait."""
    trace = profiling._load_xplane(os.path.join(FIXTURES, "trace.xplane.pb"))
    assert trace["wall_offset_ns"] is None
    text = "HloModule jit_chain\n\nENTRY %main () -> f32[] {\n}\n"
    ops = sorted(trace["devices"][0], key=lambda e: e[1])
    gap = max(zip(ops, ops[1:]), key=lambda p: p[1][1] - p[0][2])
    trace["wall_offset_ns"] = WALL_S * 1e9
    span = {"name": "train:loop", "start": WALL_S + gap[0][2] / 1e9 - 1.0,
            "end": WALL_S + gap[1][1] / 1e9 + 1.0}
    out = profiling.device_regions(trace, text, spans=[span])
    assert out["median"]["idle_gaps"][0][0] == "train:loop"
    assert out["wall_clock_offset_s"] == pytest.approx(WALL_S)


def test_device_slices_lay_the_ops_by_region_on_the_wall_clock():
    """A lane a chip; back-to-back ops of one region and phase are one
    slice; a while is drawn by the ops nested in it; a gap ends a run."""
    slices = profiling.device_slices(DATED, HLO)
    assert {e["pid"] for e in slices} == {"chip:0"}
    assert all(e["cat"] == "device" and e["ph"] == "X" for e in slices)
    got = [(e["name"], e["args"]["phase"], round(e["ts"] / 1e3 - WALL_S * 1e3),
            round(e["dur"] / 1e3), e["args"]["ops"]) for e in slices]
    assert got == [
        ("mlp", "forward", 0, 10, 1), ("attn_core", "recompute", 10, 10, 1),
        ("attn_core", "backward", 20, 20, 1), ("head", "forward", 45, 10, 1),
        ("mlp", "backward", 70, 5, 1), (UNATTRIBUTED, DASH, 75, 5, 1),
        ("optimizer", DASH, 82, 5, 1), ("attn_core", "recompute", 95, 25, 1)]
    merged = profiling.device_slices(dict(DATED, devices={0: [
        _event("fusion.1", "fusion", 0, 10),
        _event("fusion.1", "fusion", 10, 15),
        _event("fusion.1", "fusion", 16, 20)]}), HLO)
    assert [(round(e["dur"] / 1e3), e["args"]["ops"]) for e in merged] == [
        (15, 2), (4, 1)]
    with pytest.raises(ValueError, match="wall clock"):
        profiling.device_slices(HAND_MADE, HLO)


def test_device_trace_dates_its_stretch(jax_cpu, tmp_path):
    """The stretch's annotation carries time.time_ns() at its start: the
    loaded trace's offset turns the stretch's own start into that wall
    time; trace_files finds the trace and the one HLO text beside it."""
    import time
    log_dir = str(tmp_path / "trace")
    before = time.time_ns()
    with profiling.device_trace(log_dir):
        jax_cpu.numpy.ones(4).block_until_ready()
    after = time.time_ns()
    with pytest.raises(ValueError, match="0 \\*.hlo.txt"):
        profiling.trace_files(log_dir)
    with open(os.path.join(log_dir, "step" + profiling.HLO_SUFFIX), "w") as f:
        f.write(HLO)
    path, text = profiling.trace_files(log_dir)
    assert text == HLO and path.endswith(".xplane.pb")
    trace = profiling._load_xplane(path)
    (stretch,) = [h for h in trace["host"] if h[0] == profiling.STRETCH_SPAN]
    assert before <= stretch[1] + trace["wall_offset_ns"] <= after
