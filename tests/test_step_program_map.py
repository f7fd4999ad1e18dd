"""The compiled step's own account of itself (ISSUE 25): ``phase_of`` on
hand-written scope paths, the step-program map of a toy GPT-2 with remat
under ZeRO-2 (gas 1 and 2) and ZeRO-3 on the virtual devices, its
laziness, and the host spans as ``ds/`` events of a profiler session."""
import glob
import os

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import reset_tracer, tracing
from deepspeed_tpu.telemetry.costmodel import ring_wire_factor
from deepspeed_tpu.telemetry.tracing import (
    NULL_TRACER, PHASES, SpanTracer, count_in_step, get_program_map,
    grouped_gemm_rows, parse_program_text, phase_of, register_program,
    reset_programs, step_account)
from tests.util import base_config, random_batch, tiny_gpt2


@pytest.fixture(autouse=True)
def _isolation():
    reset_tracer()
    reset_programs()
    yield
    reset_tracer()
    reset_programs()


# ---------------------------------------------------------------- phase_of
J = "jit(train_step)/"
PHASE_CASES = {
    "forward_bare_scan": (
        J + "while/body/closed_call/ds.fwd_bwd/jvp()/while/body/closed_call/"
            "ds.block/attn/dot_general", "forward"),
    "forward_wrapped_scope": (
        J + "ds.fwd_bwd/jvp(ds.head_loss)/dot_general", "forward"),
    "forward_no_loop": (J + "ds.fwd_bwd/jvp(ds.embed)/gather", "forward"),
    "forward_kernel_in_shard_map": (
        J + "ds.fwd_bwd/jvp()/while/body/ds.block/attn/shard_map/"
            "custom_vjp_call/ds_flash_fwd/pallas_call", "forward"),
    "recompute_in_loop": (
        J + "ds.fwd_bwd/transpose(jvp())/while/body/closed_call/checkpoint/"
            "rematted_computation/ds.block/mlp/dot_general", "recompute"),
    "recompute_kernel": (
        J + "ds.fwd_bwd/transpose(jvp(ds.block))/ds.fwd_bwd/jvp(ds.block)/"
            "checkpoint/rematted_computation/attn/ds_flash_fwd/pallas_call",
        "recompute"),
    "backward_in_loop": (
        J + "while/body/closed_call/ds.fwd_bwd/transpose(jvp())/while/body/"
            "closed_call/checkpoint/ds.block/attn/dot_general", "backward"),
    "backward_kernel_custom_vjp": (
        J + "ds.fwd_bwd/transpose(jvp(ds.block))/ds.fwd_bwd/jvp(ds.block)/"
            "checkpoint/attn/shard_map/ds_flash_bwd_dkv/pallas_call",
        "backward"),
    "backward_embedding": (
        J + "ds.fwd_bwd/transpose(jvp(ds.embed))/scatter-add", "backward"),
    "optimizer": (J + "ds.optimizer/reduce_sum", "optimizer"),
    "optimizer_in_conditional": (
        J + "ds.optimizer/cond/branch_1_fun/mul", "optimizer"),
    "accumulate_in_scan": (
        J + "while/body/closed_call/ds.accumulate/add", "accumulate"),
    "accumulate_bare": (J + "ds.accumulate/convert_element_type",
                        "accumulate"),
    "other_loop_counter": (J + "while/body/add", "other"),
    "other_argument": ("state['params']['wpe']", "other"),
    "other_none": (None, "other"),
    "other_empty": ("", "other"),
}


@pytest.mark.parametrize("case", sorted(PHASE_CASES))
def test_phase_of(case):
    op_name, want = PHASE_CASES[case]
    assert phase_of(op_name) == want
    assert want in PHASES


def test_fixed_names():
    """What readers key on by name: the phases, the program's name and
    the host spans of a start.  A scope or a kernel is read off an
    instruction's ``op_name`` and is listed nowhere."""
    assert PHASES == ("forward", "recompute", "backward", "optimizer",
                      "accumulate", "other")
    assert tracing.TRAIN_STEP_PROGRAM == "train/step"
    assert tracing.SETUP_SPANS == (
        "engine/init", "engine/init/shardings", "engine/init/params",
        "engine/init/optimizer", "train/step", "train/fused_step",
        "costmodel/analyze", "memory/compiled", "program_map/text",
        "compile/aot")
    assert tracing.OBSERVER_SPANS == (
        "costmodel/analyze", "memory/compiled", "program_map/text",
        "compile/aot")
    assert not hasattr(tracing, "STEP_SCOPES")
    assert not hasattr(tracing, "KERNEL_NAMES")


# ------------------------------------------------------- the text's parser
HLO = """HloModule jit_train_step, entry_computation_layout={()->f32[]}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b)
}

%fused_computation.3 (p0: bf16[2,64]) -> bf16[8,64] {
  %p0 = bf16[2,64]{1,0} parameter(0)
  %all-gather.9 = bf16[8,64]{1,0} all-gather(%p0), channel_id=3, replica_groups=[1,4]<=[4], dimensions={0}, metadata={op_name="jit(train_step)/ds.fwd_bwd/jvp()/dynamic_slice"}
  ROOT %custom-call.7 = bf16[8,64]{1,0} custom-call(%all-gather.9), custom_call_target="X"
}

%all-reduce-scatter.4 (p1: f32[64]) -> f32[16] {
  %p1 = f32[64]{0} parameter(0)
  %all-reduce.5 = f32[64]{0} all-reduce(%p1), replica_groups={{0,1,2,3}}, to_apply=%region_0.1
  ROOT %dynamic-slice.1 = f32[16]{0} dynamic-slice(%all-reduce.5), dynamic_slice_sizes={16}
}

ENTRY %main.1 (x: f32[1,32], y: f32[128]) -> f32[] {
  %x = f32[1,32]{1,0} parameter(0)
  %y = f32[128]{0} parameter(1)
  %all-gather.1 = f32[8,32]{1,0:T(8,128)S(1)} all-gather(%x), channel_id=1, replica_groups=[1,8]<=[8], dimensions={0}, use_global_device_ids=true
  %all-reduce.2 = (f32[128]{0}, bf16[4,4]{1,0}) all-reduce(%y, %z), replica_groups={{0,1,2,3}}, to_apply=%region_0.1, metadata={op_name="jit(train_step)/ds.optimizer/reduce_sum"}
  %async-collective-start.1 = (bf16[2,64]{1,0}, bf16[8,64]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) fusion(%w), kind=kCustom, calls=%fused_computation.3
  %async-collective-done.1 = bf16[8,64]{1,0} fusion(%g0, %g1), kind=kCustom, calls=%fused_computation.3, metadata={op_name="jit(train_step)/ds.fwd_bwd/jvp()/dynamic_slice"}
  %fusion.7 = f32[16]{0} fusion(%y), kind=kCustom, calls=%all-reduce-scatter.4
  %ds_flash_fwd.2 = (bf16[2,4,512,64]{3,2,1,0:T(8,128)(2,1)S(1)}, f32[2,4,512,1]{3,2,1,0}) custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/ds.fwd_bwd/jvp()/ds.block/attn/ds_flash_fwd/pallas_call" stack_frame_id=5}
  ROOT %fusion.554 = f32[] fusion(%all-gather.1), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(train_step)/ds.fwd_bwd/transpose(jvp())/checkpoint/rematted_computation/ds.block/mlp/mul"}
}
"""


def test_parse_hand_written_text():
    table = parse_program_text(HLO)
    # bodies of fused computations are not rows; everything else is
    assert "all-gather.9" not in table and "all-reduce.5" not in table
    assert "add.0" in table and "x" in table
    # one all-gather and one all-reduce by hand: payload x ring factor
    gather = table["all-gather.1"]
    assert gather["collective"] == "all-gather"
    assert gather["wire_bytes"] == 8 * 32 * 4 * 7 // 8 == 896
    assert gather["wire_bytes"] == round(
        1024 * ring_wire_factor("all_gather", 8))
    reduce = table["all-reduce.2"]
    assert reduce["collective"] == "all-reduce"
    assert reduce["phase"] == "optimizer"
    assert reduce["wire_bytes"] == (128 * 4 + 4 * 4 * 2) * 2 * 3 // 4 == 816
    # an async pair counts once, at the done; the fusion says what it wraps
    start, done = (table["async-collective-start.1"],
                   table["async-collective-done.1"])
    assert start["collective"] == done["collective"] == "all-gather"
    assert start["wire_bytes"] is None
    assert done["wire_bytes"] == 8 * 64 * 2 * 3 // 4
    assert done["phase"] == "forward" and start["phase"] == "other"
    scatter = table["fusion.7"]
    assert scatter["collective"] == "reduce-scatter"
    assert scatter["wire_bytes"] == 16 * 4 * 4 * 3 // 4
    kernel = table["ds_flash_fwd.2"]
    assert kernel["kernel"] == "ds_flash_fwd" and kernel["phase"] == "forward"
    assert table["fusion.554"] == {
        "scope": "jit(train_step)/ds.fwd_bwd/transpose(jvp())/checkpoint/"
                 "rematted_computation/ds.block/mlp/mul",
        "phase": "recompute", "kernel": None, "collective": None,
        "wire_bytes": None}


# ------------------------------------------- the step's account of itself
ROWS = dict(grouped_routed_rows=32768, grouped_padded_rows=40960)
CALL = {"kernel": "ds_ggemm_fwd", "k": 2048, "n": 1024,
        "blocks": (2048, 1024), "regime": "resident",
        "weight_bytes_per_call": 64 * 2048 * 1024 * 2,
        "operand_bytes_per_call": 40960 * (2048 + 1024) * 2}
ACCOUNT_CASES = {
    # what is counted where -> what grouped_gemm_rows("train/step") says
    "inside": ("train/step", ROWS, {"routed_rows_per_call": 32768,
                                    "padded_rows_per_call": 40960}),
    # the kernels' own rows (ops/pallas/grouped_gemm.py), one per kernel
    # and weight shape however often the call is traced
    "with_kernel_calls": ("train/step", dict(ROWS, grouped_calls={
        "ds_ggemm_fwd:2048x1024": CALL}), {
            "routed_rows_per_call": 32768, "padded_rows_per_call": 40960,
            "calls": [CALL]}),
    "another_program": ("eval/step", ROWS, None),
    "no_grouped_dispatch": ("train/step", dict(other=1), None),
    "outside_any_account": (None, ROWS, None),
}


@pytest.mark.parametrize("case", sorted(ACCOUNT_CASES))
def test_step_account(case):
    """Counts are kept under the program whose trace is running, a count
    outside any trace is dropped, and a new trace starts the account
    anew (it describes the step as last traced)."""
    program, counters, want = ACCOUNT_CASES[case]
    if program is None:
        count_in_step(**counters)
    else:
        with step_account(program):
            count_in_step(**counters)
            count_in_step(**counters)       # traced twice, counted once
    assert grouped_gemm_rows("train/step") == want
    with step_account("train/step"):
        pass
    assert grouped_gemm_rows("train/step") is None


# ------------------------------------------------------ a compiled toy step
@pytest.fixture
def fresh_compiles():
    """The persistent compile cache's key leaves debug info out, so an
    executable that an older tree cached comes back with that tree's
    scopes (jax: "may have stale metadata").  A test that reads scopes
    compiles for itself."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _engine(stage, gas):
    zero = {"stage": stage}
    if stage == 3:
        zero["param_persistence_threshold"] = 0
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(remat=True),
        config=base_config(gradient_accumulation_steps=gas,
                           zero_optimization=zero))
    one = random_batch(batch_size=engine.topology.dp_world_size, seq_len=16)
    return engine, {k: np.stack([v] * gas) for k, v in one.items()}


@pytest.mark.parametrize("stage,gas", [(2, 1), (2, 2), (3, 1)],
                         ids=["zero2_gas1", "zero2_gas2", "zero3_gas1"])
def test_map_of_a_compiled_step(stage, gas, devices8, fresh_compiles):
    engine, batch = _engine(stage, gas)
    engine.train_batch(batch=batch)
    table = get_program_map("train/step")
    text = engine.compile_train_step(batch).as_text()
    assert table == parse_program_text(text)
    by_phase = {p: 0 for p in PHASES}
    collectives = 0
    for name, row in table.items():
        assert set(row) == {"scope", "phase", "kernel", "collective",
                            "wire_bytes"}
        assert row["phase"] in PHASES, (name, row)
        assert row["phase"] == phase_of(row["scope"])
        by_phase[row["phase"]] += 1
        if row["collective"]:
            collectives += 1
            assert row["collective"] in ("all-gather", "all-reduce",
                                         "reduce-scatter", "all-to-all",
                                         "collective-permute")
            if not name.partition(".")[0].endswith("-start"):
                assert isinstance(row["wire_bytes"], int) \
                    and row["wire_bytes"] > 0, (name, row)
    for phase in ("forward", "recompute", "backward", "optimizer"):
        assert by_phase[phase] > 0, by_phase
    # every dot, fusion, custom call and collective of the text has a row
    for line in text.splitlines():
        head, _, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        if head.startswith("%") and any(
                f" {op}(" in " " + rest for op in (
                    "dot", "all-gather", "all-reduce", "all-to-all",
                    "reduce-scatter")):
            assert head[1:] in table, head
    assert collectives > 0       # data=8: the partitioner's, in no jaxpr
    # the matmuls of a block are where the scopes say they are
    scopes = [r["scope"] for r in table.values() if r["scope"]]
    for needle in ("ds.block/attn/", "ds.block/mlp/", "ds.head_loss",
                   "ds.embed", "ds.optimizer", "ds.fwd_bwd"):
        assert any(needle in s for s in scopes), needle
    if gas > 1:
        assert by_phase["accumulate"] > 0


# ------------------------------ every step program carries its scopes
#: which of ds.fwd_bwd / ds.accumulate / ds.optimizer a program must hold:
#: fwd_bwd where it differentiates, accumulate where it sums gradients,
#: optimizer where it updates — and no other
PROGRAM_SCOPES = {
    "train_step": {"ds.fwd_bwd", "ds.accumulate", "ds.optimizer"},
    "loss": set(),
    "grad": {"ds.fwd_bwd", "ds.accumulate"},
    "grad_step": {"ds.fwd_bwd", "ds.accumulate"},
    "grad_micro": {"ds.fwd_bwd"},
    "grad_acc": {"ds.accumulate"},
    "apply": {"ds.optimizer"},
    "zero_grads": set(),
}
#: the pipelined builds of the fused step (all-live: one pass, nothing to
#: accumulate; 1f1b: its own interleaved forward/backward)
PIPELINE_SCOPES = {
    "all_live": ({}, {"ds.fwd_bwd", "ds.optimizer"}),
    "chunked": ({"num_pipe_buffers": 2},
                {"ds.fwd_bwd", "ds.accumulate", "ds.optimizer"}),
    "1f1b": ({"schedule": "1f1b"}, {"ds.fwd_bwd", "ds.optimizer"}),
}


def _phase_scopes(lowered):
    """The three phase scopes among the op names of a lowered program."""
    import re
    return set(re.findall(r"ds\.(?:fwd_bwd|accumulate|optimizer)\b",
                          lowered.as_text(debug_info=True)))


def test_scope_table_covers_every_program():
    from deepspeed_tpu.runtime import step_programs
    assert set(PROGRAM_SCOPES) == set(step_programs.PROGRAMS)


@pytest.mark.parametrize("name", sorted(PROGRAM_SCOPES))
def test_step_program_carries_its_scopes(name, devices8):
    engine, batch = _engine(2, 2)
    stacked = engine._shard_batch(batch, stacked=True)
    micro = engine._shard_batch({k: v[0] for k, v in batch.items()},
                                stacked=False)
    grads = engine._get_compiled("zero_grads")(engine.state["params"])
    state, rng = engine.state, engine._rng
    args = {
        "train_step": (state, stacked, rng), "loss": (state, micro, rng),
        "grad": (state, micro, rng, grads),
        "grad_step": (state, stacked, rng),
        "grad_micro": (state, micro, rng), "grad_acc": (grads, grads),
        "apply": (state, grads), "zero_grads": (state["params"],),
    }[name]
    fn = engine._get_compiled(name)
    assert _phase_scopes(fn.lower(*args)) == PROGRAM_SCOPES[name]
    if name == "train_step":
        # the benchmark finds the fused step by its module's name
        assert engine.compile_train_step(batch).as_text().startswith(
            "HloModule jit_train_step,")
        # a chaos variant is the same build, handed its group
        nf = engine._get_compiled("train_step@nf1")
        poisoned = nf.lower(*args)
        assert _phase_scopes(poisoned) == PROGRAM_SCOPES[name]
        assert poisoned.as_text() != fn.lower(*args).as_text()


@pytest.mark.parametrize("schedule", sorted(PIPELINE_SCOPES))
def test_pipeline_step_carries_its_scopes(schedule, devices8):
    from deepspeed_tpu.runtime.pipe.pipeline import pipeline_model
    pipe_cfg, want = PIPELINE_SCOPES[schedule]
    engine, *_ = deepspeed_tpu.initialize(
        model=pipeline_model(tiny_gpt2(), num_stages=2),
        config=base_config(
            gradient_accumulation_steps=4, pipeline=pipe_cfg,
            mesh={"pipe_parallel_size": 2, "data_parallel_size": 4}))
    batch = engine._shard_batch(
        {"input_ids": np.zeros((4, 4, 16), np.int32)}, stacked=True)
    fn = engine._get_compiled("train_step")
    assert _phase_scopes(fn.lower(engine.state, batch, engine._rng)) == want


def test_the_map_is_lazy(monkeypatch, capsys):
    engine, batch = _engine(2, 1)
    asked = {"compile": 0, "as_text": 0}
    real_compile = type(engine)._compile_train_step

    def counting_compile(self, signature):
        asked["compile"] += 1
        return real_compile(self, signature)

    real_as_text = jax.stages.Compiled.as_text

    def counting_as_text(self, *a, **k):
        asked["as_text"] += 1
        return real_as_text(self, *a, **k)

    monkeypatch.setattr(type(engine), "_compile_train_step",
                        counting_compile)
    monkeypatch.setattr(jax.stages.Compiled, "as_text", counting_as_text)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **kw: compiles.append(event)
        if event.endswith("backend_compile_duration") else None)
    capsys.readouterr()
    for _ in range(2):          # the step's own compilations (jit caches)
        engine.train_batch(batch=batch)
    warm = len(compiles)
    for _ in range(3):
        engine.train_batch(batch=batch)
    # nobody asked: from the first dispatch on no lowering and no text;
    # once warm no compilation at all; nothing on stdout
    assert asked == {"compile": 0, "as_text": 0}
    assert len(compiles) == warm
    assert capsys.readouterr().out == ""
    first = get_program_map("train/step")
    assert first and asked == {"compile": 1, "as_text": 1}
    assert get_program_map("train/step") is first
    assert asked == {"compile": 1, "as_text": 1}
    assert get_program_map("no/such/program") is None


def test_registry_holds_thunks_not_text():
    calls = []

    def thunk():
        calls.append(1)
        return HLO

    register_program("toy", thunk)
    assert calls == []
    assert get_program_map("toy")["fusion.554"]["phase"] == "recompute"
    assert get_program_map("toy") is get_program_map("toy")
    assert calls == [1]
    register_program("toy", lambda: None)     # the engine is gone
    assert get_program_map("toy") is None


# ------------------------------------------- host spans on the profiler's clock
def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names += [e.name for e in line.events]
    return names


def _profiled(trace_dir, fn):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


def test_train_spans_land_in_a_profiler_session(tmp_path, capsys):
    engine, batch = _engine(2, 1)
    assert engine.tracer is NULL_TRACER
    jax.block_until_ready(engine.train_batch(batch=batch))      # compiled
    capsys.readouterr()

    def two_steps():
        for _ in range(2):
            loss = engine.train_batch(batch=batch)
        jax.block_until_ready(loss)

    names = _profiled(str(tmp_path / "trace"), two_steps)
    assert names.count("ds/train/step") == 2
    assert names.count("ds/train/fused_step") == 2
    # with no session the null tracer keeps nothing, and the step is silent
    two_steps()
    assert NULL_TRACER.drain() == [] and NULL_TRACER.flush() is None
    assert capsys.readouterr().out == ""


def test_span_tracer_keeps_its_file_and_joins_the_session(tmp_path):
    t = SpanTracer(str(tmp_path / "trace.json"))

    def spans():
        with t.span("train/step", cat="train", corr="train-step-3"):
            t.begin("timer/fwd", cat="timer")
            t.instant("fault/train.step", cat="resilience")
            t.end("timer/fwd")
            with t.span("ckpt/stage", cat="ckpt"):
                pass

    names = _profiled(str(tmp_path / "profile"), spans)
    for name in ("ds/train/step", "ds/timer/fwd", "ds/fault/train.step",
                 "ds/ckpt/stage"):
        assert names.count(name) == 1, (name, names)
    # the file's event model is what it was: B/E pairs in LIFO order, the
    # correlation id inherited by everything nested
    events = t.drain()
    assert [(e["ph"], e["name"]) for e in events] == [
        ("B", "train/step"), ("B", "timer/fwd"), ("i", "fault/train.step"),
        ("E", "timer/fwd"), ("B", "ckpt/stage"), ("E", "ckpt/stage"),
        ("E", "train/step")]
    assert all(e["args"]["corr"] == "train-step-3" for e in events)
    assert t.current_corr() is None
