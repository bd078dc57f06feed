"""The program's tracer (profiling/trace.py): host spans on the profiler's
clock, named scopes on the compiled train step, compile events by program and
span, the scheduler's due and first-scheduled times. CPU only: counts, names
and containment, never a rate."""

import glob
import os
import re

import numpy as np
import pytest

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.models import Transformer
from shuffle_exchange_tpu.models.transformer import tiny
from shuffle_exchange_tpu.profiling import trace

KNOWN = {name for names in trace.SCOPES.values() for name in names}


@pytest.fixture(autouse=True)
def _no_kept_spans():
    trace.keep_spans(False)
    yield
    trace.keep_spans(False)


def make_engine(**extra):
    config = {"optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "gradient_clipping": 1.0, "train_batch_size": 8,
              "steps_per_print": 10 ** 9, **extra}
    return sxt.initialize(model=Transformer(tiny()), config=config, seed=3)[0]


def ids(batch=8, seq=17, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, 256, (batch, seq)).astype(np.int32)}


def scopes_of(op_name):
    """The program's scopes along an op_name path, outermost first:
    ``jit(f)/transpose(jvp(mlp))/mul`` -> ``["mlp"]``."""
    parts = (re.sub(r"^(?:\w+\()*|\)*$", "", c) for c in op_name.split("/"))
    return [p for p in parts if p in KNOWN]


def host_events(logdir, prefixes=("sxt:", "cb:")):
    import jax

    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith(prefixes)]
    return out


# -- span ---------------------------------------------------------------


def test_span_without_session_or_breakdown_keeps_nothing():
    with trace.span("train/place"):
        with trace.span("train/dispatch"):
            pass
    assert trace.kept_spans() == []
    assert trace._kept is None           # no list exists, nothing to append to
    assert trace._stack() == []


def test_kept_spans_are_bounded_and_cleared():
    trace.keep_spans(True)
    for _ in range(trace._KEEP_MAX + 10):
        with trace.span("a"):
            pass
    rows = trace.kept_spans(clear=True)
    assert len(rows) == trace._KEEP_MAX
    assert all(n == "a" and t1 >= t0 for n, t0, t1 in rows)
    assert trace.kept_spans() == []


def test_spans_nest_on_the_profilers_clock(tmp_path):
    import jax

    engine = make_engine()
    engine.train_batch(ids())            # compile outside the session
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("cb:window"):
        loss = engine.train_batch(ids(seed=1))
        jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    events = host_events(str(tmp_path))
    by = {}
    for n, a, b in events:
        by.setdefault(n, []).append((a, b))
    for name in ("sxt:train", "sxt:train/batch", "sxt:train/fetch",
                 "sxt:train/place", "sxt:train/dispatch", "sxt:train/post"):
        assert len(by.get(name, [])) == 1, (name, sorted(by))
    (w0, w1), = by["cb:window"]
    (s0, s1), = by["sxt:train"]
    (b0, b1), = by["sxt:train/batch"]
    assert w0 <= s0 <= b0 and b1 <= s1 <= w1     # one clock, nested
    inner = [by["sxt:train/" + n][0]
             for n in ("fetch", "place", "dispatch", "post")]
    assert all(b0 <= a and b <= b1 for a, b in inner)
    assert all(inner[i][1] <= inner[i + 1][0] for i in range(3))   # in order


# -- scopes on the compiled step ----------------------------------------


@pytest.fixture(scope="module")
def step_ops():
    from shuffle_exchange_tpu.parallel.mesh import reset_topology

    reset_topology()
    engine = make_engine()
    engine.compile(ids())
    return trace.registered_ops("train_step")


@pytest.mark.parametrize("name", ["attn_core", "mlp", "loss"])
def test_layer_scope_holds_forward_and_backward(step_ops, name):
    paths = [op.scope for op in step_ops.values()
             if scopes_of(op.scope)[-1:] == [name]]
    assert any("transpose(" in p for p in paths), f"no backward under {name}"
    assert any("transpose(" not in p for p in paths), f"no forward under {name}"


@pytest.mark.parametrize("name", ["optimizer", "grad_clip", "embed",
                                  "attn_norm", "attn_qkv", "attn_out",
                                  "mlp_norm", "final_norm", "layers"])
def test_scope_reaches_the_compiled_step(step_ops, name):
    assert any(name in scopes_of(op.scope) for op in step_ops.values())


def test_most_of_the_step_is_under_a_scope(step_ops):
    """Of the instructions that came from the program (they carry an
    op_name; what the compiler adds carries none) under 20% sit under no
    scope of the program."""
    plumbing = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
    named = [op for op in step_ops.values()
             if op.scope and op.opcode not in plumbing]
    bare = [op for op in named if not scopes_of(op.scope)]
    assert len(named) > 100
    assert len(bare) < 0.2 * len(named), sorted(
        {(o.opcode, o.scope) for o in bare})[:20]


HLO = """HloModule jit_train_step, entry_computation_layout={(f32[8]{0})->f32[2]{0}}

%add.1 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.2 = f32[] add(f32[] %x, f32[] %y)
}

%fused_computation.3 (param_0.1: f32[8]) -> f32[2] {
  %param_0.1 = f32[8]{0:T(128)} parameter(0)
  %multiply.4 = f32[8]{0:T(128)} multiply(f32[8]{0:T(128)} %param_0.1, f32[8]{0:T(128)} %param_0.1), metadata={op_name="jit(train_step)/optimizer/zero3_reduce_scatter/div"}
  ROOT %reduce-scatter.5 = f32[2]{0:T(128)} reduce-scatter(f32[8]{0:T(128)} %multiply.4), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add.1
}

%fused_computation.6 (param_0.2: f32[2]) -> f32[2] {
  %param_0.2 = f32[2]{0:T(128)} parameter(0)
  ROOT %negate.7 = f32[2]{0:T(128)} negate(f32[2]{0:T(128)} %param_0.2)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[2] {
  %Arg_0.1 = f32[8]{0:T(128)} parameter(0), metadata={op_name="state.master"}
  %splash_mqa_fwd.1 = f32[8]{0:T(128)} custom-call(f32[8]{0:T(128)} %Arg_0.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\"block_q\": 1024}"
}}, metadata={op_name="jit(train_step)/jvp(layers)/while/body/attn_core/splash_mqa_fwd/pallas_call"}, backend_config={"x":1}
  %fusion.8 = f32[2]{0:T(128)} fusion(f32[8]{0:T(128)} %Arg_0.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/closed_call/mlp/dot_general" source_file="x.py" source_line=3}
  ROOT %fusion.10 = f32[2]{0:T(128)} fusion(f32[2]{0:T(128)} %fusion.8), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""


def test_program_ops_sees_a_collective_inside_a_fusion():
    ops = trace.program_ops(HLO)
    assert ops["fusion.8"] == trace.Op(
        "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/mlp/dot_general",
        "fusion", True)
    assert ops["fusion.10"].opcode == "fusion"
    assert ops["fusion.10"].contains_collective is False
    assert ops["reduce-scatter.5"].opcode == "reduce-scatter"
    assert ops["reduce-scatter.5"].contains_collective is True
    assert ops["multiply.4"].contains_collective is False
    assert scopes_of(ops["fusion.8"].scope) == ["layers", "mlp"]
    assert ops["Arg_0.1"].opcode == "parameter"
    # a kernel's attributes break the line: its metadata is found all the same
    assert ops["splash_mqa_fwd.1"].opcode == "custom-call"
    assert scopes_of(ops["splash_mqa_fwd.1"].scope) == ["layers", "attn_core"]
    assert len(ops) == 12


# -- compile events -----------------------------------------------------


def test_second_batch_shape_is_one_compile_event_with_program_and_span():
    import time

    engine = make_engine()
    engine.train_batch(ids(seq=17))
    mark = time.perf_counter()
    engine.train_batch(ids(seq=17, seed=1))
    assert trace.compile_events(since=mark) == []
    mark = time.perf_counter()
    engine.train_batch(ids(seq=9))
    steps = [e for e in trace.compile_events(since=mark)
             if e["program"] == "train_step"]
    assert len(steps) == 1, trace.compile_events(since=mark)
    event, = steps
    assert event["span"] == "train/dispatch"
    assert event["fun_name"] == "jit(train_step)"
    assert event["seconds"] > 0 and isinstance(event["cache_hit"], bool)
    assert event["at"] >= mark


# -- wall_clock_breakdown -----------------------------------------------


def test_wall_clock_breakdown_logs_from_the_spans(caplog):
    import logging

    engine = make_engine(wall_clock_breakdown=True, steps_per_print=3)
    lg = logging.getLogger("shuffle_exchange_tpu")
    lg.addHandler(caplog.handler)
    old = lg.level
    lg.setLevel(logging.INFO)
    try:
        for i in range(4):
            engine.train_batch(ids(seed=i))
    finally:
        lg.removeHandler(caplog.handler)
        lg.setLevel(old)
    lines = [r.getMessage() for r in caplog.records
             if "time (ms) |" in r.getMessage()]
    assert len(lines) == 1
    for part in ("train/batch:", "train/fetch:", "train/place:",
                 "train/dispatch:", "train/post:", "train/wait:", "samples/s:"):
        assert part in lines[0], lines[0]
    # the fourth step's spans are kept for the next line
    names = [n for n, _, _ in trace.kept_spans()]
    assert names.count("train/batch") == 2 and names.count("train/wait") == 2


def test_breakdown_line_counts_samples_over_step_spans():
    rows = [("train/batch", 0.0, 0.5), ("train/batch", 0.5, 1.0),
            ("train/place", 0.0, 0.002)]
    line = trace.breakdown_line(rows, batch_size=8, step_span="train/batch")
    assert "train/batch: 500.00" in line and "train/place: 2.00" in line
    assert line.endswith("samples/s: 16.00")


def test_staged_api_has_its_spans():
    trace.keep_spans(True)
    engine = make_engine(train_batch_size=8)
    loss = engine.forward(ids())
    engine.backward(loss)
    engine.step()
    names = [n for n, _, _ in trace.kept_spans()]
    assert names == ["train/forward", "train/backward", "train/step"]


# -- scheduler ----------------------------------------------------------


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 0.25
        return self.t


def make_scheduler(clock):
    import jax

    from shuffle_exchange_tpu.inference.config import InferenceConfig
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2
    from shuffle_exchange_tpu.inference.scheduler import \
        ContinuousBatchingScheduler

    cfg = tiny(vocab=97, d=32, layers=2, heads=2, seq=64)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig.from_dict({
        "dtype": "float32", "max_seq_len": 64, "kv_block_size": 8,
        "num_kv_blocks": 33,
        "serving": {"token_budget": 16, "max_running": 4}})
    engine = InferenceEngineV2(model, params, icfg)
    return ContinuousBatchingScheduler(engine, clock=clock)


def test_scheduler_times_from_the_due_time():
    clock = Clock()
    sched = make_scheduler(clock)
    late = sched.submit([1, 2, 3, 4, 5], max_new_tokens=3, due_at=90.0)
    plain = sched.submit([5, 4, 3], max_new_tokens=2)
    r_late, r_plain = sched.requests[late], sched.requests[plain]
    assert r_late.due_at == 90.0 and r_late.submitted_at > 100.0
    assert r_plain.due_at == r_plain.submitted_at
    assert r_late.first_scheduled_at is None
    while sched.tick():
        pass
    for r in (r_late, r_plain):
        assert r.due_at <= r.first_scheduled_at <= r.first_token_at
    stats = sched.stats()
    ttft = sorted(r.first_token_at - r.due_at for r in (r_late, r_plain))
    wait = sorted(r.first_scheduled_at - r.due_at for r in (r_late, r_plain))
    assert stats["ttft_p50_s"] == pytest.approx(np.percentile(ttft, 50))
    assert stats["ttft_p95_s"] == pytest.approx(np.percentile(ttft, 95))
    assert stats["queue_wait_p50_s"] == pytest.approx(np.percentile(wait, 50))
    assert stats["queue_wait_p95_s"] == pytest.approx(np.percentile(wait, 95))
    # from submission it would read ten seconds less for the late request
    assert max(ttft) > r_late.first_token_at - r_late.submitted_at + 10.0


def test_tick_is_covered_by_its_three_spans(tmp_path):
    import jax

    sched = make_scheduler(Clock())
    sched.submit([1, 2, 3, 4, 5], max_new_tokens=4)
    sched.tick()                                     # compiles the prefill
    sched.tick()                                     # and the decode program
    jax.profiler.start_trace(str(tmp_path))
    assert sched.tick() is True
    jax.profiler.stop_trace()
    by = {}
    for n, a, b in host_events(str(tmp_path)):
        by.setdefault(n, []).append((a, b))
    (t0, t1), = by["sxt:serve"]
    (a0, a1), = by["sxt:serve/admit"]
    (d0, d1), = by["sxt:serve/dispatch"]
    (e0, e1), = by["sxt:serve/emit"]
    assert t0 <= a0 and a1 <= d0 and d1 <= e0 and e1 <= t1
    # the phases abut: what the three leave uncovered is the cost of
    # closing one annotation and opening the next
    covered = (a1 - a0) + (d1 - d0) + (e1 - e0)
    assert covered >= 0.9 * (t1 - t0)
    for inner in ("sxt:serve/pack", "sxt:serve/launch", "sxt:serve/readback"):
        (i0, i1), = by[inner]
        assert d0 <= i0 and i1 <= d1


def test_engine_v2_names_its_program_for_the_compile_listener():
    import time

    mark = time.perf_counter()
    sched = make_scheduler(Clock())
    sched.submit([1, 2, 3, 4, 5], max_new_tokens=3)
    while sched.tick():
        pass
    launched = [e for e in trace.compile_events(since=mark)
                if e["span"] == "serve/launch"]
    programs = {e["program"] for e in launched}
    assert any(p.startswith("extend/") for p in programs), programs
    assert any(p.startswith("decode/") for p in programs), programs
