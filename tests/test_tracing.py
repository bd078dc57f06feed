"""The program's tracer (profiling/trace.py): host spans on the profiler's
clock, named scopes on the compiled train step, compile events by program and
span, the scheduler's due and first-scheduled times. CPU only: counts, names
and containment, never a rate."""

import collections
import glob
import gzip
import json
import os
import re

import numpy as np
import pytest

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.models import Transformer
from shuffle_exchange_tpu.models.transformer import TransformerConfig, tiny
from shuffle_exchange_tpu.profiling import trace

KNOWN = {name for names in trace.SCOPES.values() for name in names}


@pytest.fixture(autouse=True)
def _no_open_step():
    """A test leaves no step open on its thread: the next one's spans would
    land in its record."""
    yield
    assert getattr(trace._open, "step", None) is None


def steps_since(mark, kind="train"):
    return trace.steps(kind, since=mark)


def make_engine(model=None, **extra):
    config = {"optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "gradient_clipping": 1.0, "train_batch_size": 8,
              "steps_per_print": 10 ** 9, **extra}
    return sxt.initialize(model=Transformer(model or tiny()), config=config,
                          seed=3)[0]


def ids(batch=8, seq=17, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, (batch, seq)).astype(np.int32)}


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
    before = {k: len(v) for k, v in trace._steps.items()}
    with trace.span("train/place") as outer:
        with trace.span("train/dispatch"):
            pass
    # outside a step a span is the profiler's alone: no clock is read and
    # no ring grows
    assert outer._step is None and outer._t0 == 0.0
    assert {k: len(v) for k, v in trace._steps.items()} == before
    assert trace._stack() == []


def test_the_step_ring_is_bounded_and_hands_out_copies():
    import time

    mark = time.perf_counter()
    for i in range(trace._STEPS_MAX + 10):
        with trace.step("bounded", i):
            with trace.span("a"):
                pass
    rows = steps_since(mark, "bounded")
    assert len(rows) == len(trace._steps["bounded"]) == trace._STEPS_MAX
    assert [r["n"] for r in rows] == list(range(10, trace._STEPS_MAX + 10))
    assert all(r["t1"] >= r["t0"] and list(r["spans"]) == ["a"] for r in rows)
    rows[0]["spans"]["a"] = -1.0            # a copy: the ring's row stands
    assert steps_since(mark, "bounded")[0]["spans"]["a"] >= 0.0
    del trace._steps["bounded"]


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


# -- phase: start-up, always kept ----------------------------------------


def fresh_jit(seed):
    """A jitted function nothing has compiled before (its constant is new)."""
    import jax

    salt = float(np.random.default_rng(seed).random()) + os.getpid()

    def brand_new(x):
        return x * salt + 1.0

    return jax.jit(brand_new)


def test_phase_is_kept_with_no_session_and_goes_to_no_step_record():
    import time

    mark = time.perf_counter()
    with trace.phase("init/params") as ph:
        assert trace._stack() == [("init/params", None)]
    row, = trace.phases(since=mark)
    assert row == {"name": "init/params", "program": None, "t0": ph._began,
                   "t1": row["t1"], "parent": None}
    assert mark <= row["t0"] <= row["t1"] <= time.perf_counter()
    # a step's record is another thing: a phase never goes there, a span does
    with trace.step("phased", 0):
        with trace.phase("init/params"), trace.span("train/place"):
            pass
    record, = steps_since(mark, "phased")
    assert list(record["spans"]) == ["train/place"]
    assert len(trace.phases(since=mark)) == 2
    assert trace._stack() == [] and trace._open_phases() == []


def test_a_plain_span_leaves_no_phase_row():
    import time

    mark = time.perf_counter()
    with trace.span("train/place"), trace.span("train/dispatch"):
        pass
    assert trace.phases(since=mark) == []


def test_phases_nest_name_their_parent_and_hand_down_their_program():
    import time

    mark = time.perf_counter()
    with trace.phase("init/engine", program="p"):
        with trace.phase("init/shardings"):
            with trace.span("inner") as sp:
                assert sp.program == "p"
        with trace.span("not a phase"):
            with trace.phase("init/params") as ph:
                assert ph.parent == "init/engine"    # spans are not parents
    rows = trace.phases(since=mark)
    assert [(r["name"], r["parent"], r["program"]) for r in rows] == [
        ("init/engine", None, "p"), ("init/shardings", "init/engine", "p"),
        ("init/params", "init/engine", "p")]            # oldest first
    outer, first, second = rows
    assert outer["t0"] <= first["t0"] <= first["t1"] <= second["t0"]
    assert second["t1"] <= outer["t1"]
    assert trace.phases(since=second["t0"]) == [second]


def test_a_backdated_phase_starts_where_it_is_told():
    import time

    t0 = time.perf_counter() - 5.0
    with trace.phase("init/import", t0=t0):
        pass
    row = [r for r in trace.phases(since=t0) if r["name"] == "init/import"][0]
    assert row["t0"] == t0 and row["t1"] - row["t0"] >= 5.0


def test_the_packages_import_is_the_first_phase():
    import subprocess
    import sys

    code = ("import json, time; t = time.perf_counter(); "
            "import shuffle_exchange_tpu; "
            "from shuffle_exchange_tpu.profiling import trace; "
            "print(json.dumps([t, time.perf_counter(), trace.phases()]))")
    out = subprocess.run(
        [sys.executable, "-c", code], check=True, capture_output=True,
        text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    before, after, rows = json.loads(out.stdout.splitlines()[-1])
    row, = rows                                  # importing opens no other
    assert (row["name"], row["parent"]) == ("init/import", None)
    # first line to last line of the package's __init__
    assert before <= row["t0"] < row["t1"] <= after
    assert row["t1"] - row["t0"] >= 0.5 * (after - before)


ENGINE_SECTIONS = ["init/rest", "init/shardings", "init/params",
                   "init/optimizer", "init/rest", "init/programs"]


def test_engine_init_is_covered_by_its_sections():
    import time

    mark = time.perf_counter()
    make_engine()
    rows = trace.phases(since=mark)
    assert [r["name"] for r in rows] == ["init/config", "init/engine"] + ENGINE_SECTIONS
    config, engine = rows[:2]
    assert config["parent"] is None and engine["parent"] is None
    assert config["t1"] <= engine["t0"]
    children = rows[2:]
    assert all(r["parent"] == "init/engine" for r in children)
    assert all(a["t1"] <= b["t0"] for a, b in zip(children, children[1:]))
    covered = sum(r["t1"] - r["t0"] for r in children)
    assert abs(covered - (engine["t1"] - engine["t0"])) < 1e-3
    # placing the masters is where a jitted init compiles
    placed = [e for e in trace.compile_events(since=mark)
              if e["fun_name"] == "jit(init_master)"]
    assert [e["span"] for e in placed] == ["init/params"]


def test_an_init_that_raises_closes_its_phases():
    import time

    from shuffle_exchange_tpu.config import ConfigError

    mark = time.perf_counter()
    with pytest.raises(ConfigError, match="sparse_gradients"):
        make_engine(sparse_gradients=True)
    assert trace._stack() == [] and trace._open_phases() == []
    assert [r["name"] for r in trace.phases(since=mark)] == [
        "init/config", "init/engine", "init/rest"]


def test_compile_leaves_lower_and_compile_and_the_first_step_one_phase():
    import time

    engine = make_engine()
    mark = time.perf_counter()
    assert engine.compile(ids()) is not None
    names = [r["name"] for r in trace.phases(since=mark)]
    assert names == ["train/lower", "train/compile", "train/register"]
    assert all(r["program"] == "train_step" for r in trace.phases(since=mark))
    # tracing and lowering belong to train/lower, the backend to train/compile
    lowered, = [e for e in trace.compile_events(since=mark, every=True)
                if e["span"] == "train/lower" and e["fun_name"] == "jit(train_step)"]
    assert not lowered["compiled"] and lowered["seconds"] == 0.0
    assert lowered["trace_s"] > 0 and lowered["lower_s"] > 0
    built, = [e for e in trace.compile_events(since=mark)
              if e["span"] == "train/compile"]
    assert built["compiled"] and built["seconds"] > 0
    assert built["program"] == "train_step"
    assert built["trace_s"] == 0.0 and built["lower_s"] == 0.0

    mark = time.perf_counter()
    engine.train_batch(ids())
    first, = trace.phases(since=mark)
    assert (first["name"], first["program"], first["parent"]) == (
        "train/first_step", "train_step", None)
    mark = time.perf_counter()
    engine.train_batch(ids(seed=1))
    assert trace.phases(since=mark) == []            # the hot path opens none
    assert trace.compile_events(since=mark, every=True) == []


def test_first_step_without_compile_holds_the_whole_pipeline():
    import time

    engine = make_engine()
    mark = time.perf_counter()
    engine.train_batch(ids(seq=13))
    first, = trace.phases(since=mark)
    assert first["name"] == "train/first_step"
    step, = [e for e in trace.compile_events(since=mark)
             if e["program"] == "train_step" and e["fun_name"] == "jit(train_step)"]
    assert step["span"] == "train/dispatch"          # innermost: a span
    assert first["t0"] <= step["at"] <= first["t1"]
    assert step["trace_s"] > 0 and step["lower_s"] > 0 and step["seconds"] > 0
    assert step["trace_s"] + step["lower_s"] + step["seconds"] <= first["t1"] - first["t0"]


# -- compile records ----------------------------------------------------

OLD_KEYS = {"span", "program", "fun_name", "seconds", "cache_hit", "at"}


def test_a_compile_inside_a_phase_is_stamped_with_it_and_carries_the_pipeline():
    import time

    import jax.numpy as jnp

    f, x = fresh_jit(1), jnp.ones((4,))
    mark = time.perf_counter()
    with trace.phase("init/programs", program="probe"):
        f(x)
    event, = trace.compile_events(since=mark)
    assert OLD_KEYS <= set(event)
    assert event["span"] == "init/programs" and event["program"] == "probe"
    assert event["fun_name"] == "jit(brand_new)"
    assert event["trace_s"] > 0 and event["lower_s"] > 0 and event["seconds"] > 0
    assert event["compiled"] is True and isinstance(event["cache_hit"], bool)
    assert event["at"] >= mark
    # the same call again: jax holds the executable, nothing is built
    mark = time.perf_counter()
    f(x)
    assert trace.compile_events(since=mark, every=True) == []


def test_a_lowering_alone_leaves_a_record_only_on_request():
    import time

    import jax
    import jax.numpy as jnp

    f, x = fresh_jit(2), jnp.ones((4,))
    mark = time.perf_counter()
    lowered = f.lower(x)
    assert trace.compile_events(since=mark) == []    # the old meaning
    record, = trace.compile_events(since=mark, every=True)
    assert OLD_KEYS <= set(record) and record["compiled"] is False
    assert record["lower_s"] > 0 and record["trace_s"] > 0
    assert record["seconds"] == 0.0 and record["cache_hit"] is False
    # compiled under the same (no) span it completes that record
    lowered.compile()
    done, = trace.compile_events(since=mark, every=True)
    assert done["compiled"] and done["seconds"] > 0
    assert done["trace_s"] == record["trace_s"] and done["at"] > record["at"]
    # what eval_shape traced is the tracing of the program built from it
    g = fresh_jit(3)
    jax.eval_shape(g, x)
    mark = time.perf_counter()
    g(x)
    built, = trace.compile_events(since=mark)
    assert built["trace_s"] > 1e-4


@pytest.mark.parametrize("every", [False, True], ids=["compiled", "every"])
def test_the_ring_holds_three_hundred_programs(every):
    import time

    import jax

    assert trace._EVENTS_MAX >= 1024
    trace.compile_events()                           # listening
    mark = time.perf_counter()
    with trace.phase("serve/warmup", program="ladder"):
        for i in range(300):
            name = f"rung{i}"
            jax.monitoring.record_event_duration_secs(
                trace._TRACE, 0.25, fun_name=name)
            jax.monitoring.record_event_duration_secs(
                trace._LOWER, 0.5, fun_name=f"jit({name})")
            if i % 3:                                # a third is lowered only
                jax.monitoring.record_event(trace._HIT)
                jax.monitoring.record_event_duration_secs(
                    trace._COMPILE, 1.0, fun_name=f"jit({name})")
    records = trace.compile_events(since=mark, every=every)
    assert len(records) == (300 if every else 200)
    assert [r["fun_name"] for r in records][:2] == (
        ["jit(rung0)", "jit(rung1)"] if every else ["jit(rung1)", "jit(rung2)"])
    assert all(r["span"] == "serve/warmup" and r["program"] == "ladder"
               and r["trace_s"] == 0.25 and r["lower_s"] == 0.5
               for r in records)
    assert all(r["cache_hit"] and r["seconds"] == 1.0
               for r in records if r["compiled"])


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


# -- the pass an op ran in ------------------------------------------------

# real paths, from the two tables cut on the chip (PR 26:
# chipbench/tests/data/*.scoped.json.gz) and from the held-share cells' steps
_L = "jit(train_step)/jvp(layers)/while/body/closed_call/"
_B = "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
_H = "jit(train_step)/transpose(jvp(loss))/while/body/closed_call/checkpoint/"
PATHS = [
    (_L + "attn_qkv/add", "forward"),
    (_L + "attn_core/jit(flash_attention)/pallas_call", "forward"),
    (_L + "attn_core/shard_map/vmap(vmap(jit(_splash_attention)))/"
     "splash_mqa_fwd_residuals/splash_mqa_fwd_residuals/pallas_call", "forward"),
    (_L + "attn_norm/shard_map/sxt_rmsnorm/pallas_call", "forward"),
    ("jit(train_step)/jvp(embed)/jit(_take)/gather", "forward"),
    ("jit(train_step)/jvp(loss)/while", "forward"),
    ("jit(train_step)/jvp()/reduce_sum", "forward"),
    (_B + "checkpoint/dot_general", "backward"),
    (_B + "mlp/dot_general", "backward"),
    (_B + "attn_core/transpose", "backward"),       # the primitive, not a wrapper
    (_B + "attn_core/jit(flash_attention)/flash_mha_bwd_dq_block_q_major=1024"
     "_block_k_major=1024_block_k=1024/pallas_call", "backward"),
    (_B + "attn_norm/shard_map/psum", "backward"),
    (_H + "neg", "backward"),
    (_H + "final_norm/shard_map/rsqrt", "backward"),
    ("jit(train_step)/transpose(jvp(embed))/jit(_take)/scatter-add", "backward"),
    # the chunked loss takes the head's gradients in the pass of its loss
    ("jit(train_step)/jvp(loss)/while/body/closed_call/head_dw/dot_general",
     "backward"),
    ("jit(train_step)/jvp(loss)/while/body/closed_call/head_dx/"
     "transpose(jvp(final_norm))/mul", "backward"),
    ("jit(train_step)/jvp(loss)/while/body/closed_call/head_softmax/exp",
     "forward"),
    (_H + "rematted_computation/final_norm/div", "recompute"),
    (_H + "rematted_computation/final_norm/shard_map/sxt_rmsnorm/pallas_call",
     "recompute"),
    (_H + "rematted_computation/jit(log_softmax)/reduce_sum", "recompute"),
    # a custom_vjp's forward rule, replayed under the layer's checkpoint
    (_B + "checkpoint/rematted_computation/attn_core/gdn_scan/gdn_rule_fwd_keep"
     "/pallas_call", "recompute"),
    ("jit(train_step)/optimizer/optimizer/reshape", "update"),
    ("jit(train_step)/optimizer/optimizer/reshape;"
     "jit(train_step)/optimizer/optimizer/reshape", "update"),
    ("jit(train_step)/optimizer/optimizer/shard_map/sxt_fused_adamw/pallas_call",
     "update"),
    ("jit(train_step)/optimizer/grad_clip/dot_general", "update"),
    ("jit(train_step)/optimizer/jit(_where)/select_n", "update"),
    ("jit(train_step)/optimizer/zero3_reduce_scatter/div", "update"),
    ("jit(train_step)/weight_mix/dot_general", "update"),
    ("jit(train_step)/zero3_gather/convert_element_type", "other"),
    ("jit(train_step)/zero3_reduce_scatter/convert_element_type", "other"),
    ("jit(train_step)/final_norm/broadcast_in_dim", "other"),
    ("jit(train_step)/attn_core/shard_map/vmap(vmap(jit(_splash_attention)))/"
     "broadcast_in_dim", "other"),
    ("broadcast.33", "other"),
    ("", "other"),
]


@pytest.mark.parametrize("path, phase", PATHS,
                         ids=[f"{i}-{p}" for i, (_, p) in enumerate(PATHS)])
def test_phase_of_reads_the_pass_off_a_real_path(path, phase):
    assert phase in trace.PHASES
    assert trace.phase_of(path) == phase


@pytest.mark.parametrize("table, want", [
    ("train_one_step", {"forward": 38, "backward": 43, "recompute": 8,
                        "update": 5, "other": 4}),
    ("zero3_x4_one_step", {"forward": 36, "backward": 46, "recompute": 6,
                           "update": 5, "other": 15})])
def test_phase_of_splits_the_scope_paths_of_a_chip_table(table, want):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "tests", "data",
        table + ".scoped.json.gz")
    with gzip.open(path, "rt") as f:
        scopes = json.load(f)["scopes"]
    assert collections.Counter(map(trace.phase_of, scopes)) == want
    assert len(scopes) == sum(want.values())


_BLOCK = dict(vocab_size=64, d_model=64, n_layers=2, max_seq_len=64,
              activation="swiglu", norm="rmsnorm", position="rope",
              rope_theta=1e6, norm_eps=1e-6, tie_embeddings=False)
MODELS = {
    "attn": lambda: tiny(),
    "mla": lambda: TransformerConfig(
        **_BLOCK, n_heads=2, head_size=192, rotary_dim=64,
        rope_interleaved=True, mla_kv_rank=32, mla_qk_content_dim=128,
        mla_qk_rope_dim=64, mla_v_dim=128, layer_pattern=(("mla", "mlp"),)),
    "gated_attn": lambda: TransformerConfig(
        **_BLOCK, n_heads=4, n_kv_heads=2, head_size=64,
        layer_pattern=(("gated_attn", "mlp"),)),
}


@pytest.mark.parametrize("remat", [False, True], ids=["no_remat", "full"])
@pytest.mark.parametrize("mixer", sorted(MODELS))
def test_every_pass_of_a_compiled_step_is_found(mixer, remat):
    """The markers ``phase_of`` reads are jax's own names: a jax that renames
    ``rematted_computation`` or ``transpose(jvp(`` fails here, loudly, and
    not as a metric that reads 0 on the chip."""
    from shuffle_exchange_tpu.parallel.mesh import reset_topology

    reset_topology()
    extra = {"activation_checkpointing": {"enabled": True, "policy": "full"}
             } if remat else {}
    engine = make_engine(model=MODELS[mixer](), **extra)
    engine.compile(ids(vocab=64))
    ops = trace.registered_ops("train_step")
    seen = collections.Counter(trace.phase_of(op.scope) for op in ops.values())
    want = set(trace.PHASES) - (set() if remat else {"recompute"})
    assert {p for p, n in seen.items() if n} == want, seen
    # a scan's body (the layers, under remat their replay) is differentiated
    # whole: every op of it is forward, replayed or backward
    body = [op.scope for op in ops.values()
            if op.scope.startswith("jit(train_step)/") and "/while/body/" in op.scope]
    assert len(body) > 100
    lost = {p for p in body
            if trace.phase_of(p) not in ("forward", "recompute", "backward")}
    assert not lost, sorted(lost)[:10]
    # and everything under the optimizer's scopes is the update
    for op in ops.values():
        if set(scopes_of(op.scope)) & set(trace.SCOPES["optimizer"]):
            assert trace.phase_of(op.scope) == "update", op.scope
    by = collections.defaultdict(set)
    for op in ops.values():
        for name in scopes_of(op.scope):
            by[name].add(trace.phase_of(op.scope))
    for name in ("attn_qkv", "attn_core", "mlp"):
        assert {"forward", "backward"} <= by[name], (name, by[name])
        assert ("recompute" in by[name]) == remat, (name, by[name])


# -- what the compiled step holds -----------------------------------------


def test_registered_memory_is_the_compilers_sizing(monkeypatch):
    from shuffle_exchange_tpu.parallel.mesh import reset_topology

    monkeypatch.setattr(trace, "_programs", {})
    assert trace.registered_memory("train_step") is None    # before compile()
    assert trace.registered_ops("train_step") is None
    reset_topology()
    engine = make_engine()
    compiled = engine.compile(ids())
    sizes = trace.registered_memory("train_step")
    assert set(sizes) == {"argument", "output", "alias", "temp",
                          "generated_code", "peak"}
    analysis = compiled.memory_analysis()
    for key, attr in (("argument", "argument_size_in_bytes"),
                      ("output", "output_size_in_bytes"),
                      ("alias", "alias_size_in_bytes"),
                      ("temp", "temp_size_in_bytes"),
                      ("generated_code", "generated_code_size_in_bytes")):
        assert type(sizes[key]) is int and sizes[key] == getattr(analysis, attr)
    # the state is donated: what the step is handed it hands back
    assert sizes["argument"] > 0 and sizes["alias"] > 0 and sizes["temp"] > 0
    assert sizes["peak"] is None or (type(sizes["peak"]) is int
                                     and sizes["peak"] >= sizes["alias"])
    # a copy: a reader cannot change what the tracer keeps
    sizes["peak"] = -1
    assert trace.registered_memory("train_step")["peak"] != -1
    assert trace.registered_memory("no_such_program") is None


class _NoAnalysis:
    """A backend whose executables have no memory analysis."""

    def as_text(self):
        return HLO

    def memory_analysis(self):
        return None


def test_a_backend_without_analysis_registers_ops_and_no_memory(monkeypatch):
    monkeypatch.setattr(trace, "_programs", {})
    trace.register_program("p", _NoAnalysis())
    assert trace.registered_memory("p") is None
    assert len(trace.registered_ops("p")) == 12


@pytest.mark.parametrize("compiled", [False, True],
                         ids=["before_compile", "after_compile"])
def test_memory_breakdown_line_holds_the_steps_peak(compiled, monkeypatch, caplog):
    import logging

    from shuffle_exchange_tpu.parallel.mesh import reset_topology

    monkeypatch.setattr(trace, "_programs", {})
    reset_topology()
    engine = make_engine(memory_breakdown=True, steps_per_print=1)
    if compiled:
        engine.compile(ids())
    lg = logging.getLogger("shuffle_exchange_tpu")
    lg.addHandler(caplog.handler)
    old = lg.level
    lg.setLevel(logging.INFO)
    try:
        engine.train_batch(ids())
    finally:
        lg.removeHandler(caplog.handler)
        lg.setLevel(old)
    line, = [r.getMessage() for r in caplog.records
             if "mem in_use=" in r.getMessage()]
    assert "in_use=" in line and " peak=" in line
    assert ("step_peak=" in line) == compiled, line
    if compiled:
        sizes = trace.registered_memory("train_step")
        assert f"step_temp={sizes['temp'] / 2**30:.2f}GB" in line
        if sizes["peak"]:
            assert f"step_peak={sizes['peak'] / 2**30:.2f}GB" in line


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


def test_wall_clock_breakdown_logs_from_the_step_records(caplog):
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
    # the line held the three records closed by then; the fourth step's is
    # kept for the next line
    rest = steps_since(engine._breakdown_since)
    assert [r["n"] for r in rest] == [3] and "train/wait" in rest[0]["spans"]


def test_breakdown_line_counts_samples_over_step_records():
    def record(t0, t1, **spans):
        return {"kind": "train", "n": 0, "t0": t0, "t1": t1, "spans": spans,
                "compiles": 0, "numbers": {"samples": 8}}

    rows = [record(0.0, 0.5, **{"train/batch": 0.5, "train/place": 0.004}),
            record(0.5, 1.0, **{"train/batch": 0.5})]
    line = trace.breakdown_line(rows)
    assert "train/batch: 500.00" in line and "train/place: 2.00" in line
    assert line.endswith("samples/s: 16.00")
    assert trace.breakdown_line([]) == "time (ms) | "


def test_staged_api_has_its_spans_on_the_profilers_side(tmp_path):
    import time

    import jax

    engine = make_engine(train_batch_size=8)
    engine.backward(engine.forward(ids()))
    engine.step()                               # compile outside the session
    mark = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    loss = engine.forward(ids(seed=1))
    engine.backward(loss)
    engine.step()
    jax.block_until_ready(engine.state)
    jax.profiler.stop_trace()
    names = [n for n, _, _ in sorted(host_events(str(tmp_path), ("sxt:",)),
                                     key=lambda e: e[1])]
    assert names == ["sxt:train/forward", "sxt:train/backward",
                     "sxt:train/step"]
    # outside a step a span is the profiler's alone: the staged calls open
    # no step, so they feed no record and leave none open behind them
    assert steps_since(mark) == []


def test_a_forward_alone_leaves_no_step_open_for_the_next_train_batch():
    import time

    engine = make_engine()
    mark = time.perf_counter()
    engine.forward(ids())                       # a loss evaluation: no step()
    assert getattr(trace._open, "step", None) is None
    engine.train_batch(ids(seed=1))
    with pytest.raises(Exception):
        engine.backward(batch=np.zeros((3, 5), np.int32))   # raises inside
    assert getattr(trace._open, "step", None) is None
    engine.train_batch(ids(seed=2))
    rows = steps_since(mark)
    assert [r["n"] for r in rows] == [0, 1]
    for r in rows:
        assert sorted(r["spans"]) == ["train/batch", "train/dispatch",
                                      "train/fetch", "train/place",
                                      "train/post"]


# -- step: the trainer's log of its own steps -----------------------------


def test_a_step_is_recorded_with_no_session_and_no_breakdown():
    import time

    engine = make_engine()
    assert not engine.config.wall_clock_breakdown
    mark = time.perf_counter()
    for i in range(3):
        engine.train_batch(ids(seed=i))
    rows = steps_since(mark)
    assert [r["n"] for r in rows] == [0, 1, 2]
    for r in rows:
        assert r["kind"] == "train" and mark <= r["t0"] <= r["t1"]
        assert r["numbers"] == {"samples": 8}
        for name in ("train/batch", "train/fetch", "train/place",
                     "train/dispatch", "train/post"):
            assert 0.0 <= r["spans"][name] <= r["t1"] - r["t0"], (name, r)
        assert "train/wait" not in r["spans"]
    assert all(a["t1"] <= b["t0"] for a, b in zip(rows, rows[1:]))
    json.dumps(rows)                            # plain Python all the way down


def test_since_cuts_the_step_log():
    import time

    mark = time.perf_counter()
    with trace.step("cut", 0):
        pass
    middle = time.perf_counter()
    with trace.step("cut", 1):
        pass
    assert [r["n"] for r in steps_since(mark, "cut")] == [0, 1]
    assert [r["n"] for r in steps_since(middle, "cut")] == [1]
    assert steps_since(time.perf_counter(), "cut") == []
    assert trace.steps("a kind nobody fed") == []


def test_a_steps_spans_sum_a_name_opened_twice():
    import time

    mark = time.perf_counter()
    with trace.step("summed", 7, rows=3) as st:
        for _ in range(2):
            with trace.span("serve/launch"):
                time.sleep(0.01)
        with trace.span("serve/emit"):
            pass
        st.numbers["rows"] = 4                  # amended while open
    record, = steps_since(mark, "summed")
    assert sorted(record["spans"]) == ["serve/emit", "serve/launch"]
    assert 0.02 <= record["spans"]["serve/launch"] <= record["t1"] - record["t0"]
    assert record["n"] == 7 and record["numbers"] == {"rows": 4}


def test_a_span_outside_any_step_reaches_no_record():
    import time

    mark = time.perf_counter()
    with trace.span("train/place"):
        with trace.step("inside", 0):
            with trace.span("train/dispatch"):
                pass
    with trace.span("train/post"):
        pass
    record, = steps_since(mark, "inside")
    # the span that was open before the step is not the step's either
    assert list(record["spans"]) == ["train/dispatch"]


def test_compiles_counts_the_step_that_built_a_program():
    import time

    f = fresh_jit(31)
    mark = time.perf_counter()
    for n in range(2):
        with trace.step("built", n), trace.span("train/dispatch", program="p"):
            f(np.float32(1.0))
    first, second = steps_since(mark, "built")
    assert first["compiles"] == 1 and second["compiles"] == 0
    assert len(trace.compile_events(since=first["t0"])) == 1


def test_two_threads_open_steps_do_not_mix():
    import threading
    import time

    mark = time.perf_counter()
    both_open = threading.Barrier(2)

    def replica(name):
        with trace.step("threads", 0, replica=name):
            both_open.wait(timeout=10)
            with trace.span("serve/" + name):
                pass
            both_open.wait(timeout=10)          # both close after both spans

    threads = [threading.Thread(target=replica, args=(n,)) for n in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rows = steps_since(mark, "threads")
    assert sorted((r["numbers"]["replica"], list(r["spans"])) for r in rows) \
        == [("a", ["serve/a"]), ("b", ["serve/b"])]
    assert getattr(trace._open, "step", None) is None


def test_a_step_inside_a_step_hands_the_thread_back():
    import time

    mark = time.perf_counter()
    with trace.step("outer", 0):
        with trace.step("inner", 5):
            with trace.span("b"):
                pass
        with trace.span("a"):
            pass
    assert list(steps_since(mark, "outer")[0]["spans"]) == ["a"]
    assert list(steps_since(mark, "inner")[0]["spans"]) == ["b"]


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


def test_a_scheduler_tick_lands_under_serve():
    import time

    sched = make_scheduler(Clock())
    sched.submit([1, 2, 3, 4, 5], max_new_tokens=3)
    mark = time.perf_counter()
    first_tick = sched.ticks
    while sched.tick():
        pass
    rows = steps_since(mark, "serve")
    assert [r["n"] for r in rows] == list(range(first_tick, sched.ticks))
    assert steps_since(mark, "train") == []
    busy = [r for r in rows if "serve/launch" in r["spans"]]
    assert busy and rows[0]["compiles"] >= 1
    for r in busy:
        for name in ("serve/admit", "serve/dispatch", "serve/pack",
                     "serve/launch", "serve/readback", "serve/emit"):
            assert name in r["spans"], (name, r)
        assert r["spans"]["serve/launch"] <= r["spans"]["serve/dispatch"]


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
