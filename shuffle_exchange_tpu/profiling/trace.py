"""The program's one tracer: what trainer, model, mesh and scheduler report
about themselves. Five primitives, no config field, no environment variable.

``span(name)``
    A host span: ``jax.profiler.TraceAnnotation("sxt:" + name)``. Whenever
    anyone's profiler session runs (``xla_trace`` below, a benchmark's own)
    the span lands on the host plane of the same ``.xplane.pb`` as the device
    ops, on the same clock, so an idle gap of the device can be laid against
    what the host was doing. With no session it is a TraceMe that records
    nothing; where a ``step`` is open on its thread its duration is added, on
    ``time.perf_counter``, to that step's record, and outside a step it is
    the profiler's alone.
``phase(name)``
    A span for what runs once per engine or once per program, never per step:
    the package's import, ``sxt.initialize``, the sections of
    ``Engine.__init__``, ``Engine.compile()``'s lowering and compiling, an
    engine's first ``train_batch``. It is a ``span`` (same annotation, same
    open-span stack: a compilation inside it is stamped with it) that is ALSO
    always appended, on ``time.perf_counter``, to a bounded start-up log:
    ``phases()`` hands out the rows (``name``, ``program``, ``t0``, ``t1``,
    ``parent``: the open phase it nested in) with no profiler session, so
    start-up can be read after the fact. A phase
    is HOST time: what the host spent between two lines, device work it
    waited for included, device work it only enqueued not. It synchronises
    nothing, and it never goes on the hot path: a step opens ``span``s.
``scope(name)``
    ``jax.named_scope``, for code under ``jit``: it changes the ``op_name``
    metadata of the ops traced inside it and nothing else, so the device
    ops of a trace can be summed by the layer of the program they belong to.
``step(kind, n, **numbers)``
    ``jax.profiler.StepTraceAnnotation``: one per ``train_batch`` and one per
    scheduler tick, so the profiler groups device work by step. It is ALSO,
    with or without a profiler session, the program's log of its own steps:
    closing it appends one plain record to a bounded ring per ``kind``, which
    ``steps(kind, since)`` hands out: ``kind``, ``n`` (the step number the
    annotation carries: a record and the profiler's event of one step are
    joined by it), ``t0`` and ``t1`` on ``time.perf_counter`` (the clock of
    ``phases()`` and ``compile_events()``), ``spans`` (``{name: seconds}``:
    the summed duration of every ``span`` closed inside the step on the
    step's thread, a parent and its children each under their own name),
    ``numbers`` (the caller's: ``train_batch`` passes ``samples``) and
    ``compiles``, which ``steps`` fills in when it is read: how many of
    ``compile_events()`` were stamped between ``t0`` and ``t1``. HOST time,
    as a phase is: a step that only enqueues device work is as long as its
    dispatch. No device array, no synchronisation. The rings are the
    process's: two engines' steps are one kind's rows.
``compile_events()``
    The program's own ``jax.monitoring`` listener: one record per program
    built, stamped with the innermost open phase or span and the program it
    was opened for: ``trace_s`` (tracing to a jaxpr), ``lower_s`` (jaxpr to
    an MLIR module: the two parts no cache serves), ``seconds`` (the backend
    compilation, or the persistent cache's read), cache hit or not and the
    ``perf_counter`` time. By default the records that ended in a backend
    compilation or a cache read; ``every=True`` adds those of a lowering
    alone. Bounded; always on.

``program_ops`` reads a compiled program's HLO text into instruction name ->
(scope path, opcode, contains a collective): the join for traces whose device
events carry no op-name stat, and the only way to see a collective inside a
``fusion``. ``Engine.compile()`` registers it for ``train_step``, with the
compiler's own sizing of the program (``registered_memory``: the peak that
decides whether a step fits). ``phase_of`` reads the pass an op ran in
(forward, recompute, backward, update) off the same scope path.

    from shuffle_exchange_tpu.profiling import trace

    with trace.xla_trace("traces/step100"):
        engine.train_batch(batch)       # sxt: spans + scoped device ops

View with TensorBoard's profile plugin pointed at the log dir, or read the
``.xplane.pb`` with ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import functools
import re
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

PREFIX = "sxt:"

# every scope the package opens under jit, by the layer a reader sums it to.
# "attn_qk_norm" nests inside "attn_qkv", the "moe_*" scopes inside
# "moe" and the four "head_*" scopes (the chunked loss's scan) inside "loss":
# a reader that sums by the outer name counts them with it. A Gated DeltaNet
# layer opens the attention layer's outer scopes and its own inside them:
# "gdn_conv" and "gdn_gates" inside "attn_qkv", "gdn_scan" (the chunked rule)
# inside "attn_core", "gdn_out_norm" inside "attn_out"; a gated attention
# layer's "attn_gate" sits inside "attn_out"; the shared expert runs under
# "moe_shared" inside "moe". A latent-attention (MLA) layer's "mla_q",
# "mla_kv_down", "mla_kv_norm", "mla_kv_up" and "mla_rope" (rotation, the one
# rotary key's broadcast over the heads, the concatenations) nest inside
# "attn_qkv" (its attention kernel takes scores and values at their own
# widths: nothing is padded, so there is no padding scope). A window layer
# (mixer "swa") opens "swa_qkv" and "swa_rope" inside "attn_qkv", "swa_core"
# inside "attn_core" and "swa_out" inside "attn_out"; a full layer of such a
# stack that rotates by a YaRN table does so under "rope_yarn" inside
# "attn_qkv"; a full layer that rotates NOTHING in a model whose window layers
# rotate (``unrotated_mixers``) opens "nope_qkv", "nope_core" and "nope_out" as
# a window layer opens its "swa_*". A router that reads an input of its own
# (``moe_router_input`` "block") opens "pre_router" AROUND "moe_router".
# A learned sparse attention layer (mixer "dsa") rotates under "mrope" and opens
# "dsa_index" (the indexer's projections, norm, rotation and scores) and
# "dsa_select" (the k-th largest score a query, the mask) inside "attn_qkv",
# "dsa_core" (the softmax over the chosen keys) and "dsa_kl" (the indexer's
# loss: its target and the KL) inside "attn_core"; the M-RoPE table itself is
# built under "mrope" inside "embed".
# A gated short-convolution layer (mixer "sconv") opens "sconv_in"
# inside "attn_qkv", "sconv_mix" (the pass between its projections) inside
# "attn_core" and "sconv_out" inside "attn_out". A Mamba-2 state-space layer
# (mixer "ssm") opens "ssm_in" (its input projection), "ssm_conv" (taps, bias,
# SiLU) and "ssm_gates" (the step and the decay) inside "attn_qkv", "ssm_scan"
# (without its skip) inside "attn_core", "ssm_out_norm" (the skip "D x", the
# gate and the grouped norm) and "ssm_out" inside "attn_out". A layer that is a mixer
# alone (ffn "none") opens no scope of the "mlp" layer.
# A KDA layer (mixer "kda", the delta rule with a decay a key channel) opens
# "kda_conv" (its three convolutions, SiLU and the l2 norms of q and k) and
# "kda_gates" (beta, the per-channel log-decay and its step statistics) inside
# "attn_qkv", "kda_scan" (the chunked rule) inside "attn_core" and
# "kda_out_norm" (the per-head norm and the low-rank sigmoid gate) inside
# "attn_out"; a latent-attention layer under ``unrotated_mixers`` opens the
# "nope_*" scopes around its "mla_*" ones and rotates nothing under "mla_rope".
# A looped stack (``loop_steps`` > 1) opens "loop" AROUND its outer scan (every
# op of the layers inside carries it as an outer component; what has "loop"
# for its INNERMOST scope is the outer scan's own work: the carry, slicing
# and stacking what the visits keep, the sums of the weights' gradients over
# the visits), "loop_norm" inside it (the final norm after each pass of the
# stack, in every pass: the head's scan opens no "final_norm" then) and
# "loop_exit" inside "loss" (the exit gate, the exit distribution, its
# entropy, the weighing of the exits' losses and their backward).
# "plumbing" is what belongs to no layer of the model: the layer scan's own
# slicing and stacking, the masters' cast to the compute dtype, the
# gradients' cast back and normalization
SCOPES = {
    "attn": ("attn_norm", "attn_qkv", "attn_qk_norm", "attn_core", "attn_out",
             "attn_gate", "gdn_conv", "gdn_gates", "gdn_scan", "gdn_out_norm",
             "kda_conv", "kda_gates", "kda_scan", "kda_out_norm",
             "mla_q", "mla_kv_down", "mla_kv_norm", "mla_kv_up", "mla_rope",
             "swa_qkv", "swa_rope", "swa_core", "swa_out", "rope_yarn",
             "nope_qkv", "nope_core", "nope_out",
             "mrope", "dsa_index", "dsa_select", "dsa_core", "dsa_kl",
             "sconv_in", "sconv_mix", "sconv_out",
             "ssm_in", "ssm_conv", "ssm_gates", "ssm_scan", "ssm_out_norm", "ssm_out"),
    "mlp": ("mlp_norm", "mlp", "moe", "pre_router", "moe_router", "moe_dispatch",
            "moe_experts", "moe_combine", "moe_shared"),
    "loss": ("embed", "final_norm", "loop_norm", "loss", "loop_exit", "head_logits",
             "head_softmax", "head_dx", "head_dw"),
    "optimizer": ("optimizer", "grad_clip", "weight_mix"),
    "mesh": ("zero3_gather", "zero3_reduce_scatter"),
    "plumbing": ("layers", "loop", "weight_cast", "grad_normalize"),
}

# the passes of a train step, as ``phase_of`` reads them off an op_name path
PHASES = ("forward", "recompute", "backward", "update", "other")
# gradients the program takes by hand inside a forward rule, where no
# ``transpose(`` marks them: the chunked loss's ``custom_vjp`` computes the
# head's dx and dw in the pass of its loss (PR 32)
_BACKWARD_SCOPES = ("head_dx", "head_dw")

# a 45 s window at 109 ms a step is 413 steps; a serving tick is a few ms
_STEPS_MAX = 4096
_PHASES_MAX = 512
# a run's programs: 160 in one training cell, 324 up a serving ladder
_EVENTS_MAX = 1024

# kind -> ring of step records
_steps: Dict[str, collections.deque] = collections.defaultdict(
    lambda: collections.deque(maxlen=_STEPS_MAX))
_phases: collections.deque = collections.deque(maxlen=_PHASES_MAX)
_events: collections.deque = collections.deque(maxlen=_EVENTS_MAX)
# .stack: [(name, program)] of open spans and phases; .phases: open phases;
# .step: the innermost open step of this thread
_open = threading.local()
_listening = False
# what this thread is building: .hit (the persistent cache answered),
# .traced ({fun_name: seconds} since its last lowering), .lowered (the record
# of that lowering, which a backend compilation may complete)
_build = threading.local()


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


class span:
    """``with span("train/place"):`` - see the module docstring. ``program``
    names what a compilation inside the span builds (``train_step``, an
    engine-v2 cache key); inner spans inherit it. ``numbers`` ride along as
    the event's stats in a profiler session (``span("serve/first_schedule",
    wait_ms=3.2)``) and nowhere else."""

    __slots__ = ("name", "program", "numbers", "_note", "_step", "_t0")

    def __init__(self, name: str, program: Optional[str] = None, **numbers):
        self.name = name
        self.program = program
        self.numbers = numbers

    def __enter__(self):
        import jax

        if not _listening:
            _listen()
        stack = _stack()
        if self.program is None and stack:
            self.program = stack[-1][1]
        stack.append((self.name, self.program))
        self._note = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                  **self.numbers)
        self._note.__enter__()
        self._step = getattr(_open, "step", None)
        self._t0 = time.perf_counter() if self._step is not None else 0.0
        return self

    def __exit__(self, *exc):
        if self._step is not None:
            took, spans = time.perf_counter() - self._t0, self._step.spans
            spans[self.name] = spans.get(self.name, 0.0) + took
        self._note.__exit__(*exc)
        _stack().pop()
        return False


def _open_phases() -> list:
    try:
        return _open.phases
    except AttributeError:
        _open.phases = []
        return _open.phases


class phase(span):
    """``with phase("init/params"):`` - a ``span`` whose row always goes to
    the start-up log that ``phases()`` reads, and never to a step's record.
    For code that runs once per engine or per program
    (module docstring). ``t0`` backdates the start on ``perf_counter`` for a
    phase that cannot be opened where it begins (the package's import opens
    its own after importing this module)."""

    __slots__ = ("parent", "_began")

    def __init__(self, name: str, program: Optional[str] = None,
                 t0: Optional[float] = None):
        super().__init__(name, program)
        self._began = t0

    def __enter__(self):
        if self._began is None:
            self._began = time.perf_counter()
        opened = _open_phases()
        self.parent = opened[-1] if opened else None
        opened.append(self.name)
        return super().__enter__()

    def __exit__(self, *exc):
        # not ``span.__exit__``: that one feeds the open step's record
        ended = time.perf_counter()
        self._note.__exit__(*exc)
        _stack().pop()
        _open_phases().pop()
        _phases.append({"name": self.name, "program": self.program,
                        "t0": self._began, "t1": ended, "parent": self.parent})
        return False


def phases(since: float = 0.0) -> List[dict]:
    """The start-up log: the phases closed so far that began at or after
    ``since`` on ``perf_counter``, oldest first (a parent before its
    children)."""
    rows = [dict(r) for r in list(_phases) if r["t0"] >= since]
    return sorted(rows, key=lambda r: (r["t0"], -r["t1"]))


def scope(name: str):
    """A named scope for code traced under ``jit`` (``op_name`` metadata)."""
    import jax

    return jax.named_scope(name)


class step:
    """``with step("train", n, samples=8):`` - one training step or scheduler
    tick: the profiler's step annotation and, always, one record in the ring
    of its ``kind`` (module docstring). ``numbers`` may be amended while the
    step is open. Opens and closes on one thread; a step opened inside
    another keeps its own record and hands the thread back to the outer one."""

    __slots__ = ("kind", "n", "numbers", "spans", "_note", "_outer", "_t0")

    def __init__(self, kind: str, n: int, **numbers):
        self.kind = kind
        self.n = int(n)
        self.numbers = numbers

    def __enter__(self):
        import jax

        if not _listening:
            _listen()       # a program built inside is in ``compiles``
        self._note = jax.profiler.StepTraceAnnotation(PREFIX + self.kind,
                                                      step_num=self.n)
        self._note.__enter__()
        self.spans = {}
        self._outer = getattr(_open, "step", None)
        _open.step = self
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        ended = time.perf_counter()
        _open.step = self._outer
        self._note.__exit__(*exc)
        _steps[self.kind].append({
            "kind": self.kind, "n": self.n, "t0": self._t0, "t1": ended,
            "spans": self.spans, "numbers": self.numbers})
        return False


def steps(kind: str, since: float = 0.0) -> List[dict]:
    """The program's log of its own steps: copies of the records of ``kind``
    ("train", "serve") that began at or after ``since`` on ``perf_counter``,
    oldest first, each with ``compiles``: the programs ``compile_events``
    stamped inside it (a compilation on another thread counts where it falls;
    the events' ring is bounded too). The newest ``_STEPS_MAX`` of a kind are
    kept."""
    built = sorted(e["at"] for e in compile_events(since))
    rows = [dict(r, spans=dict(r["spans"]), numbers=dict(r["numbers"]),
                 compiles=bisect.bisect_right(built, r["t1"])
                 - bisect.bisect_left(built, r["t0"]))
            for r in list(_steps.get(kind, ())) if r["t0"] >= since]
    return sorted(rows, key=lambda r: r["t0"])


def breakdown_line(records) -> str:
    """The ``wall_clock_breakdown`` log line off step records: milliseconds
    A STEP per span name (a name opened twice in a step counts twice; the
    mean is over the records, not over the name's occurrences), and
    samples/s from the records' ``numbers["samples"]`` over their
    ``t1 - t0``."""
    by: Dict[str, float] = {}
    for r in records:
        for name, took in r["spans"].items():
            by[name] = by.get(name, 0.0) + took
    parts = [f"{n}: {1e3 * by[n] / len(records):.2f}" for n in sorted(by)]
    msg = "time (ms) | " + " | ".join(parts)
    took = sum(r["t1"] - r["t0"] for r in records)
    samples = sum(r["numbers"].get("samples", 0) for r in records)
    if samples and took > 0:
        msg += f" | samples/s: {samples / took:.2f}"
    return msg


@contextlib.contextmanager
def xla_trace(logdir: str):
    """An operator's profiler session around a region: device ops with the
    program's scopes, host plane with its ``sxt:`` spans, into ``logdir``."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# Compile events
# ---------------------------------------------------------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


def _on_duration(event: str, secs: float, **kw) -> None:
    """One record per program built, from the three durations jax reports on
    the building thread in this order: tracing (``fun_name`` "f"; every jit
    traced inside it reports first, ``jax.eval_shape(f)`` reports one too,
    and a call that finds the jaxpr cached reports ~0 s), lowering
    ("jit(f)") and the backend's compilation or cache read ("jit(f)").
    Tracings add up by name until a lowering claims its own and drops the
    rest (they ran inside it), so a cached call leaves nothing; a lowering
    opens the record; a backend compilation completes the record its thread
    lowered last if that was the same program under the same span (a ``jit``
    call), and is a record of its own otherwise (``lowered.compile()`` under
    another phase than ``.lower()``; a compilation jax reports no lowering
    for)."""
    if event == _TRACE:
        try:
            traced = _build.traced
        except AttributeError:
            traced = _build.traced = {}
        fun = kw.get("fun_name")
        traced[fun] = traced.get(fun, 0.0) + float(secs)
        return
    if event != _COMPILE and event != _LOWER:
        return
    stack = _stack()
    name, program = stack[-1] if stack else (None, None)
    fun = kw.get("fun_name")
    now = time.perf_counter()
    if event == _LOWER:
        traced = getattr(_build, "traced", {})
        inner = fun[fun.find("(") + 1:-1] if fun and fun.endswith(")") else fun
        record = {"span": name, "program": program or fun, "fun_name": fun,
                  "trace_s": traced.get(inner, 0.0), "lower_s": float(secs),
                  "seconds": 0.0, "cache_hit": False, "compiled": False,
                  "at": now}
        traced.clear()          # what it traced inside is in its seconds
        _build.lowered = record
        _events.append(record)
        return
    hit = getattr(_build, "hit", False)
    _build.hit = False
    record = getattr(_build, "lowered", None)
    _build.lowered = None
    done = {"seconds": float(secs), "cache_hit": bool(hit), "compiled": True,
            "at": now}
    if record is not None and record["fun_name"] == fun and record["span"] == name:
        record.update(done)
    else:
        _events.append({"span": name, "program": program or fun,
                        "fun_name": fun, "trace_s": 0.0, "lower_s": 0.0, **done})


def _on_event(event: str, **_) -> None:
    # the backend-compile duration wraps the persistent-cache lookup, so the
    # hit is seen before the duration it belongs to
    if event == _HIT:
        _build.hit = True


def _listen() -> None:
    global _listening
    import jax

    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)


def compile_events(since: float = 0.0, every: bool = False) -> List[dict]:
    """Programs built so far (``at`` >= ``since`` on ``perf_counter``, the
    time of a record's last part): those that ended in a backend compilation
    or a read of the persistent cache; with ``every`` also those of a tracing
    and lowering alone (``compiled`` false, ``seconds`` 0: an AOT
    ``.lower()``, a lowering whose executable jax already held)."""
    _listen()
    return [dict(e) for e in list(_events)
            if e["at"] >= since and (every or e["compiled"])]


# ---------------------------------------------------------------------------
# A compiled program's instructions, by scope
# ---------------------------------------------------------------------------


# ``transpose(jvp(layers))`` -> ("transpose(jvp(", "layers")
_WRAPPED = re.compile(r"^((?:\w+\()*)(.*?)\)*$")


@functools.lru_cache(maxsize=None)
def phase_of(op_name: str) -> str:
    """The pass of the train step an op belongs to, read off its ``op_name``
    path (``Op.scope``): one of ``PHASES``, first match wins.

    ``update``     a component is one of ``SCOPES["optimizer"]``
                   (``jit(train_step)/optimizer/optimizer/reshape``);
    ``recompute``  a component is ``rematted_computation``: what
                   ``jax.checkpoint`` replays inside the backward pass
                   (``.../transpose(jvp(loss))/while/body/closed_call/
                   checkpoint/rematted_computation/final_norm/div``). A
                   ``custom_vjp``'s forward rule replayed there
                   (``gdn_rule_fwd_keep``, the splash forward under a policy
                   that does not keep its residuals) is ``recompute`` too;
    ``backward``   a component is wrapped in ``transpose(``
                   (``.../transpose(jvp(layers))/while/body/closed_call/mlp/
                   dot_general``), the ``checkpoint``'s own transposed ops
                   included; or is a scope the program names for a gradient
                   it takes by hand inside a forward rule (``.../jvp(loss)/
                   while/body/closed_call/head_dw/dot_general``);
    ``forward``    a component is wrapped in ``jvp(``
                   (``jit(train_step)/jvp(layers)/while/body/closed_call/
                   attn_qkv/add``);
    ``other``      the rest: the masters' cast and gather, the gradients'
                   reduce-scatter and normalisation outside ``optimizer``,
                   constants, ops with no ``op_name``.

    A ``fusion`` carries the ONE ``op_name`` XLA leaves on it (as with
    scopes): where the compiler fuses a replayed producer into a backward
    consumer the whole fusion counts as that one op's pass."""
    wrappers, names = set(), set()
    for part in op_name.split("/"):
        head, name = _WRAPPED.match(part).groups()
        wrappers.update(head.split("("))
        names.add(name)
    if names.intersection(SCOPES["optimizer"]):
        return "update"
    if "rematted_computation" in names:
        return "recompute"
    if "transpose" in wrappers or names.intersection(_BACKWARD_SCOPES):
        return "backward"
    if "jvp" in wrappers:
        return "forward"
    return "other"


class Op(NamedTuple):
    scope: str                  # the op_name metadata, "" where there is none
    opcode: str
    contains_collective: bool


_COLLECTIVE = re.compile(r"^(all-gather|reduce-scatter|all-reduce|"
                         r"collective-permute|all-to-all)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
# where a new instruction, computation or block end starts (not a continuation)
_STARTS = re.compile(r"^(\s+(?:ROOT\s+)?%?[\w.\-]+\s+=\s|(?:ENTRY\s+)?%?[\w.\-]+\s.*\{\s*$|\}\s*$|\s*$)")
_OPCODE = re.compile(r"(?<![\w.%\-])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|branch_computations|"
                     r"called_computations)=\{?([^,}\s]+(?:,\s*%[\w.\-]+)*)\}?")

# name -> (reader of the instruction table, the compiler's sizes or None)
_programs: Dict[str, Tuple[Callable[[], Dict[str, Op]], Optional[dict]]] = {}


def program_ops(compiled) -> Dict[str, Op]:
    """Instruction name -> ``Op`` for a compiled program (``jit(f).lower(...)
    .compile()``) or its HLO text. ``contains_collective`` is true if the
    instruction, or a computation it calls (a fusion's, a while's body), holds
    an all-gather / reduce-scatter / all-reduce / collective-permute /
    all-to-all."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    ops: Dict[str, Tuple[str, str, Tuple[str, ...]]] = {}
    holds: Dict[str, List[str]] = {}            # computation -> instructions
    current = None
    lines = text.splitlines()
    for i, line in enumerate(lines):
        m = _INSTRUCTION.match(line)
        if m and current is not None:
            name, rest = m.groups()
            head, _, meta = rest.partition(", metadata={")
            # a kernel's frontend attributes hold JSON with line breaks: the
            # instruction's metadata then sits on a following line
            j = i + 1
            while not meta and j < len(lines) and not _STARTS.match(lines[j]):
                _, _, meta = lines[j].partition(", metadata={")
                j += 1
            code = _OPCODE.search(head)
            called = tuple(c.strip().lstrip("%") for g in _CALLED.findall(head)
                           for c in g.split(","))
            scope = _OP_NAME.search(meta)
            ops[name] = (scope.group(1) if scope else "",
                         code.group(1) if code else "", called)
            holds[current].append(name)
            continue
        m = _COMPUTATION.match(line)
        if m and "=" not in line.split("(", 1)[0]:
            current = m.group(1)
            holds[current] = []

    memo: Dict[str, bool] = {}

    def computation_has(comp: str) -> bool:
        if comp not in memo:
            memo[comp] = False                  # a cycle cannot occur in HLO
            memo[comp] = any(instruction_has(n) for n in holds.get(comp, ()))
        return memo[comp]

    def instruction_has(name: str) -> bool:
        _, code, called = ops[name]
        return bool(_COLLECTIVE.match(code)) or any(
            computation_has(c) for c in called)

    return {n: Op(s, code, instruction_has(n))
            for n, (s, code, _) in ops.items()}


def register_program(name: str, compiled) -> None:
    """Keep ``program_ops`` of a compiled program under ``name``, and the
    compiler's sizing of it (``registered_memory``). Text and sizes are taken
    now (the executable is not kept alive); the text is parsed when first
    read."""
    text = compiled.as_text()
    _programs[name] = (functools.cache(lambda: program_ops(text)),
                       _memory_of(compiled))


def _memory_of(compiled) -> Optional[dict]:
    """``memory_analysis()`` as plain integers, bytes on one device; None
    where the backend has no analysis (jax then returns None)."""
    m = compiled.memory_analysis()
    if m is None:
        return None
    peak = getattr(m, "peak_memory_in_bytes", None)
    return {"argument": int(m.argument_size_in_bytes),
            "output": int(m.output_size_in_bytes),
            "alias": int(m.alias_size_in_bytes),
            "temp": int(m.temp_size_in_bytes),
            "generated_code": int(m.generated_code_size_in_bytes),
            "peak": int(peak) if peak else None}


def registered_ops(name: str) -> Optional[Dict[str, Op]]:
    """``program_ops`` of the program registered as ``name``, or None."""
    entry = _programs.get(name)
    return entry[0]() if entry is not None else None


def registered_memory(name: str) -> Optional[dict]:
    """The compiler's sizes of the program registered as ``name``, in bytes
    on one device: ``argument``, ``output``, ``alias``, ``temp``,
    ``generated_code`` and ``peak`` (XLA's ``peak_memory_in_bytes``: what the
    step needs at its fullest, arguments included; None where this backend's
    analysis has none). None where nothing is registered under ``name``."""
    entry = _programs.get(name)
    return dict(entry[1]) if entry is not None and entry[1] else None
