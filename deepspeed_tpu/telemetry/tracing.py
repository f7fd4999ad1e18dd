"""One timeline, two sinks — and the compiled step's account of itself.

**Host spans.**  Every subsystem emits spans through the process-wide
tracer: train-step phases (fwd/bwd/step through the engine timers),
serving scheduler iterations (admit/prefill/decode), checkpoint
stage/publish, and resilience events (faults fired, health transitions,
drains).  A span lands in up to two places:

- the **profiler's trace**: every ``span`` (of the armed tracer *and* of
  the null tracer), ``begin``/``end`` and ``instant`` enters a
  ``jax.profiler.TraceAnnotation("ds/" + name)``.  That is a TraceMe:
  nanoseconds while no profiler session runs, and inside any session —
  the benchmark's, an operator's — an event on ``/host:CPU`` on the same
  clock as the ``/device:TPU:n`` lines (``ds/train/step``,
  ``ds/train/fused_step``, ``ds/ckpt/stage`` ...).  The program never
  starts or stops a session itself;
- the **Chrome-trace file**: ``DS_TRACE=/path/trace.json`` (or the
  ``telemetry.trace`` config key) arms a :class:`SpanTracer`, which also
  keeps the spans in memory with correlation ids and writes them for
  ``chrome://tracing`` / https://ui.perfetto.dev.

Correlation ids stitch the file's timeline together: a span opened with
``corr="train-step-12"`` pushes that id onto a thread-local stack, and
every nested span/instant that does not name its own id inherits it —
so a fault injected inside step 12's checkpoint save carries
``train-step-12`` without the fault injector knowing about steps.

Event model of the file (Chrome trace-event format):
- spans are matched ``B``/``E`` pairs per (pid, tid) — the context
  manager guarantees LIFO nesting, which ``scripts/trace_validate.py``
  asserts;
- point events are ``i`` instants (process-scoped);
- ``flush()`` sorts by timestamp and writes ``{"traceEvents": [...]}``
  atomically (tmp + rename); an atexit hook flushes the active tracer
  so a drain/exit still lands the file.

When no trace path is armed, every hook routes through
:data:`NULL_TRACER`, whose ``span()`` is the bare TraceAnnotation and
whose other hooks do nothing.

**Inside the compiled step** the host cannot see, so the program names
its own parts: ``jax.named_scope``s with the fixed names of
:data:`STEP_SCOPES` and Pallas kernels with those of
:data:`KERNEL_NAMES`.  Scopes are HLO metadata only — no runtime cost,
no change to what XLA fuses.  A device trace does not carry that
metadata (an ``XLA Ops`` event is the instruction's text without
``metadata={...}``), but it does carry the **instruction name**, and so
does the executable's own text.  :func:`get_program_map` publishes the
table between the two: instruction name -> its scope path, phase
(:func:`phase_of`), kernel name, collective kind and wire bytes.  It is
built **lazily**: the engine registers a thunk on the first fused
dispatch, and the text of the executable is fetched and parsed only
when someone first asks.
"""
import atexit
import json
import os
import re
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional

from jax.profiler import TraceAnnotation

TRACE_ENV = "DS_TRACE"
#: prefix of every host span in a profiler session (``/host:CPU``)
ANNOTATION_PREFIX = "ds/"


class SpanTracer:
    """Thread-safe in-memory trace buffer with Chrome-trace emission.

    Signal-safety: resilience code emits instants from SIGTERM handlers
    (preemption latch, serving drain → health transition), which run ON
    the thread they interrupt — possibly while that thread holds the
    buffer lock.  The lock is therefore an ``RLock`` (re-acquiring on
    the same thread cannot deadlock), and the size-triggered background
    flush is ``acquire(blocking=False)`` so a handler can never wedge on
    file I/O either.

    The buffer self-bounds: past :data:`FLUSH_EVENT_THRESHOLD` buffered
    events the emitting thread flushes to disk (append-merge), so a
    multi-hour traced run costs bounded host RAM and a hard kill loses
    at most one threshold window of events, not the whole trace."""

    FLUSH_EVENT_THRESHOLD = 50_000

    def __init__(self, path: str):
        self.path = path
        self.enabled = True
        self.pid = os.getpid()
        self._clock = time.perf_counter
        self._t0 = self._clock()
        self._events = []
        self._lock = threading.RLock()
        self._flush_lock = threading.Lock()
        self._tls = threading.local()

    # ------------------------------------------------------------ helpers
    def _ts_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_corr(self) -> Optional[str]:
        """Innermost correlation id on this thread (None outside spans)."""
        for corr, _ in reversed(self._stack()):
            if corr is not None:
                return corr
        return None

    def _emit(self, ev: Dict[str, Any]):
        with self._lock:
            self._events.append(ev)
            n = len(self._events)
        if n >= self.FLUSH_EVENT_THRESHOLD:
            # best-effort spill outside the buffer lock; skip rather
            # than block if another thread is already writing
            if self._flush_lock.acquire(blocking=False):
                try:
                    self._flush_locked()
                finally:
                    self._flush_lock.release()

    def _event(self, ph: str, name: str, cat: str,
               corr: Optional[str], args: Optional[Dict]) -> Dict[str, Any]:
        ev = {"name": name, "ph": ph, "ts": self._ts_us(),
              "pid": self.pid, "tid": threading.get_ident() % (1 << 31),
              "cat": cat or "ds"}
        a = dict(args or {})
        if corr is not None:
            a["corr"] = corr
        if a:
            ev["args"] = a
        return ev

    # -------------------------------------------------------------- spans
    def begin(self, name: str, cat: str = "", corr: Optional[str] = None,
              args: Optional[Dict] = None):
        """Open a span (``E`` must follow on the same thread, LIFO)."""
        corr = corr if corr is not None else self.current_corr()
        annotation = TraceAnnotation(ANNOTATION_PREFIX + name)
        annotation.__enter__()
        self._stack().append((corr, annotation))
        self._emit(self._event("B", name, cat, corr, args))

    def end(self, name: str, args: Optional[Dict] = None):
        st = self._stack()
        corr, annotation = st.pop() if st else (None, None)
        self._emit(self._event("E", name, "", corr, args))
        if annotation is not None:
            annotation.__exit__(None, None, None)

    @contextmanager
    def span(self, name: str, cat: str = "", corr: Optional[str] = None,
             args: Optional[Dict] = None):
        self.begin(name, cat=cat, corr=corr, args=args)
        try:
            yield self
        finally:
            self.end(name)

    def instant(self, name: str, cat: str = "", corr: Optional[str] = None,
                args: Optional[Dict] = None):
        """Point event (fault fired, health transition, signal)."""
        corr = corr if corr is not None else self.current_corr()
        ev = self._event("i", name, cat, corr, args)
        ev["s"] = "p"                     # process-scoped instant
        with TraceAnnotation(ANNOTATION_PREFIX + name):
            self._emit(ev)

    # ------------------------------------------------------------- output
    def drain(self):
        """Snapshot + clear the buffer (sorted by ts); flush() callers
        normally want the file, tests may want the raw events."""
        with self._lock:
            events, self._events = self._events, []
        events.sort(key=lambda e: e["ts"])
        return events

    def flush(self) -> Optional[str]:
        """Append-merge the buffer into ``self.path`` atomically.  Safe
        to call repeatedly; returns the path (None when disabled)."""
        with self._flush_lock:
            return self._flush_locked()

    def _flush_locked(self) -> Optional[str]:
        events = self.drain()
        if not events and os.path.exists(self.path):
            return self.path               # nothing new to merge
        merged = events
        if os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    prior = json.load(f).get("traceEvents", [])
                merged = prior + events
            except (json.JSONDecodeError, OSError):
                merged = events           # unreadable prior file: rewrite
        merged.sort(key=lambda e: e["ts"])
        tmp = self.path + ".tmp"
        dirname = os.path.dirname(self.path)
        if dirname:
            os.makedirs(dirname, exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"traceEvents": merged, "displayTimeUnit": "ms"}, f)
        os.replace(tmp, self.path)
        return self.path


class _NullTracer:
    """Disabled tracer (shared singleton): a span is still an event in
    whatever profiler session is running; nothing else is recorded."""

    enabled = False
    path = None

    def begin(self, *a, **kw):
        pass

    def end(self, *a, **kw):
        pass

    def span(self, name, *a, **kw):
        return TraceAnnotation(ANNOTATION_PREFIX + name)

    def instant(self, *a, **kw):
        pass

    def current_corr(self):
        return None

    def drain(self):
        return []

    def flush(self):
        return None


NULL_TRACER = _NullTracer()

_ACTIVE_LOCK = threading.Lock()
_ACTIVE = None          # None = unconfigured; NULL_TRACER-or-SpanTracer after
_ATEXIT_INSTALLED = False


def configure_tracer(path: Optional[str] = None):
    """Arm (or return) the process-wide tracer.  ``DS_TRACE`` wins over
    the explicit path (the repo's env-overrides-config convention); with
    neither set, an already-armed tracer stays armed and otherwise the
    null tracer is installed."""
    global _ACTIVE, _ATEXIT_INSTALLED
    effective = os.environ.get(TRACE_ENV, "").strip() or path
    with _ACTIVE_LOCK:
        if not effective:
            if _ACTIVE is None:
                _ACTIVE = NULL_TRACER
            return _ACTIVE
        if isinstance(_ACTIVE, SpanTracer) and _ACTIVE.path == effective:
            return _ACTIVE
        _ACTIVE = SpanTracer(effective)
        if not _ATEXIT_INSTALLED:
            # flush whatever tracer is active when the process exits —
            # a preemption drain's final events must land on disk
            atexit.register(lambda: get_tracer().flush())
            _ATEXIT_INSTALLED = True
        return _ACTIVE


def reset_tracer():
    """Disarm (tests): subsequent get_tracer() is the null tracer unless
    DS_TRACE re-arms it."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = NULL_TRACER


def get_tracer():
    """The active tracer; auto-configures from DS_TRACE on first use."""
    if _ACTIVE is None:
        return configure_tracer()
    return _ACTIVE


# ===================================================== the compiled step
#: Fixed ``jax.named_scope`` names inside the compiled train step — part
#: of the program's interface: readers of a device trace key on them.
SCOPE_FWD_BWD = "ds.fwd_bwd"        # the value_and_grad call of a micro-step
SCOPE_ACCUMULATE = "ds.accumulate"  # gradient cast + add into the accumulator
SCOPE_OPTIMIZER = "ds.optimizer"    # apply_grads: norm, clip, scaler, update
SCOPE_EMBED = "ds.embed"            # model: token + position embedding
SCOPE_BLOCK = "ds.block"            # model: one transformer block ...
SCOPE_ATTN = "attn"                 # ... LN1, QKV, attention, projection
SCOPE_MLP = "mlp"                   # ... LN2, MLP
SCOPE_HEAD_LOSS = "ds.head_loss"    # model: final LN, logits, cross-entropy
# inside ``mlp``, where it is a routed-expert layer (moe/layer.py):
SCOPE_ROUTER = "router"             # router matmul, softmax, top-k, aux terms
SCOPE_DISPATCH = "dispatch"         # rows by (token, choice), sort, scatter
SCOPE_EXPERTS = "experts"           # the grouped GEMMs and the activation
SCOPE_COMBINE = "combine"           # gather back, gate-weighted sum over k
SCOPE_SHARED_EXPERT = "shared_expert"  # the expert every token passes through
# inside ``ds.block``, where the mixer is a linear-attention layer
# (models/qwen3_next.py; ``attn`` stays the full-attention layer's):
SCOPE_LINEAR_ATTN = "linear_attn"   # LN1 and all of the below
SCOPE_IN_PROJ = "in_proj"           # ... q, k, v, z, decay and write strength
SCOPE_CONV = "conv"                 # ... the short causal convolution + silu
SCOPE_DELTA_RULE = "delta_rule"     # ... l2-norm, the gated delta rule
SCOPE_GATE_NORM = "gate_norm"       # ... per-head RMSNorm, silu(z) gate
SCOPE_OUT_PROJ = "out_proj"         # ... output projection + residual
# inside ``ds.block``, where the mixer is a state-space layer
# (models/nemotron_h.py), over ``in_proj`` / ``conv`` / ``gate_norm`` /
# ``out_proj`` as above:
SCOPE_SSM = "ssm"                   # the norm and all of the below
SCOPE_SCAN = "scan"                 # ... the state-space scan (SSD)
STEP_SCOPES = (SCOPE_FWD_BWD, SCOPE_ACCUMULATE, SCOPE_OPTIMIZER,
               SCOPE_EMBED, SCOPE_BLOCK, SCOPE_ATTN, SCOPE_MLP,
               SCOPE_HEAD_LOSS, SCOPE_ROUTER, SCOPE_DISPATCH, SCOPE_EXPERTS,
               SCOPE_COMBINE, SCOPE_SHARED_EXPERT, SCOPE_LINEAR_ATTN,
               SCOPE_IN_PROJ, SCOPE_CONV, SCOPE_DELTA_RULE, SCOPE_GATE_NORM,
               SCOPE_OUT_PROJ, SCOPE_SSM, SCOPE_SCAN)
#: ``name=`` of each ``pl.pallas_call`` of the training path: the flash
#: kernel's three, and the grouped GEMM's forward, dx (the forward kernel
#: on a transposed right-hand side) and dw, the gated delta rule's two and
#: the state-space scan's two
KERNEL_NAMES = ("ds_flash_fwd", "ds_flash_bwd_dkv", "ds_flash_bwd_dq",
                "ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw",
                "ds_gdr_fwd", "ds_gdr_bwd", "ds_ssd_fwd", "ds_ssd_bwd")
PHASES = ("forward", "recompute", "backward", "optimizer", "accumulate",
          "other")
#: the name the engine registers its fused train step under (the cost
#: model's table uses the same)
TRAIN_STEP_PROGRAM = "train/step"

_DS_SCOPE = re.compile(r"(?:^|[/(])(ds\.[a-z_]+)")
# ``.../experts/ds_ggemm_fwd/pallas_call``; where the kernel's name is the
# outermost scope under a transform, ``transpose(jvp(ds_ggemm_dx))/...``
_KERNEL = re.compile(r"[/(]([^/()]+)\)*/pallas_call$")


def phase_of(op_name: Optional[str]) -> str:
    """Which phase of the step an instruction belongs to, from its own
    ``op_name`` (``jit(train_step)/ds.fwd_bwd/transpose(jvp(ds.embed))/
    while/body/checkpoint/rematted_computation/ds.block/attn/dot_general``).
    ``ds.optimizer`` and ``ds.accumulate`` name their phase; under any
    other ``ds.*`` scope jax's own transform names decide:
    ``rematted_computation`` is the recompute (the second forward of a
    ``jax.checkpoint``, with the residuals the backward needs),
    ``transpose(`` the backward, all else (``jvp(`` or bare) the forward.
    An instruction under no ``ds.*`` scope is ``other``."""
    scopes = _DS_SCOPE.findall(op_name or "")
    if not scopes:
        return "other"
    if SCOPE_OPTIMIZER in scopes:
        return "optimizer"
    if SCOPE_ACCUMULATE in scopes:
        return "accumulate"
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return "forward"


# -- the executable's text -> {instruction name: scope, phase, ...}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,)}]+)")
_ARRAY = re.compile(r"\b(pred|[a-z]+(\d+)[a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([0-9,]*)\}")
#: HLO opcode (less ``-start`` / ``-done``) -> the cost model's family
_COLLECTIVE_OPS = {"all-gather": "all_gather", "all-reduce": "all_reduce",
                   "reduce-scatter": "reduce_scatter",
                   "all-to-all": "all_to_all",
                   "collective-permute": "ppermute"}


def _split_shape(rest: str):
    """``<shape> <opcode>(operands...), attrs`` -> (shape, opcode, tail)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, tail = rest[:i + 1], rest[i + 2:]
    else:
        shape, _, tail = rest.partition(" ")
    return shape, tail.partition("(")[0], tail


def _shape_bytes(shape: str) -> int:
    """Bytes of every array in an HLO shape (a tuple's elements summed;
    tiling and memory-space annotations carry no ``[...]`` and are
    skipped).  An element type's width is the first number in its name
    (``bf16``, ``s32``, ``f8e4m3fn``, ``c64``); ``pred`` and anything
    under a byte count one."""
    total = 0
    for _, bits, dims in _ARRAY.findall(shape):
        size = max(int(bits or 8) // 8, 1)
        for d in dims.split(","):
            size *= int(d) if d else 1
        total += size
    return total


def _group_size(tail: str) -> Optional[int]:
    m = _GROUPS_IOTA.search(tail)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST.search(tail)
    if m:
        return len([x for x in m.group(1).split(",") if x])
    return None


def parse_program_text(text: str) -> Dict[str, Dict[str, Any]]:
    """The step-program map of one executable's HLO text
    (``compiled.as_text()``).  Pure: text in, table out.

    Every instruction of every computation that is not a fused
    computation's body gets a row ``{"scope", "phase", "kernel",
    "collective", "wire_bytes"}``.  A fusion carries the ``op_name`` XLA
    gave it, which is its root's.  ``collective`` is the HLO opcode less
    ``-start``/``-done`` (``all-gather``, ``all-reduce`` ...), also for a
    fusion that wraps one (TPU: ``%async-collective-start/done``, a
    ``calls=%all-reduce-scatter`` fusion reads ``reduce-scatter``).
    ``wire_bytes`` = payload x ``costmodel.ring_wire_factor``: the payload
    is the result shape's bytes (the full tensor for an all-gather or an
    all-reduce; a reduce-scatter's result is one shard, so x group size,
    the cost model's convention), the group size is read from
    ``replica_groups``; a ``-start`` half carries ``None`` so that a
    start/done pair counts once."""
    from deepspeed_tpu.telemetry.costmodel import ring_wire_factor
    rows, body_collective = [], {}
    computation = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        shape, opcode, tail = _split_shape(rest)
        base = opcode
        for suffix in ("-start", "-done"):
            if base.endswith(suffix):
                base = base[:-len(suffix)]
        if base in _COLLECTIVE_OPS:
            # the first collective of a computation names what a fusion
            # calling it wraps; its replica_groups are that fusion's
            body_collective.setdefault(computation,
                                       (base, _group_size(tail)))
        rows.append((name, computation, shape, opcode, base, tail))

    fused = set()
    for _, _, _, opcode, _, tail in rows:
        if opcode == "fusion":
            fused.update(_CALLS.findall(tail))

    table = {}
    for name, computation, shape, opcode, base, tail in rows:
        if computation in fused:
            continue
        op_name = _OP_NAME.search(tail)
        scope = op_name.group(1) if op_name else None
        kernel = collective = wire = None
        half = opcode[len(base):]           # "", "-start" or "-done"
        if 'custom_call_target="tpu_custom_call"' in tail:
            k = _KERNEL.search(scope or "")
            kernel = k.group(1) if k else "pallas_call"
        if base in _COLLECTIVE_OPS:
            collective, group = base, _group_size(tail)
        elif opcode == "fusion":
            # TPU: a fusion may wrap a collective.  Only the two kinds
            # whose *name* says so count as one — a compute fusion that an
            # asynchronous gather runs under calls a computation holding
            # that gather too, and is compute.
            called = _CALLS.search(tail)
            called = called.group(1) if called else ""
            inner = body_collective.get(called, (None, None))
            if called.startswith("all-reduce-scatter"):
                collective, group = "reduce-scatter", inner[1]
            elif name.startswith("async-collective-") and inner[0]:
                collective, group = inner
                half = "-start" if "-start" in name else "-done"
        if collective is not None and half != "-start":
            payload = _shape_bytes(shape)
            if collective == "reduce-scatter" and group:
                payload *= group
            wire = int(round(payload * ring_wire_factor(
                _COLLECTIVE_OPS[collective], group)))
        table[name] = {"scope": scope, "phase": phase_of(scope),
                       "kernel": kernel, "collective": collective,
                       "wire_bytes": wire}
    return table


# -- process-wide table by program name, beside costmodel.get_report
_PROGRAM_LOCK = threading.Lock()
_PROGRAM_THUNKS: Dict[str, Callable[[], Optional[str]]] = {}
_PROGRAM_MAPS: Dict[str, Dict[str, Dict[str, Any]]] = {}


def register_program(name: str, text_thunk: Callable[[], Optional[str]]):
    """Publish a program under ``name``.  ``text_thunk()`` returns the
    HLO text of the executable that runs (or None if it can no longer be
    had); it is NOT called here — only by the first
    :func:`get_program_map` that asks."""
    with _PROGRAM_LOCK:
        _PROGRAM_THUNKS[name] = text_thunk
        _PROGRAM_MAPS.pop(name, None)


def get_program_map(name: str = TRAIN_STEP_PROGRAM):
    """``{instruction name: {"scope", "phase", "kernel", "collective",
    "wire_bytes"}}`` of the program registered under ``name`` (see
    :func:`parse_program_text`), or None if none is.  The first call
    pays for the executable's text (with the persistent compile cache a
    load, ~2 s for a 760M step) and the parse; later calls return the
    same table."""
    with _PROGRAM_LOCK:
        if name in _PROGRAM_MAPS:
            return _PROGRAM_MAPS[name]
        thunk = _PROGRAM_THUNKS.get(name)
    if thunk is None:
        return None
    text = thunk()
    if text is None:
        return None
    table = parse_program_text(text)
    with _PROGRAM_LOCK:
        if _PROGRAM_THUNKS.get(name) is thunk:
            _PROGRAM_MAPS[name] = table
    return table


#: an instruction inside a layer loop of the step: a ``while`` body below
#: the micro-step's ``ds.fwd_bwd`` (the scan over layers, or its transpose)
_IN_LAYER_LOOP = re.compile(r"ds\.fwd_bwd/.*\bwhile/body\b")


def in_layer_loop(row: Dict[str, Any]) -> bool:
    return bool(_IN_LAYER_LOOP.search(row["scope"] or ""))


def layer_loop_gathers(name: str = TRAIN_STEP_PROGRAM):
    """Whether ZeRO-3 gathers one layer at a time, from the program's own
    map: ``{"rows", "max_wire_bytes", "wire_bytes_per_iteration",
    "by_phase"}`` of the all-gathers inside the layer loops — how many
    instructions, the largest one's bytes on the wire and their sum over
    one iteration of each loop (a step executes it once per layer;
    ``by_phase`` splits rows and bytes by the map's phase).  None where no
    program is registered.  On request only: it pays for the map
    (:func:`get_program_map`) if nobody has yet."""
    table = get_program_map(name)
    if table is None:
        return None
    hit = [row for row in table.values()
           if row["collective"] == "all-gather"
           and row["wire_bytes"] is not None and in_layer_loop(row)]
    by_phase: Dict[str, Dict[str, int]] = {}
    for row in hit:
        acc = by_phase.setdefault(row["phase"], {"rows": 0, "wire_bytes": 0})
        acc["rows"] += 1
        acc["wire_bytes"] += row["wire_bytes"]
    return {"rows": len(hit),
            "max_wire_bytes": max((r["wire_bytes"] for r in hit), default=0),
            "wire_bytes_per_iteration": sum(r["wire_bytes"] for r in hit),
            "by_phase": by_phase}


# -- counts the step states about itself while it is traced
_STEP_COUNTERS: Dict[str, Dict[str, int]] = {}
_ACCOUNT_OPEN: Optional[str] = None


@contextmanager
def step_account(name: str = TRAIN_STEP_PROGRAM):
    """Entered by the engine in the traced body of its step: whatever the
    model code below calls :func:`count_in_step` with while this trace
    runs is the account of the program ``name``.  Every trace starts it
    anew, so it describes the step as last traced."""
    global _ACCOUNT_OPEN
    outer, _ACCOUNT_OPEN = _ACCOUNT_OPEN, name
    _STEP_COUNTERS[name] = {}
    try:
        yield
    finally:
        _ACCOUNT_OPEN = outer


def count_in_step(**counters):
    """Trace-time, static values only (shapes): no host callback, nothing
    in the compiled step.  A number replaces what its name held; a dict
    is merged into the dict kept under its name (one entry per key,
    however often the code that counts is traced).  A no-op outside
    :func:`step_account`."""
    if _ACCOUNT_OPEN is None:
        return
    account = _STEP_COUNTERS[_ACCOUNT_OPEN]
    for name, value in counters.items():
        if isinstance(value, dict):
            account.setdefault(name, {}).update(value)
        else:
            account[name] = int(value)


def grouped_gemm_rows(name: str = TRAIN_STEP_PROGRAM):
    """What one grouped GEMM call of the step computes, beside
    :func:`layer_loop_gathers`: ``{"routed_rows_per_call",
    "padded_rows_per_call"}``, shapes that moe/layer.py wrote when the
    plan was traced (every grouped call of a step has the same: R =
    tokens x top_k routed rows inside ``round_up(R, bm) + E*bm`` padded
    ones, the rest zeros).  Where the step's grouped calls ran as Pallas
    kernels, also ``"calls"``: one row per kernel and weight shape, as
    ops/pallas/grouped_gemm.py tiled it — ``kernel``, ``k``, ``n``,
    ``blocks`` (bk, bn), ``regime`` ("resident": the expert's weight panel
    stays in VMEM across its M-tiles | "streamed": every M-tile fetches
    it again) and the bytes one call moves as tiled, at most:
    ``weight_bytes_per_call`` and ``operand_bytes_per_call`` (the rows in
    and out).  None where no step with a grouped dispatch was traced."""
    account = _STEP_COUNTERS.get(name, {})
    if "grouped_padded_rows" not in account:
        return None
    rows = {"routed_rows_per_call": account["grouped_routed_rows"],
            "padded_rows_per_call": account["grouped_padded_rows"]}
    # an expert layer that holds a subset (MoEConfig.experts_held): the
    # routed rows are then the EXPECTED held ones under even routing, and
    # the plan's static bound on them stands beside
    for key in ("held_rows_bound", "experts_held", "experts_routed"):
        if key in account:
            rows[key] = account[key]
    if "grouped_calls" in account:
        rows["calls"] = [account["grouped_calls"][key]
                         for key in sorted(account["grouped_calls"])]
    return rows


def delta_rule_chunks(name: str = TRAIN_STEP_PROGRAM):
    """The gated-delta-rule calls of the step as ops/linear_attention.py
    traced them: one row per shape — ``chunks`` and ``chunk_len`` of the
    scan, ``batch``, ``heads``, ``dk``, ``dv`` and ``path``: ``"kernel"``
    where the call ran as the Mosaic kernels ``ds_gdr_fwd`` /
    ``ds_gdr_bwd`` (then also ``heads_per_step`` and ``chunks_per_step``,
    the value heads and chunks one grid step takes), ``"xla"`` where it
    fell back to the XLA chunked form.  None where the step has no such
    call."""
    return _account_rows(name, "delta_rule_calls")


def _account_rows(name: str, counter: str):
    """The rows a dict counter of the step's account holds, by key."""
    calls = _STEP_COUNTERS.get(name, {}).get(counter)
    return [calls[key] for key in sorted(calls)] if calls else None


def ssd_chunks(name: str = TRAIN_STEP_PROGRAM):
    """The state-space scans of the step as ops/state_space.py traced
    them: one row per shape — ``chunks`` and ``chunk_len`` of the scan,
    ``batch``, ``heads``, ``groups``, ``head_dim``, ``state`` and ``path``:
    ``"kernel"`` where the call ran as the Mosaic kernels ``ds_ssd_fwd`` /
    ``ds_ssd_bwd`` (then also ``heads_per_step`` and ``chunks_per_step``,
    the heads — one group's — and chunks one grid step takes), ``"xla"``
    where it fell back to the chunked form as XLA einsums around a
    ``lax.scan``.  None where the step has no such call."""
    return _account_rows(name, "ssd_calls")


def reset_programs():
    """Tests: forget every registered program."""
    with _PROGRAM_LOCK:
        _PROGRAM_THUNKS.clear()
        _PROGRAM_MAPS.clear()
    _STEP_COUNTERS.clear()
