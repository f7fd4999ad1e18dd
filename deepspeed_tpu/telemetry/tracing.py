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
its own parts: ``jax.named_scope``s with the fixed names of the
``SCOPE_*`` constants below and Pallas kernels with a ``ds_*`` ``name=``
of their own.  Scopes are HLO metadata only — no runtime cost,
no change to what XLA fuses.  A device trace does not carry that
metadata (an ``XLA Ops`` event is the instruction's text without
``metadata={...}``), but it does carry the **instruction name**, and so
does the executable's own text.  :func:`get_program_map` publishes the
table between the two: instruction name -> its scope path, phase
(:func:`phase_of`), kernel name, collective kind and wire bytes.  It is
built **lazily**: the engine registers a thunk on the first fused
dispatch, and the text of the executable is fetched and parsed only
when someone first asks.

**Before the first step** the program keeps an account of its own start
(:func:`setup_account`), armed or not: the spans it opens at its own
boundaries (:data:`SETUP_SPANS`, through :func:`setup_span` and so
through the tracer above) and one row for every trace, lowering and
backend compile that jax reports through ``jax.monitoring`` — by
program, by stage (``trace`` / ``lower`` / ``compile`` /
``cache_load``), by the span that caused it, on the tracer's clock.  The
listeners run only when jax traces, lowers or compiles: a steady step
calls none.
"""
import atexit
import itertools
import json
import os
import re
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

from deepspeed_tpu.telemetry.registry import get_registry
from deepspeed_tpu.utils.logging import logger

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

    def interval(self, name: str, start: float, end: float, cat: str = "",
                 args: Optional[Dict] = None):
        """A span that has already ended, from ``start`` to ``end`` on
        this tracer's clock (``time.perf_counter()`` seconds): one
        ``B``/``E`` pair on the calling thread.  For work that reports
        itself when it is over (jax's compile events); it must lie inside
        whatever span is open on this thread, as work done there does."""
        corr = self.current_corr()
        for ph, t in (("B", start), ("E", end)):
            ev = self._event(ph, name, cat, corr, args if ph == "B" else None)
            ev["ts"] = max((t - self._t0) * 1e6, 0.0)
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

    def interval(self, *a, **kw):
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
# beside those four, where the experts are spread over the ``expert`` mesh
# axis (moe/layer.py ``_exchanged_grouped_moe``): the all-to-alls, and
# nothing else — beneath ``exchange``, ``exchange_send`` (rows and their
# experts out, between ``dispatch``'s two halves) and ``exchange_return``
# (the experts' rows back, inside ``combine``'s)
SCOPE_EXCHANGE = "exchange"
SCOPE_SEND = "exchange_send"
SCOPE_RETURN = "exchange_return"
# inside ``ds.block``, where the mixer is a linear-attention layer
# (models/qwen3_next.py; ``attn`` stays the full-attention layer's):
SCOPE_LINEAR_ATTN = "linear_attn"   # LN1 and all of the below
SCOPE_IN_PROJ = "in_proj"           # ... q, k, v, z, decay and write strength
SCOPE_CONV = "conv"                 # ... the short causal convolution + silu
SCOPE_DELTA_RULE = "delta_rule"     # ... l2-norm, the gated delta rule
SCOPE_GATE_NORM = "gate_norm"       # ... per-head RMSNorm, silu(z) gate
SCOPE_OUT_PROJ = "out_proj"         # ... output projection + residual
# ... and, where the decay (a vector a head) and the output's gate each
# come through a low-rank pair of matrices (models/kimi_linear.py): both
SCOPE_LOW_RANK_GATE = "low_rank_gate"
# inside ``ds.block``, where the mixer is a state-space layer
# (models/nemotron_h.py), over ``in_proj`` / ``conv`` / ``gate_norm`` /
# ``out_proj`` as above:
SCOPE_SSM = "ssm"                   # the norm and all of the below
SCOPE_SCAN = "scan"                 # ... the state-space scan (SSD)
# inside ``attn``, where the attention is latent (models/joyai.py), beside
# ``out_proj`` as above:
SCOPE_Q_LATENT = "q_latent"         # ... queries: down, norm, up, the join
SCOPE_KV_LATENT = "kv_latent"       # ... keys/values: down, norm, and two
#     products up: k whole (the shared rotary key in every head's last
#     lanes) and v, each as the kernels read it
SCOPE_ROPE = "rope"                 # ... rotary on q's part and the shared key
SCOPE_SCORES = "scores"             # ... softmax(q k^T) v: the flash kernels
# beside ``ds.block``, around a whole multi-token-prediction module
# (models/joyai.py): its embedding, its projection, its block (whose
# scopes are ``ds.block``'s, beneath this one), its norm, head and loss
SCOPE_MTP = "ds.mtp"
# around one attention sublayer, where a stack has two kinds of them with
# different head counts (models/laguna.py): ``attn`` and its parts lie
# beneath, so that a reader can tell a windowed layer's time from a full
# one's; ``ds.head_gate`` is the per-head sigmoid gate on the attention's
# output, ``ds.lead_mlp`` the leading layer's dense feed-forward
SCOPE_ATTN_FULL = "ds.attn_full"
SCOPE_ATTN_SLIDING = "ds.attn_sliding"
SCOPE_HEAD_GATE = "ds.head_gate"
SCOPE_LEAD_MLP = "ds.lead_mlp"
# inside ``ds.block``, where the mixer is a Mamba-1 layer, a gated memory
# unit or differential attention (models/phi4flash.py).  ``mamba`` holds
# the norm, ``in_proj``, ``conv``, ``scan`` (the selective scan), ``gate``
# and ``out_proj``; ``gmu`` the whole unit; ``diff_attn`` the norm,
# ``qkv``, ``flash`` (the two maps' flash calls), ``combine`` (the lambda
# combine and its norm) and ``out_proj`` — the windowed, the full and the
# cross layers alike: the flash calls' account tells them apart
SCOPE_MAMBA = "mamba"
SCOPE_GATE = "gate"
SCOPE_GMU = "gmu"
SCOPE_DIFF_ATTN = "diff_attn"
SCOPE_QKV = "qkv"
SCOPE_FLASH = "flash"
# inside ``attn`` and ``mlp``, where the residual is several streams
# mixed by hyper-connections (ops/hyper_connection.py, models/xing.py):
# ``hc/coeff`` the flattened norm, the projection, the sigmoids and the
# Sinkhorn sweeps; ``hc/read`` the streams summed into the sublayer's
# input; ``hc/write`` the streams mixed and the sublayer's output added
SCOPE_HC = "hc"
SCOPE_HC_COEFF = "coeff"
SCOPE_HC_READ = "read"
SCOPE_HC_WRITE = "write"
# inside ``ds.block``, where the mixer is attention over the key blocks
# each query picks for itself, or Lightning linear attention
# (models/minicpm_sala.py).  ``sparse_attn`` holds the norm, ``qkv`` (the
# three products and the norms a head of q and k), ``select`` (pooled
# keys, scores, the max-pool to blocks and the top-k: no gradient),
# ``attend`` (softmax attention over the kept blocks' keys) and
# ``out_proj`` (the output gate, the product, the residual);
# ``lightning`` holds ``in_proj``, ``rope``, ``scan`` (the recurrence:
# ops/state_space.py ``lightning_attention``), ``gate_norm`` and
# ``out_proj``
SCOPE_SPARSE_ATTN = "sparse_attn"
SCOPE_SELECT = "select"
SCOPE_ATTEND = "attend"
SCOPE_LIGHTNING = "lightning"
# beside ``ds.head_loss``, where a stack of layers runs several times and a
# token may leave after any pass (models/ouro.py): the exit gate's product
# on every pass's state, the distribution over the pass a token leaves
# after, its entropy and the masses the step reports; the passes' heads
# stay under ``ds.head_loss``
SCOPE_EXIT_GATE = "ds.exit_gate"
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
_OPERAND = re.compile(r"\(\s*(?:[a-z0-9]+\[[0-9,]*\]\S* )?%([^\s,)]+)")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([0-9,]*)\}")
#: HLO opcode (less ``-start`` / ``-done``) -> the cost model's family
_COLLECTIVE_OPS = {"all-gather": "all_gather", "all-reduce": "all_reduce",
                   "reduce-scatter": "reduce_scatter",
                   "all-to-all": "all_to_all",
                   "ragged-all-to-all": "all_to_all",
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

    shapes = {(computation, name): shape
              for name, computation, shape, *_ in rows}
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
            if collective == "ragged-all-to-all":
                # the result is a buffer sized by a bound; the rows there
                # are to send are the operand's (whichever is less: the
                # way back sends a buffer's live rows into their places)
                sent = _OPERAND.search(tail)
                sent = shapes.get((computation, sent.group(1))) \
                    if sent else None
                if sent:
                    payload = min(payload, _shape_bytes(sent))
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
#: held while a thunk runs (a load of an executable: seconds), so two
#: askers at once make one load and a registrant's thunks may share what
#: they keep; never held with _PROGRAM_LOCK wanted by a peek
_PROGRAM_ASK_LOCK = threading.RLock()
#: name -> {"text": thunk, "memory" / "cost" / "load": thunk or None}
_PROGRAM_THUNKS: Dict[str, Dict[str, Optional[Callable[[], Any]]]] = {}
#: name -> what the thunks gave when first asked, by the same keys (the
#: text as its parsed map)
_PROGRAM_FACTS: Dict[str, Dict[str, Any]] = {}


def register_program(name: str, text_thunk: Callable[[], Optional[str]],
                     memory_thunk: Optional[Callable[[], Any]] = None,
                     cost_thunk: Optional[Callable[[], Any]] = None,
                     load_thunk: Optional[Callable[[], Any]] = None):
    """Publish a program under ``name``.  ``text_thunk()`` returns the
    HLO text of the executable that runs, ``memory_thunk()`` what is
    counted of its bytes per device (telemetry/memory.py
    ``step_memory`` says which keys), ``cost_thunk()`` its
    telemetry/costmodel.py ``CostReport``, ``load_thunk()`` the load of
    the steps it has run (:func:`step_load`); each returns None if it can
    no longer be had.  None is called here — only by the first
    :func:`get_program_map` / :func:`get_program_memory` /
    :func:`get_program_cost` that asks, and by every :func:`step_load`."""
    with _PROGRAM_LOCK:
        _PROGRAM_THUNKS[name] = {"text": text_thunk, "memory": memory_thunk,
                                 "cost": cost_thunk, "load": load_thunk}
        _PROGRAM_FACTS.pop(name, None)


def _program_fact(name: str, kind: str, reduce=lambda raw: raw,
                  create: bool = True):
    """``reduce`` of what the ``kind`` thunk of ``name`` returns, asked
    once and kept; None where there is no such program or thunk, or the
    thunk has nothing to give any more.  ``create=False`` peeks: what
    an earlier asker was given — no thunk is called and no lock taken
    (a debug reader beside a wedged writer)."""
    if not create:
        return _PROGRAM_FACTS.get(name, {}).get(kind)
    with _PROGRAM_ASK_LOCK:
        with _PROGRAM_LOCK:
            facts = _PROGRAM_FACTS.get(name, {})
            if kind in facts:
                return facts[kind]
            thunks = _PROGRAM_THUNKS.get(name)
        thunk = thunks and thunks[kind]
        if thunk is None:
            return None
        raw = thunk()
        if raw is None:
            return None
        fact = reduce(raw)
        with _PROGRAM_LOCK:
            if _PROGRAM_THUNKS.get(name) is thunks:
                _PROGRAM_FACTS.setdefault(name, {})[kind] = fact
        return fact


def get_program_map(name: str = TRAIN_STEP_PROGRAM):
    """``{instruction name: {"scope", "phase", "kernel", "collective",
    "wire_bytes"}}`` of the program registered under ``name`` (see
    :func:`parse_program_text`), or None if none is.  The first call
    pays for the executable's text (with the persistent compile cache a
    load, ~2 s for a 760M step) and the parse; later calls return the
    same table."""
    return _program_fact(name, "text", parse_program_text)


def get_program_text(name: str = TRAIN_STEP_PROGRAM) -> Optional[str]:
    """The HLO text of the program registered under ``name`` as its
    ``text_thunk`` gives it now (a load of the executable every call:
    only the parsed map is kept), or None.  For a script that wants the
    text itself; :func:`get_program_map` is what the readers use."""
    with _PROGRAM_LOCK:
        thunks = _PROGRAM_THUNKS.get(name)
    with _PROGRAM_ASK_LOCK:
        return thunks["text"]() if thunks else None


def get_program_memory(name: str = TRAIN_STEP_PROGRAM, create: bool = True):
    """What the program registered under ``name`` counts of its bytes
    per device, as its ``memory_thunk`` gave it when first asked (the
    engine's: telemetry/memory.py ``step_memory`` reads it), or None.
    ``create=False``: only what an earlier asker already made — a load
    of the step's executable is not a reader's to start."""
    return _program_fact(name, "memory", create=create)


def get_program_cost(name: str = TRAIN_STEP_PROGRAM, create: bool = True):
    """The ``CostReport`` of the program registered under ``name``, as
    its ``cost_thunk`` made it when first asked (the engine's: one more
    trace of the step from shapes alone and a walk of its jaxpr, handed
    to telemetry/roofline.py ``publish_report`` — the ``perf/*`` gauges
    and ``costmodel.get_report`` have it from then on), or None.
    ``create=False``: only what an earlier asker already made."""
    return _program_fact(name, "cost", create=create)


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


def rows_in_step(key: str) -> list:
    """The rows :func:`count_in_step` has merged under ``key`` so far into
    the account being written (``[]`` outside :func:`step_account`): for
    code of the step that is traced after them and counts by them."""
    if _ACCOUNT_OPEN is None:
        return []
    return list(_STEP_COUNTERS[_ACCOUNT_OPEN].get(key, {}).values())


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


def step_load(name: str = TRAIN_STEP_PROGRAM):
    """Beside :func:`grouped_gemm_rows` and :func:`exchange_calls`, which
    are shapes: what the router did to the steps the program ``name`` has
    run, as its registrant has it NOW (the engine's ``step_load()``:
    ``{"steps", "totals", "last"}`` of the ``moe/*`` sums — and of a
    looped model's ``ouro/*`` exit masses — that leave the step beside
    its loss: data, no callback; it waits for the steps in flight).  None where no such program is registered, or it is gone."""
    with _PROGRAM_LOCK:
        thunk = _PROGRAM_THUNKS.get(name, {}).get("load")
    return thunk() if thunk else None


def delta_rule_chunks(name: str = TRAIN_STEP_PROGRAM):
    """The gated-delta-rule calls of the step as ops/linear_attention.py
    traced them: one row per shape — ``chunks`` and ``chunk_len`` of the
    scan, ``batch``, ``heads``, ``dk``, ``dv``, ``decay`` (``"head"`` or
    ``"channel"``) and ``path``: ``"kernel"`` where the call ran as the
    Mosaic kernels ``ds_gdr_fwd`` / ``ds_gdr_bwd`` (a decay a channel:
    ``ds_kda_fwd`` / ``ds_kda_bwd``; then also ``heads_per_step`` and ``chunks_per_step``,
    the value heads and chunks one grid step takes), ``"xla"`` where it
    fell back to the XLA chunked form.  None where the step has no such
    call."""
    return _account_rows(name, "delta_rule_calls")


def held_row_sums(name: str = TRAIN_STEP_PROGRAM):
    """The sums of a held plan's rows into their tokens as
    ops/pallas/grouped_gemm.py traced them (``combine_held_rows`` forward,
    ``dispatch_held_rows`` backward): one row per shape — ``tokens``,
    ``width``, ``plan_rows`` and ``path``: ``"kernel"`` where the sum ran
    as the Mosaic kernel ``ds_rowsum`` (then also ``blocks``: the tokens a
    grid step takes and the rows its stage holds), ``"xla"`` where it fell
    back to one scatter-add over the plan (off the chip, or on more than
    one device).  None where the step has no such sum."""
    return _account_rows(name, "held_row_sums")


def exchange_calls(name: str = TRAIN_STEP_PROGRAM):
    """The expert-parallel exchanges of the step as moe/layer.py traced
    them: one row per shape — ``pairs`` (chips of the ``expert`` axis),
    ``experts_held`` a chip, ``tokens`` and ``routed_rows`` of one chip
    (its (token, expert) rows), ``receive_rows`` (the (token, expert) rows
    its plan has room for from all chips together: the held plan's bound,
    a row past which is counted in ``moe/rows_over_bound``), ``width``,
    ``row_unit`` (``"token_chip"``: what a row on the wire is — a token's
    row crosses to a chip once, whatever number of that chip's experts it
    chose), ``landed_rows`` (the landing buffer: ``pairs * tokens``, a
    slot of ``tokens`` a sender), ``wire_rows_bound`` (``(pairs - 1) *
    tokens``: the most one all-to-all of rows can put on a chip's links,
    whatever the routing) and ``wire_bytes`` (that many rows' bytes),
    ``even_rows_per_pair`` (the (token, expert) rows even routing routes
    from one chip to another), ``path``, the collective the rows were
    traced to travel by (``moe/mappings.py exchange_path``:
    ``"ragged_all_to_all"`` on a TPU — the rows there are, no padding —
    and ``"all_to_all"`` of whole buffers where the backend has no ragged
    one), ``slices_per_pair`` (the slices of the narrow all-to-all that go
    from one chip to another: one an expert the receiver holds; the rows'
    has one a pair), ``receive_layout`` (``"grouped"``: a slice of the
    lanes lands inside its expert's group of the receiver's plan, and the
    rows are gathered into the same groups from where they landed),
    ``receive_fill`` / ``zeroed_rows_per_call`` (what a narrow call writes
    of its plan-sized buffer before its rows arrive; the rows' landing
    buffer is not written at all), and what a layer and micro-batch runs
    of them by phase where the layer is rematerialised:
    ``row_calls_per_pass`` (``{"forward": 2, "recompute": 1, "backward":
    2}`` — all-to-alls of ``width``-wide rows; the recompute has no return:
    a row is weighted by its gate on its expert's chip, so no row that
    came back is a residual) and ``gate_calls_per_pass`` (one a phase: the
    lanes out beside the rows — ``[rows, 128]`` float32, a gate and a
    landed place a (token, expert) — and their cotangent home).
    Statements of the layer's code, held to the compiled text by
    tests/test_chip_compile.py and tests/test_moe_exchange.py — not counts
    read off an executable.  None where the step has no exchange."""
    return _account_rows(name, "exchange_calls")


def optimizer_fused(name: str = TRAIN_STEP_PROGRAM):
    """Which parameter leaves the step's AdamW updates in one isolated
    pass, as runtime/bf16_optimizer.py ``update_in_place`` traced it:
    ``{"leaves", "param_bytes"}`` of those taken behind an
    ``optimization_barrier`` (a stacked leaf of three or more axes) and
    ``{"xla_leaves", "xla_param_bytes"}`` of those left for XLA to fuse
    with what makes their gradient (matrices, vectors, the embedding
    table).  Bytes are the parameters' own, over all devices.  None where
    the step took the optax entry (a composed transform, fp16, another
    optimizer)."""
    return _STEP_COUNTERS.get(name, {}).get("optimizer_fused")


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
    ``lax.scan`` (with ``why``, ``"chunk 256"``, where the kernels would
    have taken the call at their own chunk of 128).  A row whose ``groups`` equal its ``heads`` is a
    Lightning-attention call (``lightning_attention``: one group a head),
    and its ``chunks`` the count of Lightning chunks.  None where the step
    has no such call."""
    return _account_rows(name, "ssd_calls")


def sparse_attention_calls(name: str = TRAIN_STEP_PROGRAM):
    """The selected-block attention calls of the step as
    ops/sparse_attention.py traced them: one row per shape — ``batch``,
    ``seq_len``, ``heads``, ``kv_heads``, ``head_dim``, the selection's
    seven numbers, ``lowering`` and ``sparse/visited_keys_per_query`` (the
    keys that lowering multiplies a query by, a mean over the sequence's
    queries: static, whatever was selected).  ``lowering`` is
    ``"mosaic_tiles"`` where the call ran as the kernels of
    ops/pallas/selected_attention.py — then also ``blocks`` (the queries
    and keys of a tile), ``tiles`` (the (query tile, key tile) pairs one
    pass of one key/value head visits: every pair with a causal pair in
    it) and ``vmem_limit_bytes`` (what the three calls ask Mosaic for) —
    or ``"masked_chunks"``, every key of a span under a per-(token, block)
    mask as XLA einsums — then ``query_chunk`` and ``key_spans`` (the
    queries scored at a time, and in how many spans of growing key length
    the sequence is walked).  What depends on the
    data — the blocks kept, the keys required, the queries of documents
    under ``dense_len`` — is no shape and is not here:
    ``ops.sparse_attention.selection_counts``.  None where the step has
    no such call."""
    return _account_rows(name, "sparse_attention_calls")


def selective_scan_calls(name: str = TRAIN_STEP_PROGRAM):
    """The selective scans (Mamba-1) of the step as ops/selective_scan.py
    traced them: one row per shape and, where the caller names it, per
    ``layer`` — ``batch``, ``positions``, ``channels``, ``state``,
    ``chunk`` and ``path``: ``"kernel"`` where the call ran as the Mosaic
    kernels ``ds_sscan_fwd`` / ``ds_sscan_bwd`` (then also
    ``channels_per_step`` and ``chunks_per_step``, the channels and chunks
    one grid step takes), ``"xla"`` where it fell back to the chunked form
    (an associative scan a chunk inside a ``lax.scan``).  None where the
    step has no such call."""
    return _account_rows(name, "selective_scan_calls")


def hc_calls(name: str = TRAIN_STEP_PROGRAM):
    """The hyper-connected sublayers of the step as
    ops/hyper_connection.py ``hc_coefficients`` traced them: one row per
    call site — ``site``, ``tokens`` (of a micro-batch), ``streams``,
    ``width`` and ``calls_per_pass``, how often one forward pass of a
    micro-batch runs it (the layer loop's length).  Their sum is the
    sublayer calls of a pass; a step makes it once a micro-batch forward,
    again in the recompute, and once backward.  None where the step has
    no such call."""
    return _account_rows(name, "hc_calls")


def conv_calls(name: str = TRAIN_STEP_PROGRAM):
    """The short causal convolutions of the step as
    ops/linear_attention.py ``causal_conv`` traced them: one row per shape
    and orientation — ``batch``, ``positions``, ``channels``, ``taps``,
    ``orientation`` (which axis of the kernels' slabs holds positions:
    ``"sublanes"`` | ``"lanes"``) and ``path``: ``"kernel"`` where the call
    ran as the Mosaic kernels ``ds_conv_fwd`` / ``ds_conv_bwd`` (then also
    ``slab``, the channels one grid step takes, and ``tile``, the
    positions one step of its inner loop takes), ``"xla"`` where it fell
    back to shifted copies with autodiff's backward.  None where the step
    has no such call."""
    return _account_rows(name, "conv_calls")


def flash_calls(name: str = TRAIN_STEP_PROGRAM):
    """The flash-attention calls of the step as
    ops/pallas/ds_flash_attention.py traced them, every family's: one row
    per shape — ``batch``, ``seq_len``, ``heads``, ``kv_heads``, ``dk``
    (the score head's width: q and k) and ``dv`` (the value head's: v and
    the result), ``packed`` (segment ids or not), ``blocks`` (block_q,
    block_k), ``vmem_limit_bytes``, the limit the three kernels ask for
    (None: what a call is granted unasked), and ``tiles``: the
    ``[interior, boundary]`` score tiles a head's pass visits — wholly
    below the diagonal and inside the window, or crossed by one of them
    (``ds_flash_attention.tile_counts``); a windowed call's row also has
    its ``window``, and a call whose keys and values are another layer's
    has that layer under ``kv_of`` (and a row of its own).  None where the
    step has no such call (the XLA einsum took its place, or there is no
    attention)."""
    return _account_rows(name, "flash_calls")


def layer_loops(name: str = TRAIN_STEP_PROGRAM):
    """The layer loops of the step that run their stack more than once
    with the same weights, as models/ouro.py ``exit_states`` traced them:
    one row per loop — ``passes`` over ``layers`` layers = ``applications``
    a token goes through in one forward pass, ``shared_param_bytes`` (the
    stacked layers' bytes, which every pass reads again and whose gradient
    is the sum of the passes') and ``saved_carry_bytes`` (the carry into
    every application: what a rematerialised backward pass is handed,
    ``applications`` x one micro-batch's hidden state).  None where every
    layer of the step runs once."""
    return _account_rows(name, "layer_loops")


def head_chunks(name: str = TRAIN_STEP_PROGRAM):
    """The head-and-loss calls of the step as models/model.py
    ``head_nll_sum`` traced them: one row per call (``name``: ``"main"``,
    a prediction module's ``"mtp"``, a looped model's ``"exit1"`` ... one
    after each pass) — ``tokens`` of one chip, ``d_model``,
    ``vocab``, the ``chunk`` of tokens whose logits exist at a time
    (``head_chunk_tokens``) and how many ``chunks`` walk the tokens,
    ``whole_logits_bytes`` ([tokens, vocab] float32: what the loss would
    hold at once, and as much again for its gradient),
    ``chunk_logits_bytes`` (what it holds), and ``tied`` (the head is the
    embedding table, contracted on its own axis).  None where the step's
    loss takes whole logits (``token_loss``)."""
    return _account_rows(name, "head_chunks")


def gradient_bytes(name: str = TRAIN_STEP_PROGRAM):
    """Bytes one device holds of the step's summed gradient tree — the
    accumulator ``accumulated_grads`` carries over the micro-batches and
    ``apply_grads`` reads — as runtime/step_programs.py ``as_grads``
    traced it: every leaf in the accumulation dtype, the shard the ZeRO
    policy's layout gives a device.  None where no step with such a
    tree was traced."""
    return _STEP_COUNTERS.get(name, {}).get("gradient_bytes_per_device")


# ==================================================== where a start goes
#: Fixed names of the host spans the program opens at its own boundaries
#: before (and around) its first steps — part of the program's interface,
#: beside the ``SCOPE_*`` names above: readers of the set-up account,
#: of a profiler session (``ds/engine/init`` ...) and of
#: the ``DS_TRACE`` file key on them.
SPAN_ENGINE_INIT = "engine/init"            # DeepSpeedEngine.__init__, and in it
SPAN_INIT_SHARDINGS = "engine/init/shardings"   # ... ZeRO policy, specs
SPAN_INIT_PARAMS = "engine/init/params"     # ... parameters built and placed
SPAN_INIT_OPTIMIZER = "engine/init/optimizer"   # ... optimizer and its state
SPAN_TRAIN_STEP = "train/step"              # one train_batch call, and in it
SPAN_FUSED_STEP = "train/fused_step"        # ... the fused program's call
SPAN_COST_ANALYZE = "costmodel/analyze"     # the cost report's jaxpr walk
SPAN_MEMORY_COMPILED = "memory/compiled"    # step_memory's first asker
SPAN_PROGRAM_TEXT = "program_map/text"      # get_program_map's first asker
SPAN_COMPILE_AOT = "compile/aot"            # compile_train_step: lower + compile
SETUP_SPANS = (SPAN_ENGINE_INIT, SPAN_INIT_SHARDINGS, SPAN_INIT_PARAMS,
               SPAN_INIT_OPTIMIZER, SPAN_TRAIN_STEP, SPAN_FUSED_STEP,
               SPAN_COST_ANALYZE, SPAN_MEMORY_COMPILED, SPAN_PROGRAM_TEXT,
               SPAN_COMPILE_AOT)
#: spans that look at a program and do not run it: what they trace, lower
#: or compile is no recompile of it
OBSERVER_SPANS = (SPAN_COST_ANALYZE, SPAN_MEMORY_COMPILED, SPAN_PROGRAM_TEXT,
                  SPAN_COMPILE_AOT)
#: the stages of a row: jax traced the function, lowered it to MLIR, and
#: the backend compiled it (``compile``) or the persistent cache had it
#: (``cache_load``)
STAGES = ("trace", "lower", "compile", "cache_load")
#: a per-step span is kept in the account when it began at one of an
#: engine's first steps (a benchmark's warm-up and its first timed step)
#: or when jax traced, lowered or compiled inside it
SETUP_STEPS_KEPT = 32
_PER_STEP_SPANS = (SPAN_TRAIN_STEP, SPAN_FUSED_STEP)

_JAX_STAGE = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
_WRAPPED = re.compile(r"^\w+\((.*)\)$")     # jit(train_step) -> train_step


class _OpenSpan:
    __slots__ = ("id", "name", "start", "parent", "step", "child_s", "keep")

    def __init__(self, id_, name, start, parent, step):
        self.id, self.name, self.start = id_, name, start
        self.parent, self.step = parent, step
        self.child_s, self.keep = 0.0, False


class _OpenEvent:
    __slots__ = ("event", "program", "cause", "child_s", "retrace",
                 "recompile", "hit", "missed", "seconds")

    def __init__(self, event, program, cause):
        self.event, self.program, self.cause = event, program, cause
        self.child_s = 0.0
        self.retrace = self.recompile = self.hit = self.missed = False
        self.seconds = {}


class SetupAccount:
    """The process's account of its own start; see :func:`setup_account`
    for what it holds.  One per process (:func:`reset_programs` drops it
    and its listeners).  Spans and jax's events nest by thread: each
    thread has a stack of its open spans and one of the jax events that
    have begun on it and not ended."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ids = itertools.count()
        self._wall_offset = None
        self.spans: List[Dict[str, Any]] = []
        self.rows: List[Dict[str, Any]] = []
        self.other: Dict[tuple, Dict[str, Any]] = {}
        self.names: Dict[str, str] = {}     # jax's fun_name -> program
        self.traced = set()                 # programs, since the last init
        self.ran = set()                    # ... compiled or loaded by a call
        self.warned = set()
        self.last_s: Dict[str, Dict[str, float]] = {}
        self.steps = 0
        self._listeners = (
            (jax.monitoring.register_scalar_listener,
             jax.monitoring.unregister_scalar_listener, self._on_begin),
            (jax.monitoring.register_event_time_span_listener,
             jax.monitoring.unregister_event_time_span_listener,
             self._on_end),
            (jax.monitoring.register_event_listener,
             jax.monitoring.unregister_event_listener, self._on_cache),
            (jax.monitoring.register_event_duration_secs_listener,
             jax.monitoring.unregister_event_duration_listener,
             self._on_cache_seconds))

    def listen(self):
        for register, _, listener in self._listeners:
            register(listener)

    def unlisten(self):
        for _, unregister, listener in self._listeners:
            unregister(listener)

    def _thread(self):
        t = self._tls
        if not hasattr(t, "spans"):
            t.spans, t.events = [], []
        return t

    # -------------------------------------------------------------- spans
    def open_span(self, name: str, step: Optional[int]) -> _OpenSpan:
        t = self._thread()
        parent = t.spans[-1] if t.spans else None
        if name == SPAN_ENGINE_INIT:
            # a new engine's programs are new programs
            self.traced.clear()
            self.ran.clear()
            self.steps = step = 0
        elif step is None:
            step = parent.step if parent is not None else self.steps
        elif name == SPAN_TRAIN_STEP:
            self.steps = step + 1
        span = _OpenSpan(next(self._ids), name, time.perf_counter(), parent,
                         step)
        t.spans.append(span)
        return span

    def close_span(self, span: _OpenSpan):
        end = time.perf_counter()
        spans = self._thread().spans
        if spans and spans[-1] is span:     # not so after reset_programs
            spans.pop()
        if span.parent is not None:
            span.parent.child_s += end - span.start
        if not (span.keep or span.name not in _PER_STEP_SPANS
                or span.step < SETUP_STEPS_KEPT):
            return
        if span.parent is not None:
            span.parent.keep = True
        self.spans.append({
            "id": span.id, "name": span.name, "start": span.start,
            "end": end, "step": span.step,
            "parent": None if span.parent is None else span.parent.id,
            "self_s": end - span.start - span.child_s})

    # ------------------------------------------------------- jax's events
    def _open_event(self, t, event: str, fun_name: str) -> _OpenEvent:
        inner = _WRAPPED.match(fun_name)
        outer = t.events[-1] if t.events else None
        # the engine's programs are jitted at top level: a function of the
        # same name traced inside another's trace is not one of them
        program = None if outer is not None else self.names.get(
            inner.group(1) if inner else fun_name)
        ev = _OpenEvent(event, program, t.spans[-1] if t.spans else None)
        observed = ev.cause is not None and ev.cause.name in OBSERVER_SPANS
        if _JAX_STAGE[event] == "trace":
            if outer is not None and _JAX_STAGE[outer.event] == "trace":
                ev.retrace = outer.retrace
            elif program is not None:
                ev.retrace = program in self.traced
                self.traced.add(program)
        ev.recompile = (program is not None and program in self.ran
                        and not observed)
        return ev

    def _on_begin(self, event, _value, fun_name="", **_):
        if event in _JAX_STAGE:
            t = self._thread()
            t.events.append(self._open_event(t, event, fun_name))

    def _on_cache(self, event, **_):
        events = self._thread().events
        if event == _CACHE_HIT:
            get_registry().inc("compile/cache_hits")
            if events:
                events[-1].hit = True
        elif event == _CACHE_MISS:
            get_registry().inc("compile/cache_misses")
            if events:
                events[-1].missed = True

    def _on_cache_seconds(self, event, seconds, **_):
        key = _CACHE_SECONDS.get(event)
        events = self._thread().events if key else None
        if events:
            events[-1].seconds[key] = seconds

    def _wall_to_clock(self) -> float:
        """jax stamps its events with ``time.time()``; the account's clock
        is the tracer's (``time.perf_counter()``): one offset, taken once
        and again only if the wall clock has been stepped."""
        offset = time.perf_counter() - time.time()
        if self._wall_offset is None \
                or abs(offset - self._wall_offset) > 1e-3:
            self._wall_offset = offset
        return self._wall_offset

    def _on_end(self, event, start, end, fun_name="", **_):
        stage = _JAX_STAGE.get(event)
        if stage is None:
            return
        t = self._thread()
        if t.events and t.events[-1].event == event:
            ev = t.events.pop()
        else:                         # its beginning was not reported
            ev = self._open_event(t, event, fun_name)
        offset = self._wall_to_clock()
        start, end = start + offset, end + offset
        cause = ev.cause
        if cause is not None:
            start = max(start, cause.start)
        seconds = end - start
        if t.events:
            t.events[-1].child_s += seconds
        elif cause is not None:
            cause.child_s += seconds
        for span in t.spans:
            span.keep = True
        if stage == "compile" and ev.hit:
            stage = "cache_load"
        row = {"program": ev.program or "other", "stage": stage,
               "start": start, "end": end,
               "self_s": seconds - ev.child_s,
               "cause": None if cause is None else cause.name,
               "span": None if cause is None else cause.id,
               "step": self.steps if cause is None else cause.step,
               "retrace": ev.retrace, "recompile": ev.recompile,
               "missed": int(ev.missed), **ev.seconds}
        with self._lock:
            if ev.program is None:
                self._fold(row)
            else:
                self.rows.append(row)
        if ev.program is not None:
            self._named_row_ended(ev, row, seconds)
        tracer = get_tracer()
        if tracer.enabled:
            tracer.interval(
                "setup/" + stage, start, end, cat="setup",
                args={"program": row["program"], "fun_name": fun_name,
                      "retrace": ev.retrace, "recompile": ev.recompile})

    def _fold(self, row):
        """Events of functions the engine did not name (eager ops'
        one-primitive programs, the caller's own code) are one row per
        stage and causing span: a count and seconds.  Bounded by the
        spans kept, not by the events."""
        key = (row["stage"], row["span"], row["retrace"])
        have = self.other.get(key)
        if have is None:
            self.other[key] = {**row, "count": 1}
            return
        have["count"] += 1
        have["end"] = row["end"]
        for name in ("self_s", "missed", *_CACHE_SECONDS.values()):
            if name in row:
                have[name] = have.get(name, 0) + row[name]

    def _named_row_ended(self, ev, row, seconds):
        program, stage = row["program"], row["stage"]
        if stage in ("trace", "lower"):
            self.last_s.setdefault(program, {})[stage] = seconds
            return
        if row["cause"] not in OBSERVER_SPANS:
            self.ran.add(program)
        if not ev.recompile:
            return
        # the in-program form of a benchmark's "no compile inside the
        # window": a program that had run was traced and compiled again
        get_registry().inc("compile/recompiles")
        if program not in self.warned:
            self.warned.add(program)
            last = self.last_s.get(program, {})
            logger.warning(
                f"recompile: {program} at step {row['step']} (a new shape, "
                f"placement or static argument): trace "
                f"{last.get('trace', 0.0):.3f} s, lower "
                f"{last.get('lower', 0.0):.3f} s, {stage} {seconds:.3f} s; "
                f"further recompiles of it only count "
                f"(compile/recompiles)")

    # -------------------------------------------------------------- output
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            rows = [dict(r) for r in self.rows] \
                + [dict(r) for r in self.other.values()]
            spans = [dict(s) for s in self.spans]
        rows.sort(key=lambda r: r["start"])
        spans.sort(key=lambda s: s["start"])
        return {"spans": spans, "rows": rows, "steps": self.steps}


_SETUP: Optional[SetupAccount] = None


def _setup() -> SetupAccount:
    global _SETUP
    account = _SETUP
    if account is None:
        with _PROGRAM_LOCK:
            if _SETUP is None:
                _SETUP = SetupAccount()
                _SETUP.listen()
            account = _SETUP
    return account


class setup_span:
    """``with setup_span(name):`` — a span of :data:`SETUP_SPANS`: the
    tracer's span of that name (a ``ds/<name>`` TraceAnnotation in any
    profiler session, a ``B``/``E`` pair of the ``DS_TRACE`` file) and,
    armed or not, a span of the set-up account.  ``step`` is the
    optimizer step count at which it begins (a ``train/step`` says its
    own; anything else inherits its parent's).  ``tracer`` defaults to
    the active one; other keywords go to its ``span`` (``cat`` defaults
    to ``"setup"``).

    ``span.phase(name)`` ends the child span opened by the last call, if
    any, and opens the child ``name`` (None: none) — for a long body
    whose parts follow one another."""
    __slots__ = ("name", "step", "tracer", "kw", "_account", "_tracer_span",
                 "_open", "_phase")

    def __init__(self, name: str, step: Optional[int] = None, tracer=None,
                 **kw):
        kw.setdefault("cat", "setup")
        self.name, self.step, self.tracer, self.kw = name, step, tracer, kw
        self._phase = None

    def __enter__(self):
        self._account = _setup()
        self._tracer_span = (self.tracer or get_tracer()).span(
            self.name, **self.kw)
        self._tracer_span.__enter__()
        self._open = self._account.open_span(self.name, self.step)
        return self

    def phase(self, name: Optional[str]):
        if self._phase is not None:
            self._phase.__exit__(None, None, None)
        self._phase = None
        if name is not None:
            self._phase = setup_span(name, tracer=self.tracer).__enter__()

    def __exit__(self, *exc):
        self.phase(None)
        self._account.close_span(self._open)
        return self._tracer_span.__exit__(*exc)


def name_program(fun_name: str, program: str):
    """The engine jits ``program`` (a name ``_get_compiled`` knows) from a
    function jax will report as ``fun_name``: its traces, lowerings and
    compiles get rows of their own in the set-up account."""
    _setup().names[fun_name] = program


def setup_account() -> Dict[str, Any]:
    """Where this process's start went, kept in memory whether or not a
    tracer is armed — set-up is tens of rows a process, not rows a step.
    ``{"spans": [...], "rows": [...], "steps": n}``, every time in
    seconds on the tracer's clock (``time.perf_counter()``), sorted by
    ``start``; ``steps`` is how many ``train/step`` spans have begun.

    **spans** the program opened through :func:`setup_span` and has
    closed (:data:`SETUP_SPANS`): ``id``, ``name``, ``start``, ``end``,
    ``parent`` (the id of the span it was opened in, on the same thread,
    or None), ``step`` (the optimizer step count at which it began) and
    ``self_s`` — its duration less what its child spans and the rows
    directly under it cover.  A ``train/step`` or ``train/fused_step`` is
    kept only from a step under :data:`SETUP_STEPS_KEPT` or if jax
    traced, lowered or compiled inside it.

    **rows**, one per trace, lowering and backend compile that jax
    reported (``jax.monitoring``; a steady call of a compiled program
    reports nothing): ``program`` — the engine's name for the function
    (``train_step``, ``grad``, ``apply`` ...: :func:`name_program`) or
    ``"other"``; ``stage`` of :data:`STAGES` (a backend compile in which
    the persistent cache reported a hit is a ``cache_load`` and carries
    the cache's ``retrieval_s`` and ``saved_s``; ``missed`` counts the
    compiles that were written to the cache as new entries); ``start``,
    ``end``; ``self_s`` — the duration less the events nested in it on
    the same thread (a jitted function traced inside another's trace,
    an eager op compiled while a program is traced), so that self times
    add up and durations do not; ``cause`` and ``span`` — the name and id
    of the innermost span open on that thread when it began (None:
    outside every span of the program — the caller's own code); ``step``;
    ``retrace`` — a trace of a program this engine had traced before, or
    anything traced inside one; ``recompile`` — the program had already
    been compiled or loaded by a call, and no observer span
    (:data:`OBSERVER_SPANS`) caused this (a ``trace`` row of microseconds
    with no ``lower`` after it is jit's Python path finding its own
    caches warm: jax reports that too, and nothing was compiled).  Rows of
    ``"other"`` are folded
    by stage and causing span and carry a ``count``; ``start`` is then
    the first event's and ``end`` the last's."""
    account = _SETUP
    if account is None:
        return {"spans": [], "rows": [], "steps": 0}
    return account.snapshot()


def reset_programs():
    """Tests: forget every registered program, and the set-up account
    with its listeners."""
    global _SETUP
    with _PROGRAM_LOCK:
        _PROGRAM_THUNKS.clear()
        _PROGRAM_FACTS.clear()
        account, _SETUP = _SETUP, None
    if account is not None:
        account.unlisten()
    _STEP_COUNTERS.clear()
