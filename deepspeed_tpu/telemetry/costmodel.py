"""Compiled-program cost model (ISSUE 13 tentpole).

Every hot-path program family this framework compiles — the engine
train step, the serving scheduler's decode/window/prefill programs,
fused vs unfused kernel variants — should know its own cost instead of
having it hand-computed in PERF.md prose.  This module walks a traced
program (jaxpr) and produces a :class:`CostReport`:

- **dot FLOPs** — ``2·M·N·K`` per ``dot_general``, execution-weighted
  (a ``lax.scan`` body multiplies by its trip count, a ``pallas_call``
  body by its grid size, a ``cond`` contributes its most expensive
  branch);
- **pallas launch sites** — ``pallas_call`` equations counted
  recursively through sub-jaxprs, each one device kernel launch per
  execution.  This is the PR 12 fused-decode L-vs-4L assertion
  generalized into a library (:func:`count_pallas_launches`);
- **collective bytes** — operand bytes of psum/all_gather/etc.
  equations, execution-weighted — plus a per-collective breakdown
  keyed ``op|mesh-axis|dtype`` (ISSUE 19): call counts, logical
  payload bytes, and ring-algorithm WIRE bytes (``2·(N−1)/N`` for
  all-reduce, ``(N−1)/N`` for all-gather / reduce-scatter /
  all-to-all, ``1`` for ppermute), with axis sizes read from the
  enclosing ``shard_map``/``pmap`` equation's mesh;
- **HBM bytes** — the dtype-aware weight stream the program must pull
  per execution.  For the decode regime this IS the floor, and the
  math is the existing ``split_quantized_bytes`` accounting
  (serve_bench / decode_profile ``weights_floor_int8`` /
  ``weights_floor_moe``) promoted to library code:
  :func:`param_stream_bytes`.

Reports register into a process-wide table (plain dict writes — the
``/debug/perf`` reader never takes any scheduler lock) so the metrics
surfaces, post-mortem bundles, and ``scripts/perf_report.py`` all read
one source of truth.  Analysis costs one extra trace per program
family: the serving scheduler pays it at a family's first execution
(``DS_PERF_COSTMODEL=0`` switches that off); the engine's train step
is analysed when somebody asks (telemetry/tracing.py
``get_program_cost``), and :func:`get_report` only ever peeks.
"""
import math
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

COSTMODEL_ENV = "DS_PERF_COSTMODEL"

#: collective primitives whose operand bytes cross the interconnect
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "psum_scatter", "all_gather", "all_to_all", "ppermute",
    "pgather", "reduce_scatter", "pmax", "pmin", "allreduce"})

#: primitive name -> the canonical collective family it performs on
#: the wire (pmax/pmin are all-reduces with a different combiner;
#: ``psum_scatter`` traces as primitive ``reduce_scatter``)
CANONICAL_COLLECTIVE = {
    "psum": "all_reduce", "allreduce": "all_reduce",
    "pmax": "all_reduce", "pmin": "all_reduce",
    "psum_scatter": "reduce_scatter", "reduce_scatter": "reduce_scatter",
    "all_gather": "all_gather", "pgather": "all_gather",
    "all_to_all": "all_to_all", "ppermute": "ppermute",
}


def ring_wire_factor(op: str, n: Optional[int]) -> float:
    """Bytes each participant puts on the wire per logical payload
    byte under the standard ring algorithms (the ``calc_bw_log``
    busbw convention): ``2·(N−1)/N`` for all-reduce,
    ``(N−1)/N`` for all-gather / reduce-scatter / all-to-all,
    ``1`` for ppermute.  ``n=None`` (axis size unknown) returns 1.0 —
    never an inflated guess."""
    if n is None:
        return 1.0
    n = max(int(n), 1)
    if op == "all_reduce":
        return 2.0 * (n - 1) / n
    if op in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n
    return 1.0


def costmodel_enabled(config_default: Optional[bool] = None) -> bool:
    """Resolution order (the repo's env-wins convention):
    ``DS_PERF_COSTMODEL`` env > the ``telemetry.costmodel`` config value
    the caller passes > on."""
    env = os.environ.get(COSTMODEL_ENV, "").strip()
    if env:
        return env not in ("0", "false", "off")
    if config_default is not None:
        return bool(config_default)
    return True


@dataclass
class CostReport:
    """Static cost of ONE execution of a compiled program family."""
    name: str
    flops: int = 0                 #: dot FLOPs (2·M·N·K, execution-weighted)
    hbm_bytes: int = 0             #: weight-stream bytes per execution
    pallas_launches: int = 0       #: kernel-launch sites in the program
    collective_bytes: int = 0      #: interconnect payload per execution
    #: per-collective breakdown keyed ``"op|axis|dtype"`` (e.g.
    #: ``"all_reduce|data|float32"``) -> {calls, payload_bytes,
    #: wire_bytes, axis_size}, execution-weighted like flops
    collectives: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    detail: Dict[str, Any] = field(default_factory=dict)

    def arithmetic_intensity(self) -> Optional[float]:
        """FLOPs per HBM byte (None when the byte model is empty)."""
        if self.hbm_bytes <= 0:
            return None
        return self.flops / self.hbm_bytes

    def comm_wire_bytes(self) -> int:
        """Total ring-algorithm wire bytes per execution — the quantity
        an interconnect-bandwidth floor divides (0 when the program has
        no per-axis collective attribution)."""
        return int(sum(row.get("wire_bytes", 0)
                       for row in self.collectives.values()))

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "flops": int(self.flops),
                "hbm_bytes": int(self.hbm_bytes),
                "pallas_launches": int(self.pallas_launches),
                "collective_bytes": int(self.collective_bytes),
                "comm_wire_bytes": self.comm_wire_bytes(),
                "collectives": {k: dict(v)
                                for k, v in self.collectives.items()},
                "detail": dict(self.detail)}


# ------------------------------------------------------------ jaxpr walk
def _aval_bytes(aval) -> int:
    try:
        import numpy as np
        return int(aval.size) * int(np.dtype(aval.dtype).itemsize)
    except Exception:   # abstract tokens, opaque avals
        return 0


def _sub_jaxprs(eqn):
    """Every (Closed)Jaxpr reachable from an equation's params."""
    from deepspeed_tpu.utils.jax_compat import ClosedJaxpr, Jaxpr
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for it in items:
            if isinstance(it, ClosedJaxpr):
                yield it.jaxpr
            elif isinstance(it, Jaxpr):
                yield it


def primitive_names(jaxpr) -> List[str]:
    """The primitive of every equation of a traced program, recursively
    through sub-jaxprs (scan/cond/jit bodies) — e.g. that a gradient
    holds no ``scatter``/``scatter-add`` (how the grouped MoE layer's
    gathers-only row movement is read off the jaxpr on a CPU)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)      # accept ClosedJaxpr
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in _sub_jaxprs(eqn):
            names.extend(primitive_names(sub))
    return names


def count_pallas_launches(jaxpr) -> int:
    """Kernel-launch SITES in a traced program: ``pallas_call``
    equations, recursively through sub-jaxprs (scan/cond/jit bodies).
    Each site is one device kernel launch per execution — countable on
    CPU, where interpret-mode kernels still trace as ``pallas_call``
    equations.  This is the PR 12 fused-decode launch-count contract
    (``<= L + k`` fused vs ``~(4-6)L`` unfused) as a shared API."""
    return primitive_names(jaxpr).count("pallas_call")


def _dot_flops(eqn) -> int:
    """2·(output elements)·(contraction length) for a dot_general."""
    try:
        (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        k = 1
        for d in lhs_c:
            k *= int(lhs.shape[d])
        out = eqn.outvars[0].aval
        return 2 * int(out.size) * k
    except Exception:
        return 0


def _grid_size(eqn) -> int:
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", None) or ()
    n = 1
    for g in grid:
        if isinstance(g, int):
            n *= g
    return max(n, 1)


def _collective_axes(eqn):
    """The mesh-axis NAMES a collective equation spans (psum carries
    ``axes``, the others ``axis_name``; all_to_all's is a bare string).
    Positional (int) axes are dropped — they never cross a device."""
    names = eqn.params.get("axes")
    if names is None:
        names = eqn.params.get("axis_name")
    if names is None:
        return ()
    if isinstance(names, str):
        return (names,)
    return tuple(n for n in names if isinstance(n, str))


def _axis_product(names, axis_sizes: Dict[str, int]) -> Optional[int]:
    n = 1
    for nm in names:
        size = axis_sizes.get(nm)
        if size is None:
            return None
        n *= int(size)
    return n


def _account_collective(eqn, prim: str, mult: int,
                        collectives: Dict[str, Dict[str, Any]],
                        axis_sizes: Dict[str, int]):
    op = CANONICAL_COLLECTIVE.get(prim, prim)
    names = _collective_axes(eqn)
    axis = "+".join(names) if names else "?"
    # the equation's own axis_size param (all_gather / reduce_scatter
    # carry the participant-count product) beats the mesh lookup
    n = eqn.params.get("axis_size")
    n = int(n) if n is not None else _axis_product(names, axis_sizes)
    for v in eqn.invars:
        nbytes = _aval_bytes(v.aval)
        if nbytes <= 0:
            continue
        try:
            import numpy as np
            dtype = str(np.dtype(v.aval.dtype))
        except Exception:
            dtype = "?"
        # the logical payload is the FULL tensor: an all_gather operand
        # is one shard, so scale it back up by the participant count
        payload = nbytes * n if (op == "all_gather" and n) else nbytes
        key = f"{op}|{axis}|{dtype}"
        row = collectives.setdefault(
            key, {"calls": 0, "payload_bytes": 0, "wire_bytes": 0,
                  "axis_size": n})
        row["calls"] += mult
        row["payload_bytes"] += mult * payload
        row["wire_bytes"] += int(round(
            mult * payload * ring_wire_factor(op, n)))
        row["axis_size"] = n


def _mesh_axis_sizes(eqn) -> Dict[str, int]:
    """Axis name -> size bindings an equation establishes for its body
    (``shard_map`` carries a Mesh param; ``pmap`` carries
    axis_name/axis_size)."""
    out: Dict[str, int] = {}
    mesh = eqn.params.get("mesh")
    shape = getattr(mesh, "shape", None)
    if shape:
        try:
            out.update({str(k): int(v) for k, v in dict(shape).items()})
        except (TypeError, ValueError):     # exotic mesh shape object
            out.clear()
    name = eqn.params.get("axis_name")
    size = eqn.params.get("axis_size")
    if isinstance(name, str) and size is not None and \
            eqn.primitive.name not in COLLECTIVE_PRIMITIVES:
        out[name] = int(size)
    return out


def _new_acc() -> Dict[str, Any]:
    return {"flops": 0, "collective_bytes": 0, "launches": 0,
            "collectives": {}}


def _merge_collectives(dst: Dict[str, Dict[str, Any]],
                       src: Dict[str, Dict[str, Any]]):
    for key, row in src.items():
        cur = dst.setdefault(
            key, {"calls": 0, "payload_bytes": 0, "wire_bytes": 0,
                  "axis_size": row.get("axis_size")})
        cur["calls"] += row["calls"]
        cur["payload_bytes"] += row["payload_bytes"]
        cur["wire_bytes"] += row["wire_bytes"]
        cur["axis_size"] = row.get("axis_size")


def _walk(jaxpr, mult: int, acc: Dict[str, Any],
          axis_sizes: Optional[Dict[str, int]] = None):
    axis_sizes = axis_sizes or {}
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            acc["flops"] += mult * _dot_flops(eqn)
        elif prim in COLLECTIVE_PRIMITIVES:
            acc["collective_bytes"] += mult * sum(
                _aval_bytes(v.aval) for v in eqn.invars)
            _account_collective(eqn, prim, mult, acc["collectives"],
                                axis_sizes)
        if prim == "pallas_call":
            acc["launches"] += 1
        if prim == "cond":
            # a cond executes ONE branch: charge the most expensive
            branches = eqn.params.get("branches", ())
            best = None
            for br in branches:
                sub_acc = _new_acc()
                _walk(getattr(br, "jaxpr", br), mult, sub_acc, axis_sizes)
                if best is None or sub_acc["flops"] > best["flops"]:
                    best = sub_acc
            if best is not None:
                acc["flops"] += best["flops"]
                acc["collective_bytes"] += best["collective_bytes"]
                acc["launches"] += best["launches"]
                _merge_collectives(acc["collectives"], best["collectives"])
            continue
        sub_mult = mult
        if prim == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        elif prim == "pallas_call":
            sub_mult = mult * _grid_size(eqn)
        sub_axes = axis_sizes
        bound = _mesh_axis_sizes(eqn)
        if bound:
            sub_axes = dict(axis_sizes)
            sub_axes.update(bound)
        for sub in _sub_jaxprs(eqn):
            _walk(sub, sub_mult, acc, sub_axes)


def analyze_jaxpr(closed_jaxpr, name: str = "program",
                  hbm_bytes: Optional[int] = None) -> CostReport:
    """Cost-walk a (Closed)Jaxpr.  ``hbm_bytes`` is the caller's
    dtype-aware weight-stream model (:func:`param_stream_bytes`); when
    absent, the program-boundary bytes (inputs + outputs) stand in as
    an upper bound and are flagged in the detail dict."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    acc = _new_acc()
    _walk(jaxpr, 1, acc)
    detail: Dict[str, Any] = {}
    if hbm_bytes is None:
        hbm_bytes = sum(_aval_bytes(v.aval) for v in jaxpr.invars) + \
            sum(_aval_bytes(v.aval) for v in jaxpr.outvars)
        detail["hbm_bytes_source"] = "program_boundary_upper_bound"
    else:
        detail["hbm_bytes_source"] = "param_stream"
    return CostReport(name=name, flops=acc["flops"],
                      hbm_bytes=int(hbm_bytes),
                      pallas_launches=acc["launches"],
                      collective_bytes=acc["collective_bytes"],
                      collectives=acc["collectives"],
                      detail=detail)


def analyze_fn(fn, *args, name: str = "program",
               hbm_bytes: Optional[int] = None,
               detail: Optional[Dict[str, Any]] = None) -> CostReport:
    """Trace ``fn(*args)`` (one extra host-side trace, no compile) and
    cost-walk the result."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    report = analyze_jaxpr(closed, name=name, hbm_bytes=hbm_bytes)
    if detail:
        report.detail.update(detail)
    return report


# -------------------------------------------------- weight-stream floors
def param_stream_bytes(params, *, batch: int = 1,
                       top_k: Optional[int] = None,
                       num_experts: Optional[int] = None
                       ) -> Dict[str, int]:
    """The decode-regime weight-stream byte model, library-ized from
    serve_bench / decode_profile (``split_quantized_bytes`` is the one
    shared walk, so the scripts and this model can never drift):

    - ``dense_int8_bytes`` / ``expert_int8_bytes`` — stored int8 form
      (q + fp32 scales) split at the stacked-expert rank;
    - ``plain_bytes`` — unquantized floating leaves at their dtype
      width (the bf16/f32 weight stream);
    - ``weights_floor_int8`` — every stored byte once per step (the
      dense-model int8 byte-stream floor);
    - ``weights_floor_moe`` — dense bytes + only ``min(batch·top_k,
      E)`` DISTINCT experts' bytes (the slot-kernel schedule fetches
      each distinct routed expert exactly once per step).  Present only
      when ``num_experts``/``top_k`` describe a routed model.
    """
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.model import QuantizedTensor
    from deepspeed_tpu.models.serving import split_quantized_bytes

    dense_b, expert_b = split_quantized_bytes(params)
    plain = 0
    is_q = lambda x: isinstance(x, QuantizedTensor)
    for leaf in jax.tree_util.tree_leaves(params, is_leaf=is_q):
        if is_q(leaf):
            continue
        try:
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                plain += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
        except (TypeError, AttributeError):
            continue            # non-array leaf (config scalar, None)
    out: Dict[str, int] = {
        "dense_int8_bytes": dense_b,
        "expert_int8_bytes": expert_b,
        "plain_bytes": plain,
        "weights_floor_int8": dense_b + expert_b,
        "weights_floor_bytes": dense_b + expert_b + plain,
    }
    if num_experts and top_k and expert_b:
        per_expert = expert_b // num_experts      # all layers, one expert
        distinct = min(max(batch, 1) * top_k, num_experts)
        out["distinct_experts"] = distinct
        out["per_expert_bytes"] = per_expert
        out["weights_floor_moe"] = dense_b + distinct * per_expert
        out["weights_floor_bytes"] = (dense_b + distinct * per_expert
                                      + plain)
    return out


def abstract_quantized_blocks(model, block: int = 256):
    """Shape-only int8 packing of a model's stacked transformer blocks:
    ``jax.eval_shape`` of ``init_fn`` (no parameter materialization —
    7B floors cost nothing), then the serving ``_pack`` rule (floating
    leaves of ndim >= 3 quantize) mapped to abstract
    ``QuantizedTensor`` leaves with the ``block_quantize_int8`` layout
    (scales ``[..., ceil(C/block)]`` fp32).  Feed the result to
    :func:`param_stream_bytes` for bench-shape floors."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.model import QuantizedTensor

    shapes = jax.eval_shape(model.init_fn, jax.random.PRNGKey(0))
    blocks = shapes["blocks"] if isinstance(shapes, dict) and \
        "blocks" in shapes else shapes

    def pack(leaf):
        if leaf.ndim >= 3 and jnp.issubdtype(leaf.dtype, jnp.floating):
            c = int(leaf.shape[-1])
            s_shape = tuple(leaf.shape[:-1]) + (math.ceil(c / block),)
            return QuantizedTensor(
                jax.ShapeDtypeStruct(leaf.shape, jnp.int8),
                jax.ShapeDtypeStruct(s_shape, jnp.float32), "bfloat16")
        return leaf

    return jax.tree.map(pack, blocks)


# ------------------------------------------------- process-wide registry
_LOCK = threading.Lock()                 # writers only; readers are lock-free
_REPORTS: Dict[str, CostReport] = {}
#: program -> (last_ms, count, total_ms) — written by the roofline
#: observer, read (dict snapshot) by /debug/perf with no lock
_ACHIEVED: Dict[str, tuple] = {}


def register_report(report: CostReport):
    with _LOCK:
        _REPORTS[report.name] = report


def get_reports() -> Dict[str, CostReport]:
    """Snapshot of the registered program cost table (lock-free read:
    one dict copy under the GIL)."""
    return dict(_REPORTS)


def get_report(name: str) -> Optional[CostReport]:
    return _REPORTS.get(name)


def record_achieved(name: str, duration_s: float):
    """One measured execution.  The FIRST sample of a program carries
    jit compile + the analysis trace, so it is kept as ``last_ms`` (it
    self-heals on the next execution) but excluded from the running
    total — ``achieved_mean_ms`` reports warm steps only.  Writes take
    the module lock (concurrent fleet replicas share these keys);
    readers still only snapshot."""
    ms = float(duration_s) * 1e3
    with _LOCK:
        prev = _ACHIEVED.get(name)
        if prev is None:
            _ACHIEVED[name] = (ms, 1, 0.0)      # warmup sample: last only
        else:
            _ACHIEVED[name] = (ms, prev[1] + 1, prev[2] + ms)


def get_achieved() -> Dict[str, tuple]:
    return dict(_ACHIEVED)


def reset_reports():
    """Tests: clear the process-wide cost table."""
    with _LOCK:
        _REPORTS.clear()
        _ACHIEVED.clear()
