"""MoE layer with expert parallelism (reference: deepspeed/moe/layer.py:85
``MoE`` and sharded_moe.py:425 ``MOELayer``: gate → dispatch → all-to-all →
local experts → all-to-all → combine).

Two dispatch formulations (``MoEConfig.dispatch_mode``, ISSUE 8):

- ``einsum`` — the GShard capacity formulation: expert weights stacked
  [E, ...] and sharded over the ``expert`` mesh axis; dispatch/combine
  are einsums against dense [T, E, C] gating tensors whose resharding
  XLA lowers to the reference's pair of all-to-alls.  Deterministic and
  multi-axis-shardable, but the two einsums are O(T·E·C·D) and every
  expert pads to capacity C (tokens past C DROP).
- ``grouped`` — megablocks-style ragged dispatch
  (ops/pallas/grouped_gemm.py): tokens argsort by expert, the expert
  FFN runs as ONE grouped GEMM over the sorted rows against the stacked
  weights (zero capacity padding), and outputs combine by gather.
  **Drop-free**: every routed token computes, regardless of
  ``capacity_factor``.  On an ``expert`` mesh axis wider than one the
  experts are really spread (:func:`_exchanged_grouped_moe`): inside a
  ``shard_map`` over every mesh axis each chip routes its own tokens
  over all experts, sends a token's row once to each chip that holds an
  expert it chose — and, beside the rows, a float32 pair a (token,
  expert): its gate and where its token's row lands (one all-to-all of
  rows, one of lanes) — expands what landed into the held plan's groups,
  runs the plan, each row weighted by its gate between the experts' two
  halves, sums a token's results on that chip, sends the sums back (a
  second all-to-all of rows) and adds them up where the token lives.
  Drop-free inside a stated bound: a chip has room for
  ``held_rows_factor`` times the (token, expert) rows even routing sends
  it, and a row past that is counted (:data:`ROWS_OVER_BOUND`), never
  silently lost.
- ``auto`` — einsum when training; grouped at eval/serving when the
  kernel is real (single TPU device / interpret) or the host is
  single-device — a multi-device host where only the unsharded
  ragged_dot reference would run keeps the sharded einsum formulation.

Training through the grouped formulation is what a model's builder asks
for (``dispatch_mode="grouped"``, e.g. ``mixtral_model(...,
moe_dispatch="grouped")``): forward, ``dx`` and ``dw`` then run as the
named ``ds_ggemm_*`` kernels under ``lax.scan`` + ``jax.checkpoint`` and
no token is dropped at any imbalance.  Its parts carry the
``jax.named_scope``s ``router`` / ``dispatch`` / ``experts`` /
``combine`` (telemetry/tracing.py ``SCOPE_ROUTER`` ... ``SCOPE_COMBINE``).
"""
import contextlib
import os
from dataclasses import dataclass, fields
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import get_topology, EXPERT_AXIS
from deepspeed_tpu.moe.sharded_moe import (topkgating, topk_routing,
                                           GateOutput)
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_COMBINE, SCOPE_DISPATCH, SCOPE_EXCHANGE, SCOPE_EXPERTS,
    SCOPE_RETURN, SCOPE_ROUTER, SCOPE_SEND, SCOPE_SHARED_EXPERT,
    count_in_step)


@dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None    # None | 'Jitter'
    #: silu_glu (Mixtral: three matrices) | gelu | relu2 (Nemotron-H:
    #: ``w_out . relu(w_in . x)^2``); the last two are un-gated, two
    #: matrices and no ``w_gate`` leaf
    activation: str = "silu_glu"
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 0.0
    #: divide the k chosen gate values by their sum (Mixtral, the
    #: reference); False keeps the softmax probabilities as they are
    #: (OLMoE ``norm_topk_prob: false``)
    norm_topk_prob: bool = True
    #: what the router makes of its logits (sharded_moe.ROUTER_FORMS):
    #: "softmax", or "sigmoid" scores chosen by ``score +
    #: e_score_correction_bias`` — a leaf [num_experts] that is in the
    #: choice only, never in the weights, so its gradient is exactly zero
    #: (a load-driven update of it is not built: ROADMAP)
    router: str = "softmax"
    #: multiplies the chosen weights last, after ``norm_topk_prob``
    routed_scaling_factor: float = 1.0
    #: form of the load-balance term (sharded_moe.LOAD_BALANCE_FORMS):
    #: "first_choice" (the reference) or "all_choices" (OLMoE / Hugging
    #: Face ``load_balancing_loss_func``)
    load_balance: str = "first_choice"
    #: Residual MoE (reference moe/layer.py:28 ``use_residual``, the PR-MoE
    #: building block, arXiv:2201.05596): a dense FFN runs beside the
    #: routed experts and a learned 2-way softmax coefficient mixes them
    use_residual: bool = False
    #: expert dispatch formulation — "einsum" (GShard capacity tensors,
    #: the bitwise-back-compat default), "grouped" (megablocks-style
    #: ragged grouped GEMM, drop-free), or "auto" (einsum when training,
    #: grouped at eval/serving).  DS_MOE_DISPATCH env and the serving
    #: config's ``serving.moe_dispatch`` key override (see
    #: :func:`resolve_dispatch_mode`).
    dispatch_mode: str = "einsum"
    #: the experts THIS layer holds: ``experts_held`` from ``expert_offset``
    #: on (None = all ``num_experts``, and then nothing here changes).  The
    #: router keeps its ``num_experts`` outputs and ``top_k`` choices; the
    #: expert weights are ``[experts_held, ...]``, the plan is made over the
    #: rows routed to them (at most ``held_rows_bound`` of them; the rest
    #: are counted) and a token's other choices add nothing — expert
    #: parallelism's share of a layer, without its exchange.  Grouped
    #: dispatch only.
    expert_offset: int = 0
    experts_held: Optional[int] = None
    #: ``held_rows_bound`` is this many times the held experts' even share
    #: of the routed rows: 2 where a router spreads its tokens about
    #: evenly; a router whose experts' loads differ several-fold (sigmoid
    #: scores over un-gated relu2 experts at initialisation) needs more.
    #: It buys safety with memory (the plan's ``[bound, ·]`` buffers), not
    #: with time: dispatch, the grouped kernels and combine walk the rows
    #: that are routed here and stop (``grouped_gemm.live_rows``), and an
    #: exchange's receive buffer is initialised a tile a held expert
    #: (``grouped_gemm.zeroed_padding``), whatever the bound
    held_rows_factor: int = 2
    #: width of a shared expert every token passes through beside the
    #: routed ones (0 = none), added to their sum; ``shared_expert_gate``
    #: scales it by ``sigmoid(x . w)`` per token (Qwen3-Next)
    shared_expert_d_ff: int = 0
    shared_expert_gate: bool = False

    @classmethod
    def of(cls, config, **own) -> "MoEConfig":
        """The expert layers of a model family's ``config``: every field
        here that ``config`` carries under the same name (widths, expert
        and choice counts, loss coefficients, the share held), then what
        the family fixes or names differently (``own``: the router, the
        activation, the dispatch)."""
        shared = {f.name: getattr(config, f.name) for f in fields(cls)
                  if hasattr(config, f.name)}
        return cls(**{**shared, **own})

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def holds_subset(self) -> bool:
        return self.held != self.num_experts or self.expert_offset != 0


def init_moe_params(config: MoEConfig, rng) -> dict:
    E, D, F = config.held, config.d_model, config.d_ff
    k = iter(jax.random.split(rng, 5))
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    params = {
        "router": norm(next(k), (D, config.num_experts)) * std,
        "w_in": norm(next(k), (E, D, F)) * std,
        "w_out": norm(next(k), (E, F, D)) * std,
    }
    if config.activation == "silu_glu":
        params["w_gate"] = norm(next(k), (E, D, F)) * std
    if config.router == "sigmoid":
        params["e_score_correction_bias"] = jnp.zeros((config.num_experts,))
    if config.shared_expert_d_ff:
        # off a branch of its own, as the residual FFN below
        sk = iter(jax.random.split(jax.random.fold_in(rng, 23), 4))
        Fs = config.shared_expert_d_ff
        params["shared_in"] = norm(next(sk), (D, Fs)) * std
        params["shared_out"] = norm(next(sk), (Fs, D)) * std
        if config.activation == "silu_glu":
            params["shared_gate"] = norm(next(sk), (D, Fs)) * std
        if config.shared_expert_gate:
            params["shared_router"] = norm(next(sk), (D, 1)) * std
    if config.use_residual:
        # dense residual FFN + the 2-way mixing coefficient head; keys
        # fold off a branch so plain-MoE seeded init stays byte-identical
        rk = iter(jax.random.split(jax.random.fold_in(rng, 17), 4))
        params["res_in"] = norm(next(rk), (D, F)) * std
        params["res_out"] = norm(next(rk), (F, D)) * std
        params["coef_w"] = norm(next(rk), (D, 2)) * std
        params["coef_b"] = jnp.zeros((2,))
        if config.activation == "silu_glu":
            params["res_gate"] = norm(next(rk), (D, F)) * std
    return params


def moe_logical_specs(config: MoEConfig) -> dict:
    specs = {
        "router": P(),
        "w_in": P(EXPERT_AXIS, None, "model"),
        "w_out": P(EXPERT_AXIS, "model", None),
    }
    if config.activation == "silu_glu":
        specs["w_gate"] = P(EXPERT_AXIS, None, "model")
    if config.router == "sigmoid":
        specs["e_score_correction_bias"] = P()
    if config.shared_expert_d_ff:
        specs["shared_in"] = P(None, "model")
        specs["shared_out"] = P("model", None)
        if config.activation == "silu_glu":
            specs["shared_gate"] = P(None, "model")
        if config.shared_expert_gate:
            specs["shared_router"] = P()
    if config.use_residual:
        specs["res_in"] = P(None, "model")
        specs["res_out"] = P("model", None)
        specs["coef_w"] = P()
        specs["coef_b"] = P()
        if config.activation == "silu_glu":
            specs["res_gate"] = P(None, "model")
    return specs


# ------------------------------------------------------ dispatch resolution
#: serving-config override slot (``serving.moe_dispatch``); None = defer
_dispatch_override: Optional[str] = None

DISPATCH_MODES = ("auto", "einsum", "grouped")


def set_dispatch_override(mode: Optional[str]):
    """Install the serving config's dispatch choice (None resets).  The
    resolution order is DS_MOE_DISPATCH env > this override > the layer
    config's ``dispatch_mode`` (scheduler installs it at construction,
    mirroring ``serving.quant_scan_threshold_mb``)."""
    global _dispatch_override
    if mode is not None and mode not in DISPATCH_MODES:
        raise ValueError(f"moe dispatch mode {mode!r}: choose one of "
                         f"{DISPATCH_MODES}")
    _dispatch_override = mode


@contextlib.contextmanager
def dispatch_scope(mode: Optional[str]):
    """Force a dispatch mode for code TRACED inside this scope (A/B
    benches and parity tests; same trace-time caveat as qgemm_scope)."""
    global _dispatch_override
    prev = _dispatch_override
    set_dispatch_override(mode)
    try:
        yield
    finally:
        _dispatch_override = prev


def resolve_dispatch_mode(config: MoEConfig, train: bool) -> str:
    """-> "einsum" | "grouped" for this call (see set_dispatch_override).
    A grouped request stays grouped on an ``expert`` mesh axis of any
    width: wider than one, the layer exchanges its rows
    (:func:`_exchanged_grouped_moe`)."""
    env = os.environ.get("DS_MOE_DISPATCH")
    mode = env or _dispatch_override or config.dispatch_mode or "auto"
    if mode not in DISPATCH_MODES:
        raise ValueError(f"moe dispatch mode {mode!r}: choose one of "
                         f"{DISPATCH_MODES}")
    if mode == "auto":
        if train or expert_axis_size() > 1:
            # (auto never picks the exchange: it is asked for)
            mode = "einsum"
        elif gg_kernel_real() or jax.device_count() == 1:
            mode = "grouped"
        else:
            # multi-device host where only the ragged_dot REFERENCE
            # would run (e.g. eval inside a TP/DP training mesh): the
            # reference's argsort/gather carries none of the einsum
            # path's sharding pins, so auto keeps the sharded einsum
            # formulation; an EXPLICIT grouped request still wins
            # (single-device serving programs on a multi-device host —
            # the test/bench surface)
            mode = "einsum"
    return mode


def expert_axis_size() -> int:
    """Chips of the ``expert`` mesh axis (1: every chip holds every expert
    it is told it holds, and no row leaves it)."""
    return dict(get_topology().mesh.shape).get(EXPERT_AXIS, 1)


# ------------------------------------------------------------- telemetry
#: metrics registry tap (ISSUE 8 satellite): when installed at TRACE
#: time, moe_layer emits ``moe/dispatch_tokens`` / ``moe/dropped_tokens``
#: counters and a ``moe_drop_fraction`` gauge through a host callback
#: (einsum mode reports real capacity drops; grouped mode pins drops to
#: 0).  Off by default — the per-step host callback is observability
#: overhead serving opts into (ds_serve wires its /metrics registry).
_metrics_registry = None


def set_moe_metrics_registry(registry):
    global _metrics_registry
    _metrics_registry = registry


def _report_routing(dispatched, dropped):
    reg = _metrics_registry
    if reg is None:
        return
    d, p = float(dispatched), float(dropped)
    reg.inc("moe/dispatch_tokens", d)
    reg.inc("moe/dropped_tokens", p)
    total = d + p
    reg.set_gauge("moe_drop_fraction", (p / total) if total else 0.0)


def _emit_routing_stats(dispatched, dropped):
    """Host-callback bridge (trace-time gated on the installed tap)."""
    if _metrics_registry is None:
        return
    jax.debug.callback(_report_routing, dispatched, dropped)


def _report_router_health(entropy, load, max_frac, dead, aux, z):
    """Registry half of the router-health tap (ISSUE 15 satellite):
    routing entropy, per-expert load fractions, the hottest expert's
    share, a dead-expert counter, and the aux/z loss gauges — the
    collapsed-router signal a loss curve can't show."""
    import numpy as np
    reg = _metrics_registry
    if reg is None:
        return
    reg.set_gauge("moe/router_entropy", float(entropy))
    reg.set_gauge("moe/expert_load_max_fraction", float(max_frac))
    reg.inc("moe/dead_experts", float(dead))
    reg.set_gauge("moe/aux_loss", float(aux))
    reg.set_gauge("moe/z_loss", float(z))
    for i, f in enumerate(np.asarray(load)):
        reg.set_gauge("moe/expert_load_fraction", float(f),
                      expert=str(i))


def _report_held_plan(live, plan_rows):
    reg = _metrics_registry
    if reg is None:
        return
    reg.set_gauge(HELD_LIVE_ROWS, float(live))
    reg.set_gauge(HELD_PLAN_ROWS, float(plan_rows))


def _emit_held_plan(load):
    """How much of a held plan is live, through the registry tap alone (as
    the router's health: traced only when a tap is installed; a train step
    returns the same record, :func:`step_load`, beside its loss): per
    expert layer that runs, :data:`HELD_LIVE_ROWS` — ``used_blocks`` tiles
    of rows, what dispatch, the grouped kernels and combine walk — beside
    :data:`HELD_PLAN_ROWS`, the plan's static length."""
    if _metrics_registry is None:
        return
    jax.debug.callback(_report_held_plan, load[HELD_LIVE_ROWS],
                       load[HELD_PLAN_ROWS])


def _report_exchanged(sent, received, wire, routed):
    reg = _metrics_registry
    if reg is None:
        return
    reg.set_gauge(EXCHANGE_ROWS_SENT, float(sent))
    reg.set_gauge(EXCHANGE_ROWS_RECEIVED, float(received))
    reg.set_gauge(EXCHANGE_WIRE_ROWS_PER_ROUTED_ROW,
                  float(wire) / float(routed))


def _emit_exchanged(load, routed):
    """Beside :func:`_emit_held_plan`, through the registry tap alone: the
    (token, chip) rows this chip sent and received, and of those it sent
    the ones that left it — the wire's — a routed (token, expert) row."""
    if _metrics_registry is None:
        return
    jax.debug.callback(_report_exchanged, load[EXCHANGE_ROWS_SENT],
                       load[EXCHANGE_ROWS_RECEIVED],
                       load[EXCHANGE_WIRE_ROWS], jnp.int32(routed))


def _emit_router_health(logits, routing, config: MoEConfig):
    """Host-callback bridge for router health, armed only with the
    registry tap (the PR 8 contract: observability overhead serving /
    monitoring opts into).  Values derive from the SAME topk_routing
    decision both dispatch formulations consume, so einsum and grouped
    publish identical numbers (parity-tested)."""
    if _metrics_registry is None:
        return
    from deepspeed_tpu.moe.sharded_moe import router_health
    entropy, load, max_frac, dead = router_health(
        logits, routing, config.num_experts)
    jax.debug.callback(
        _report_router_health, entropy, load, max_frac, dead,
        routing.l_aux * config.aux_loss_coef, routing.router_z_loss)


def _dq(w, dt):
    """Expert weight -> compute dtype.  QuantizedTensor leaves reach the
    einsum path only when a grouped-mode keep-quantized decision was
    later overridden (mode mix-ups, EP fallback) — dequantize in place
    rather than crash; the grouped path consumes them natively."""
    from deepspeed_tpu.models.model import QuantizedTensor
    if isinstance(w, QuantizedTensor):
        from deepspeed_tpu.ops.pallas.quantization import \
            block_dequantize_int8
        return block_dequantize_int8(w.q, w.s).astype(dt)
    return w.astype(dt)


def _expert_ffn(params, x, config: MoEConfig):
    """x: [E, C', D] — per-expert token slots; one vmapped FFN per expert.

    The gate operand is passed explicitly as ``None`` for non-GLU
    activations (ISSUE 8 satellite): the old ``params.get("w_gate",
    params["w_in"])`` default vmapped an unused [E, D, F] operand
    through gelu-mode experts — wasted HBM reads under remat."""
    dt = x.dtype

    if config.activation == "silu_glu":
        def one(w_in, w_out, w_gate, xe):
            h = jax.nn.silu(xe @ w_gate) * (xe @ w_in)
            return h @ w_out
        return jax.vmap(one)(_dq(params["w_in"], dt),
                             _dq(params["w_out"], dt),
                             _dq(params["w_gate"], dt), x)

    def one(w_in, w_out, xe):
        return _ungated(xe @ w_in, config) @ w_out

    return jax.vmap(one)(_dq(params["w_in"], dt),
                         _dq(params["w_out"], dt), x)


def _grouped_moe(params, xt, config: MoEConfig, train: bool, rng):
    """Megablocks-style drop-free dispatch (ISSUE 8 tentpole): sort the
    [T·k] routed (token, choice) pairs by expert into a group-padded
    layout, run the expert FFN as grouped GEMMs over it
    (ops/pallas/grouped_gemm.py — zero capacity padding, no [T, E, C]
    tensors), and sum each token's k outputs.  The rows move by gathers
    only, through the plan's two index maps: one from the token-major
    ``xt`` straight into the padded layout (``dispatch_rows``: no
    [T·k, D] copy), one back out of the experts' output (``sum_rows``),
    each the other's backward — no scatter is traced here.  **A row is
    weighted by its gate where its expert is**: the gates ride the plan's
    sort into plan order (a float32 a row, 0 on a padding row; their
    cotangent is sorted home) and the activation between the two products
    forms ``gate · act(...)`` in float32 and rounds once (:func:`_glu`),
    so the way back is un-gated and keeps no row: a rematerialised layer's
    recompute ends at the activation — no output product, no sum — and a
    gate's cotangent is the row sum of ``dh · act`` in the pass that forms
    the halves' cotangents.  (The decode-sized branch, which has no plan,
    weights its rows on the way back.)  Returns (combined [T, D], aux
    scalar, (dispatched, dropped, the call's :func:`step_load`))."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    T, D = xt.shape
    E, k = config.num_experts, config.top_k
    dt = xt.dtype
    with jax.named_scope(SCOPE_ROUTER):
        logits = _routing_logits(params, xt, config)
        routing = _route(params, logits, config, train, rng)
    _emit_router_health(logits, routing, config)
    eids = routing.expert_idx.reshape(-1)               # [T*k]
    gates = routing.gate_weights.reshape(-1)            # [T*k] fp32

    w_gate = params.get("w_gate")
    w_in, w_out = params["w_in"], params["w_out"]

    R, load = T * k, {}
    if expert_axis_size() > 1:
        return _exchanged_grouped_moe(params, xt, config, routing, eids,
                                      gates)
    if config.holds_subset:
        return _held_grouped_moe(params, xt, config, routing, eids, gates)
    if gg_kernel_real() and not train and R <= gg.SLOT_MAX_ROWS:
        # decode/verify-sized: the slot kernels stream each DISTINCT
        # routed expert's weights exactly once — the top-k-distinct
        # expert floor — over the raw rows in flat routed order: no
        # plan and no group-padded layout at all
        with jax.named_scope(SCOPE_DISPATCH):
            rows = jnp.repeat(xt, k, axis=0)            # [T*k, D]
            plan = gg.make_slot_plan(eids, E)
        mm = partial(gg.ds_ggemm_slots, plan=plan, out_dtype=dt)
        with jax.named_scope(SCOPE_EXPERTS):
            y = mm(_glu(mm, rows, w_gate, w_in, config), w_out)
        with jax.named_scope(SCOPE_COMBINE):
            combined = jnp.sum(
                (gates.astype(dt)[:, None] * y).reshape(T, k, D), axis=1)
    else:
        with jax.named_scope(SCOPE_DISPATCH):
            plan = gg.make_group_plan(eids, E,
                                      gates=gates.astype(jnp.float32))
            x_pad = gg.dispatch_rows(xt, plan, k)       # [Mp, D]
        # both are shapes: the step's own account of what its grouped
        # calls compute (rows past the routed ones are zeros)
        count_in_step(grouped_routed_rows=R,
                      grouped_padded_rows=plan.padded_rows)
        load = step_load(plan, R, E)
        mm = partial(gg.ds_ggemm, plan=plan, out_dtype=dt)
        with jax.named_scope(SCOPE_EXPERTS):
            h = _glu(mm, x_pad, w_gate, w_in, config,
                     row_gate=plan.gates[:, None])
            y = mm(h, w_out)                            # [Mp, D]
        with jax.named_scope(SCOPE_COMBINE):
            combined = gg.sum_rows(y, plan, k)
    aux = routing.l_aux * config.aux_loss_coef + routing.router_z_loss
    return combined, aux, (jnp.int32(R), jnp.int32(0), load)


def _held_grouped_moe(params, xt, config: MoEConfig, routing, eids, gates):
    """The grouped formulation over the experts held here
    (``MoEConfig.experts_held``): the routing is over all experts, the
    plan over the rows whose expert is one of ours, inside a static bound
    (``grouped_gemm.held_rows_bound``); the rows go out by gathers and
    come back summed into their tokens, and nothing here makes a pass over
    the bound where the bound is mostly empty: every step walks the plan's
    live prefix, a chunk at a time, and the way back the tokens, a block
    at a time (the kernel ``ds_rowsum``: ``grouped_gemm``).
    Rows over the bound are the
    second number of the statistics (``moe_layer(..., return_stats=True)``;
    the model hands their sum to the engine: :data:`ROWS_OVER_BOUND`)."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    T, D = xt.shape
    k, dt = config.top_k, xt.dtype
    R = T * k
    bound = gg.held_rows_bound(R, config.held, config.num_experts,
                               factor=config.held_rows_factor)
    with jax.named_scope(SCOPE_DISPATCH):
        plan, over = gg.make_held_group_plan(
            eids, config.expert_offset, config.held, bound)
        x_pad = gg.dispatch_held_rows(xt, plan, k)          # [Mp, D]
    load = step_load(plan, R, config.num_experts)
    _emit_held_plan(load)
    # shapes all: ``grouped_routed_rows`` is the EXPECTED number of held
    # rows under even routing (``moe/even_rows``); the true one is data and
    # leaves the step beside it: ``moe/routed_rows`` (:func:`step_load`)
    count_in_step(grouped_routed_rows=R * config.held // config.num_experts,
                  grouped_padded_rows=plan.padded_rows,
                  held_rows_bound=bound, experts_held=config.held,
                  experts_routed=config.num_experts)
    mm = partial(gg.ds_ggemm, plan=plan, out_dtype=dt)
    with jax.named_scope(SCOPE_EXPERTS):
        h = _glu(mm, x_pad, params.get("w_gate"), params["w_in"], config,
                 plan)
        y = mm(h, params["w_out"])                          # [Mp, D]
    with jax.named_scope(SCOPE_COMBINE):
        combined = gg.combine_held_rows(y, gates, plan, k)
    aux = routing.l_aux * config.aux_loss_coef + routing.router_z_loss
    return combined, aux, (load[ROUTED_ROWS] - over, over, load)


def _exchanged_grouped_moe(params, xt, config: MoEConfig, routing, eids,
                           gates):
    """The grouped formulation with the experts spread over the ``expert``
    mesh axis: chip ``d`` of its ``n`` holds experts ``[d * E / n, (d + 1)
    * E / n)``.  The routing (``routing``, ``eids``, ``gates``: over all
    experts and the whole batch) is the caller's; from there on every mesh
    axis is manual, and each chip, for the ``t`` tokens it holds (the same
    number on every chip: rows of zero gate make it up where the tokens do
    not split evenly, a decode step's).  **A row on the wire is a (token,
    destination chip)**: a token's row crosses to a chip once, whatever
    number of that chip's experts it chose — at most ``n`` rows a token,
    one of them its own chip's, where a row a (token, expert) sent ``k``
    (:func:`_rows_to_experts` is steps 1 to 4):

    1. lays its tokens' rows out **by chip** — for each chip the tokens
       that chose at least one of its experts, in token order: a held plan
       of the ``t * n`` (token, chip) elements over the ``n`` chips
       (``make_held_group_plan``, ``dispatch_held_rows``), ``n * t`` rows
       at most, a bound that is also the worst case — and, **by expert**
       over all ``E`` (a held plan of its ``t * k`` routed elements), a
       float32 pair a routed row: its gate and the place its token's row
       lands in at the expert's chip (``scatter_to_groups``); learns from
       one small all-gather how many rows every chip has for every expert
       and for every chip, and from that table, by cumulative sums, every
       chip's layouts (``mappings.make_exchange_sizes``), its own receive
       plan among them (``make_counted_group_plan``: no sort, no look-up);
    2. ``exchange/exchange_send``: one all-to-all of the rows
       (``lax.ragged_all_to_all``: the rows there are and no padding), one
       slice a pair of chips, into a landing buffer ``[n * t, D]`` nobody
       filled — sender ``j``'s rows from ``j * t`` on, a slot no other
       sender reaches, so no row can find the wire or the buffer full;
       and a second, narrow one, **the lanes**: ``[rows, 128]`` float32
       (:data:`_GATE_LANES`: a ``[rows, 2]`` array travels as wide, and is
       re-laid on both sides of its call), one slice a (chip, expert), a
       row a (token, expert) — lane 0 its gate, lane 1 its landed place
       from 1 on, so a gate of exact 0.0 is still a chosen expert — which
       lands inside its expert's group of the receiver's plan, behind the
       rows of the senders before: what arrives IS a group-padded array,
       in a buffer one small kernel zeroed the groups' last tiles of
       (their padding rows: ``grouped_gemm.zeroed_padding``).  A chip's
       plan has room for a stated bound of (token, expert) rows from all
       chips together: ``held_rows_factor`` times what it is sent under
       even routing (``grouped_gemm.held_rows_bound``) — not a bound on
       what one chip sends another: one sender's skew uses the room the
       others leave.  A row that finds no room is counted
       (:data:`ROWS_OVER_BOUND`), never silently lost: the room goes to the
       senders in their order, and a pair's last rows — those of its
       highest experts — are the ones cut;
    3. **expands the landed rows where the experts are**: the plan's rows
       are gathered out of the landing buffer by the places the lanes
       brought (``gather_landed_rows``, over the plan's live prefix; a
       padding row reads zeros) — the receiving chip sorts nothing;
    4. runs the grouped kernels over the plan.  **The gate is applied
       here, where the experts are**: the live-prefix pass between the two
       halves forms ``gate · act(...)`` in float32 and rounds once
       (:func:`_glu`), so the output product is of weighted rows;
    5. **sums where the experts are**: a token's weighted output rows on
       this chip are summed by landed row, in float32, rounded once
       (``sum_into_landed_rows``: ``ds_rowsum`` with the landed rows for
       its tokens) — ``[n * t, D]``, a row a (token, sender);
    6. ``exchange/exchange_return``: one all-to-all of those sums, slice
       for slice, each row to the place it came from;
    7. sums a token's at most ``n`` rows, once, in float32
       (``sum_held_rows`` over its plan by chip: ``ds_rowsum`` with no
       gates).

    Backward, every step is its own transpose (an all-to-all's is the
    all-to-all back; a sum's is the gather it undoes): two all-to-alls of
    rows and one of lanes' cotangents a pass.  No row that came back is a
    residual of anything — a gate's cotangent is the row sum of ``dh · act``
    in the pass that forms the halves' cotangents, on the expert's chip, and
    travels home through the narrow exchange's transpose — so a
    rematerialised layer's recompute ends at the activation: no output
    product, no return, no sum.  Five all-to-alls of rows a layer-pass
    (forward 2, recompute 1, backward 2) and three of lanes.  The expert
    weights come in as this chip's ``[E / n, ...]`` slices and their
    gradients leave so — reduced over no chip of the ``expert`` axis.
    Returns as :func:`_held_grouped_moe` does, the counts — of (token,
    expert) rows — and the load summed over the chips."""
    from deepspeed_tpu.moe import mappings
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.utils.jax_compat import shard_map
    topo = get_topology()
    mesh = topo.mesh
    n = expert_axis_size()
    T, D = xt.shape
    E, k, dt = config.num_experts, config.top_k, xt.dtype
    tok_axes = tuple(topo.zero_shard_axes)
    chips = topo.axis_size(tok_axes)
    if config.holds_subset or E % n or mesh.shape.get("model", 1) > 1:
        raise ValueError(
            f"moe: the exchange over a {n}-wide expert axis spreads all "
            f"{E} experts evenly (no held subset: {config.held} from "
            f"{config.expert_offset}) and is not built for a model axis "
            f"wider than one ({dict(mesh.shape)})")
    held, t_chip = E // n, -(-T // chips)
    # tokens that do not split evenly over the chips that hold tokens (a
    # decode step of a small batch): rows of zeros with gates of zero make
    # up the number, their choices spread over the experts, and are cut
    # off again at the end
    pad = t_chip * chips - T
    eids, gates = eids.reshape(T, k), gates.reshape(T, k)
    if pad:
        xt = jnp.concatenate([xt, jnp.zeros((pad, D), dt)])
        eids = jnp.concatenate([eids, (jnp.arange(
            pad * k, dtype=eids.dtype) % E).reshape(pad, k)])
        gates = jnp.concatenate([gates, jnp.zeros((pad, k), gates.dtype)])
    R = t_chip * k
    # n chips send a chip R (token, expert) rows each, 1 / n of them under
    # even routing
    bound = gg.held_rows_bound(n * R, held, E,
                               factor=config.held_rows_factor)
    # the landing buffer: a row a (token, sender), a slot of t_chip a sender
    landed_rows = n * t_chip
    if landed_rows >= 1 << 24:
        raise ValueError(
            f"moe: a landed row's place travels as a float32, exact below "
            f"2**24 ({landed_rows} rows land on a chip)")
    row_bytes = D * jnp.dtype(dt).itemsize
    count_in_step(
        # of one chip; the routed rows are those it EXPECTS to receive
        grouped_routed_rows=R,
        grouped_padded_rows=bound + held * gg.default_block_m(),
        held_rows_bound=bound, experts_held=held, experts_routed=E,
        exchange_calls={f"{t_chip}x{D}:{n}": {
            "pairs": n, "experts_held": held, "tokens": t_chip,
            "routed_rows": R, "receive_rows": bound, "width": D,
            # what a row on the wire is: a token's, once a chip it has an
            # expert on — never more than the tokens a chip holds to each
            # other chip, whatever the routing, so the wire is never full
            "row_unit": "token_chip", "landed_rows": landed_rows,
            "wire_rows_bound": (n - 1) * t_chip,
            "wire_bytes": (n - 1) * t_chip * row_bytes,
            # what a call writes of a plan-sized receive buffer (the
            # lanes') before its rows arrive: the last tile of each held
            # expert's group, where the group's padding rows lie — behind
            # the live prefix nothing; of the rows' landing buffer nothing
            "receive_fill": "padding_tiles",
            "zeroed_rows_per_call": held * gg.default_block_m(),
            "even_rows_per_pair": R // n,
            "path": mappings.exchange_path(),
            # of the lanes: a (chip, expert) is one slice of the narrow
            # all-to-all, and lands in its expert's group of the receiver's
            # plan; of the rows one slice a pair
            "slices_per_pair": held, "receive_layout": "grouped",
            # what a rematerialised layer runs of them: the recompute
            # needs the rows it received and their lanes, and nothing
            # that came back
            "row_calls_per_pass": {"forward": 2, "recompute": 1,
                                   "backward": 2},
            "gate_calls_per_pass": {"forward": 1, "recompute": 1,
                                    "backward": 1}}})
    weights = {name: params[name] for name in ("w_gate", "w_in", "w_out")
               if name in params}

    def on_chip(xt, eids, gates, weights):
        out = _rows_to_experts(xt, eids, gates, E, bound)
        plan, by_chip, sizes = out.plan, out.by_chip, out.sizes
        load = step_load(plan, n * R, E, sizes)
        _emit_held_plan(load)
        _emit_exchanged(load, R)
        mm = partial(gg.ds_ggemm, plan=plan, out_dtype=dt)
        with jax.named_scope(SCOPE_EXPERTS):
            h = _glu(mm, out.x_pad, weights.get("w_gate"), weights["w_in"],
                     config, plan, out.lanes)
            y = mm(h, weights["w_out"])                     # [Mp, D]
        with jax.named_scope(SCOPE_COMBINE):
            summed = gg.sum_into_landed_rows(
                y, plan, out.source, (sizes.first, sizes.end), landed_rows)
        with jax.named_scope(SCOPE_EXCHANGE), jax.named_scope(SCOPE_RETURN):
            back = mappings.exchange_back(summed, sizes.rows,
                                          by_chip.padded_rows)
        with jax.named_scope(SCOPE_COMBINE):
            combined = _after(gg.sum_held_rows(back, by_chip, n),
                              jax.lax.stop_gradient(h[0, 0]))
        dropped = sizes.over + out.over
        return combined, jnp.stack(
            [jnp.int32(R) - sizes.over, dropped,
             *(load[name] for name in STEP_LOAD)])[None]

    tok = P(tok_axes)
    combined, counts = shard_map(
        on_chip, mesh=mesh,
        in_specs=(tok, tok, tok, jax.tree.map(lambda _: P(EXPERT_AXIS),
                                              weights)),
        out_specs=(tok, P(tuple(mesh.axis_names))), check_vma=False)(
            xt, eids, gates, weights)
    counts = jnp.sum(counts, axis=0).astype(jnp.int32)
    aux = routing.l_aux * config.aux_loss_coef + routing.router_z_loss
    load = dict(zip(STEP_LOAD, counts[2:]))
    if pad:
        return combined[:T], aux, (counts[0] - pad * k, counts[1], load)
    return combined, aux, (counts[0], counts[1], load)


class _AtTheExperts(NamedTuple):
    """What :func:`_rows_to_experts` leaves on a chip."""
    x_pad: jnp.ndarray          # [Mp, D] the receive plan's rows
    lanes: jnp.ndarray          # [Mp, lanes] float32: gate, landed place
    source: jnp.ndarray         # [Mp] the landed row behind a plan row
    plan: object                # the receive plan (``GroupPlan``)
    by_chip: object             # the rows' send layout (``GroupPlan``)
    sizes: object               # ``mappings.ExchangeSizes``
    over: jnp.ndarray           # [] rows the receive plan itself cut


def _rows_to_experts(xt, eids, gates, num_experts, bound) -> _AtTheExperts:
    """The way out of :func:`_exchanged_grouped_moe` (its steps 1 to 4), on
    one chip of the ``expert`` axis inside the ``shard_map``: this chip's
    tokens ``xt`` [t, D] with their choices ``eids`` / ``gates`` [t, k] ->
    the rows its experts multiply, in its receive plan of ``bound`` rows."""
    from deepspeed_tpu.moe import mappings
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    n = jax.lax.axis_size(EXPERT_AXIS)
    me = jax.lax.axis_index(EXPERT_AXIS)
    (t_chip, k), E = eids.shape, int(num_experts)
    held, R, landed_rows = E // n, t_chip * k, n * t_chip
    with jax.named_scope(SCOPE_DISPATCH):
        chosen = jnp.any(eids[:, :, None] == jnp.arange(E, dtype=eids.dtype),
                         axis=1)                            # [t, E]
        sizes = mappings.make_exchange_sizes(
            chosen, R, bound, gg.landed_block_rows(landed_rows))
        plan, over = gg.make_counted_group_plan(sizes.counts, bound)
        # the rows' send layout: a token's row once a chip it chose an
        # expert of — a held plan of (token, chip) elements over the chips,
        # every one of which finds room
        by_chip, _ = gg.make_held_group_plan(jnp.where(
            sizes.to_chip, jnp.arange(n, dtype=jnp.int32), n).reshape(-1),
            0, n, landed_rows)
        buf = gg.dispatch_held_rows(xt, by_chip, n)         # [n t + n bm, D]
        # the lanes': a row a routed element, by expert (R rows hold every
        # row a chip routes: nothing is over here).  Where an element's
        # row lands at its expert's chip: its token's place among those
        # its chip sends there, in this sender's slot — from 1 on, 0 being
        # no row (a padding row's)
        mine, _ = gg.make_held_group_plan(eids.reshape(-1), 0, E, R,
                                          row_to_padded=True)
        lands = 1 + me * t_chip + jnp.sum(jnp.where(
            (eids // held)[:, :, None] == jnp.arange(n, dtype=eids.dtype),
            sizes.at[:, None, :], 0), axis=-1)              # [t, k]
        # a gate and a place a row, as wide as the narrowest row the chip
        # moves whole (a ``[rows, 2]`` array is padded to it anyway, and
        # then re-laid on either side of its all-to-all)
        lane_buf = jnp.pad(gg.scatter_to_groups(jnp.stack(
            [gates.astype(jnp.float32), jax.lax.stop_gradient(
                lands.astype(jnp.float32))], axis=-1).reshape(R, 2), mine),
            ((0, 0), (0, _GATE_LANES - 2)))
    with jax.named_scope(SCOPE_EXCHANGE), jax.named_scope(SCOPE_SEND):
        landed = mappings.exchange_forth(buf, sizes.rows, landed_rows)
        lanes = mappings.exchange_forth(lane_buf, sizes.lanes,
                                        plan.padded_rows, "gates",
                                        counts=sizes.counts)
    with jax.named_scope(SCOPE_DISPATCH):
        source = _landed_source(lanes, plan, landed_rows)
        x_pad = gg.gather_landed_rows(landed, plan, source,
                                      (sizes.first, sizes.end))
    return _AtTheExperts(x_pad, lanes, source, plan, by_chip, sizes, over)


#: the name a model gives the rows over ``held_rows_bound``, summed over its
#: layers, among its step counts (``Model.loss_with_counts_fn``): they leave
#: the step beside the loss, and the engine adds them up under this name
#: (``engine.step_counts()``, the registry's ``train/step_counts``) and
#: warns of a step in which it is not zero
ROWS_OVER_BOUND = "moe/rows_over_bound"
#: gauges of the registry tap (:func:`_emit_held_plan`), never step counts:
#: the rows of a held plan's live prefix, and of the plan
HELD_LIVE_ROWS = "moe/held_live_rows"
HELD_PLAN_ROWS = "moe/held_plan_rows"
#: and, where the layer exchanges (:func:`_exchanged_grouped_moe`): the
#: rows a chip sent to the chips of the ``expert`` axis (itself among them)
#: and those it received
EXCHANGE_ROWS_SENT = "moe/exchange_rows_sent"
EXCHANGE_ROWS_RECEIVED = "moe/exchange_rows_received"
#: and what of the sent rows crossed to another chip, a routed (token,
#: expert) row: a token's row crosses to a chip once, so (n - 1) / k at most
EXCHANGE_WIRE_ROWS_PER_ROUTED_ROW = "moe/exchange_wire_rows_per_routed_row"
#: the rest of a routed step's load (:func:`step_load`), which leaves a
#: train step as the five above and :data:`ROWS_OVER_BOUND` do: the (token,
#: choice) rows sent to the experts held here before any bound, what that
#: is under even routing, one expert's even share, the fullest held
#: expert's rows; the sent rows that left their chip; and, the same on
#: every chip of an exchange and so counted once: the rows the fullest
#: chip's plan received, and the mean over the chips
ROUTED_ROWS = "moe/routed_rows"
EVEN_ROWS = "moe/even_rows"
EVEN_EXPERT_ROWS = "moe/even_expert_rows"
FULLEST_EXPERT_ROWS = "moe/fullest_expert_rows"
EXCHANGE_WIRE_ROWS = "moe/exchange_wire_rows"
FULLEST_CHIP_ROWS = "moe/fullest_chip_rows"
MEAN_CHIP_ROWS = "moe/mean_chip_rows"
#: the load facts in the order they ride, and the int32 sums one expert
#: layer-call hands the layer loop of its model (:func:`layer_sums`)
STEP_LOAD = (ROUTED_ROWS, EVEN_ROWS, EVEN_EXPERT_ROWS, FULLEST_EXPERT_ROWS,
             HELD_LIVE_ROWS, HELD_PLAN_ROWS, EXCHANGE_ROWS_SENT,
             EXCHANGE_ROWS_RECEIVED, EXCHANGE_WIRE_ROWS, FULLEST_CHIP_ROWS,
             MEAN_CHIP_ROWS)
STEP_SUMS = (ROWS_OVER_BOUND,) + STEP_LOAD


def step_load(plan, routed: int, num_experts: int, sizes=None) -> dict:
    """What the router did to one expert layer-call, {name: int32}: of the
    ``routed`` (token, choice) rows routed over all ``num_experts`` by
    everyone who sends to ``plan`` — the plan of the experts held here, the
    receive plan of an exchange whose ``sizes`` (``ExchangeSizes``) then
    add the wire's.  The ONE record of both sinks: a train step returns it
    (``moe_layer(return_stats=True)["load"]``, summed over layers,
    micro-batches and chips where :data:`ROWS_OVER_BOUND` is, to
    ``engine.step_load()``), the registry tap reads its gauges from it by
    callback.  Every value a sum's term: ratios are made on the host."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import live_rows
    load = {ROUTED_ROWS: jnp.sum(plan.counts),
            EVEN_ROWS: routed * plan.num_experts // num_experts,
            EVEN_EXPERT_ROWS: (routed + num_experts // 2) // num_experts,
            FULLEST_EXPERT_ROWS: jnp.max(plan.counts),
            HELD_LIVE_ROWS: live_rows(plan),
            HELD_PLAN_ROWS: plan.padded_rows}
    if sizes is not None:
        me = jax.lax.axis_index(EXPERT_AXIS)
        sent = jnp.sum(sizes.rows.send)
        # the table is every chip's: its two numbers leave from one
        once = (me == 0).astype(jnp.int32)
        load.update({
            # a plan's counts are what its senders kept inside the bound
            ROUTED_ROWS: load[ROUTED_ROWS] + sizes.over,
            EXCHANGE_ROWS_SENT: sent,
            EXCHANGE_ROWS_RECEIVED: jnp.sum(sizes.rows.held),
            EXCHANGE_WIRE_ROWS: sent - sizes.rows.send[me],
            FULLEST_CHIP_ROWS: once * jnp.max(sizes.chip_rows),
            MEAN_CHIP_ROWS: once * (jnp.sum(sizes.chip_rows)
                                    // sizes.chip_rows.shape[0])})
    return {name: jnp.asarray(v, jnp.int32) for name, v in load.items()}


def layer_sums(stats: dict):
    """[len(:data:`STEP_SUMS`)] int32 of one layer-call's ``stats``
    (``moe_layer(return_stats=True)``): the rows it left out, then its
    load, 0 where its path has no such fact — what a model's layer loop
    adds up, and :func:`named_sums` names again."""
    return jnp.stack([stats["dropped"].astype(jnp.int32)] + [
        stats["load"].get(name, jnp.int32(0)) for name in STEP_LOAD])


def named_sums(sums) -> dict:
    """{name: int32 scalar} of :func:`layer_sums` added up: what a model's
    ``loss_with_counts_fn`` returns beside its loss."""
    return dict(zip(STEP_SUMS, sums))


def _route(params, logits, config: MoEConfig, train: bool, rng):
    """The one selection both dispatch formulations consume."""
    return topk_routing(
        logits, config.top_k,
        rng if (train and config.noisy_gate_policy) else None,
        config.z_loss_coef, normalize=config.norm_topk_prob,
        load_balance=config.load_balance, router=config.router,
        selection_bias=params.get("e_score_correction_bias"),
        scale=config.routed_scaling_factor)


def _ungated(h, config: MoEConfig):
    """The activation of an expert of two matrices."""
    if config.activation == "relu2":
        return jnp.square(jax.nn.relu(h))
    return jax.nn.gelu(h, approximate=True)


def _silu_glu(gate, up):
    return jax.nn.silu(gate) * up


#: lanes of the float32 array that carries a routed row's gate to its
#: expert's chip: a row of 128 float32 is the narrowest the chip's
#: all-to-all moves as it lies
_GATE_LANES = 128


def _landed_source(lanes, plan, landed_rows):
    """[Mp] int32: the landed row behind each row of the receive plan, as
    lane 1 of the lanes that arrived says it — from 1 on, 0 on a padding
    row (its tile was zeroed) — and ``landed_rows``, which reads zeros and
    sums into nothing, on a padding row and behind the live prefix, where
    the lanes hold whatever the buffer held."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import live_rows
    place = jax.lax.stop_gradient(lanes[:, 1])
    row = jnp.arange(plan.padded_rows, dtype=jnp.int32)
    there = (row < live_rows(plan)) & (place >= 1) & (place <= landed_rows)
    return jnp.where(there, place, landed_rows + 1).astype(jnp.int32) - 1


def _row_weighted(fn):
    """``fn`` of a plan's rows in float32, each row times its weight (lane
    0 of the last operand, ``[rows, lanes]`` float32), rounded once to the
    rows' dtype."""
    def weighted(*operands):
        *xs, weight = operands
        h = fn(*(x.astype(jnp.float32) for x in xs))
        return (weight[:, :1] * h).astype(xs[0].dtype)
    return weighted


@jax.custom_vjp
def _after(x, token):
    """``x``, whose cotangent waits for ``token`` — a value of the
    rematerialised forward, here an element of the activation.  Nothing of
    the exchanged layer's backward pass depends on its recompute any more,
    and a scheduler left free starts it first: the ``[Mp, D]`` cotangent
    of the returned rows is then live all through the recompute's own
    exchange (the described compile of the cell's loss and gradient:
    1.2 GiB more; the step on the chips: 0.32).  The compiler expands an
    ``optimization_barrier`` before it schedules, so the wait is
    arithmetic it cannot fold: the cotangent plus zero times the token,
    made finite first."""
    return x


def _after_bwd(token, g):
    wait = 0.0 * jnp.clip(jnp.nan_to_num(token.astype(jnp.float32)), -1, 1)
    return g + wait.astype(g.dtype), jnp.zeros_like(token)


_after.defvjp(lambda x, token: (x, token), _after_bwd)


def _glu(mm, x, w_gate, w_in, config: MoEConfig, plan=None, row_gate=None):
    """The experts' first half over the plan's rows ``x``.  Given a held
    ``plan``, what XLA does between the grouped calls — the activation,
    and in the backward pass the sum of the two cotangents of ``x`` —
    walks the plan's live prefix as they do; given a ``row_gate``
    (``[Mp, lanes]`` float32, lane 0 a routed row's gate where its expert
    is: :func:`_grouped_moe`'s full plan, :func:`_exchanged_grouped_moe`)
    the activation's pass weights each row by it, and its backward forms
    the gates' cotangent beside the halves'."""
    glu = config.activation == "silu_glu"
    fn = _silu_glu if glu else partial(_ungated, config=config)
    gated = ()
    if row_gate is not None:
        fn, gated = _row_weighted(fn), (row_gate,)
    if plan is None:
        halves = (mm(x, w_gate), mm(x, w_in)) if glu else (mm(x, w_in),)
        return fn(*halves, *gated)
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    if glu:
        x_gate, x_in = gg.fan_out_live_rows(x, plan, 2)
        halves = (mm(x_gate, w_gate), mm(x_in, w_in))
    else:
        halves = (mm(x, w_in),)
    return gg.map_live_rows(fn, plan, *halves, *gated)


def gg_kernel_real() -> bool:
    """Whether ds_ggemm will run the actual Pallas kernels (single TPU
    device, or interpret mode forced) rather than the jnp reference —
    the scan-threshold and keep-quantized decisions key on this (the
    qgemm_kernel_real contract)."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import _use_reference
    use_ref, _ = _use_reference(None)
    return not use_ref


def _routing_logits(params, xt, config: MoEConfig):
    """Router matmul shared by both dispatch modes (qdot: int8 serving
    keeps the 2-D router quantized for the fused-dequant qgemm)."""
    from deepspeed_tpu.models.model import qdot
    return qdot(xt.astype(jnp.float32), params["router"])


def moe_layer(params: dict, x: jnp.ndarray, config: MoEConfig,
              train: bool = True, rng=None, return_stats: bool = False):
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar), and with
    ``return_stats`` a third: ``{"dispatched", "dropped"}``, int32 counts
    of routed rows computed and left out (einsum: past capacity; a held
    subset: past ``held_rows_bound``), and ``"load"``: what the call's
    path has of :func:`step_load`.

    einsum mode: the reference's MOELayer.forward (sharded_moe.py:477)
    step-for-step, with einsum dispatch in place of explicit
    all_to_all_single calls.  grouped mode: see :func:`_grouped_moe`.
    """
    B, S, D = x.shape
    T = B * S
    mesh = get_topology().mesh
    # layout pins for the SPMD partitioner; the serving scheduler's
    # single-device programs shed them via sharding_pin_scope(False)
    # (comm/mesh.py — a training-mesh pin inside a device-local program
    # miscompiles on this jaxlib)
    from deepspeed_tpu.comm.mesh import pin_sharding as wsc
    # token dim = flattened (batch-sharded, seq-sharded) dims: pin every
    # token-major tensor to the same layout so the SPMD partitioner never
    # falls back to replicate-then-repartition on the backward transposes
    tok = P(tuple(get_topology().zero_shard_axes))
    tok_sh = jax.sharding.NamedSharding(mesh, tok)
    xt = wsc(x.reshape(T, D), tok_sh)
    mode = resolve_dispatch_mode(config, train)
    if mode == "grouped":
        combined, aux, (n_disp, n_drop, load) = _grouped_moe(
            params, xt, config, train, rng)
        _emit_routing_stats(n_disp, n_drop)
        moe_out = wsc(combined, tok_sh).reshape(B, S, D)
        out = _finish_residual(params, x, moe_out, aux, config)
        return out + ({"dispatched": n_disp, "dropped": n_drop,
                       "load": load},) if return_stats else out
    if config.holds_subset:
        raise ValueError(
            f"moe: a held subset of the experts ({config.held} of "
            f"{config.num_experts}) runs through the grouped dispatch "
            f"only; this call resolved to {mode!r} (dispatch_mode="
            f"{config.dispatch_mode!r}, expert mesh axis, DS_MOE_DISPATCH)")
    # qdot: int8 serving keeps the (stacked-2-D) router quantized — the
    # fused-dequant qgemm consumes it; plain arrays take the same matmul
    cf = config.capacity_factor if train else config.eval_capacity_factor
    noise = rng if (train and config.noisy_gate_policy) else None
    # selection runs ONCE and feeds both the capacity tensors and the
    # router-health tap — the grouped path consumes the same decision,
    # so the two modes publish bitwise-identical health numbers
    with jax.named_scope(SCOPE_ROUTER):
        logits = wsc(_routing_logits(params, xt, config), tok_sh)
        routing = _route(params, logits, config, train, rng)
    _emit_router_health(logits, routing, config)
    gate: GateOutput = topkgating(logits, config.top_k, cf,
                                  config.min_capacity, noise,
                                  config.z_loss_coef, routing=routing)
    combine_w = wsc(gate.combine_weights, tok_sh)
    dispatch_m = wsc(gate.dispatch_mask, tok_sh)
    kept = jnp.sum(dispatch_m.astype(jnp.int32))
    n_drop = jnp.int32(T * config.top_k) - kept
    _emit_routing_stats(kept, n_drop)
    # dispatch: [T,E,C] x [T,D] -> [E,C,D]  (token->expert all-to-all)
    dispatched = jnp.einsum("tec,td->ecd",
                            dispatch_m.astype(x.dtype), xt)
    dispatched = wsc(dispatched,
                     jax.sharding.NamedSharding(mesh, P(EXPERT_AXIS)))
    out = _expert_ffn(params, dispatched, config)          # [E, C, D]
    out = wsc(out, jax.sharding.NamedSharding(mesh, P(EXPERT_AXIS)))
    # combine: [T,E,C] x [E,C,D] -> [T,D]  (expert->token all-to-all)
    combined = wsc(jnp.einsum("tec,ecd->td",
                              combine_w.astype(x.dtype), out), tok_sh)
    aux = gate.l_aux * config.aux_loss_coef + gate.router_z_loss
    moe_out = combined.reshape(B, S, D)
    out = _finish_residual(params, x, moe_out, aux, config)
    if not return_stats:
        return out
    # no plan: the rows routed, the fullest expert's, and the slots the
    # capacity tensors hold (the even numbers are the routed ones')
    R, E = T * config.top_k, config.num_experts
    fullest = jnp.max(jnp.sum(
        routing.expert_idx.reshape(-1, 1) == jnp.arange(E), axis=0))
    load = {ROUTED_ROWS: R, EVEN_ROWS: R, EVEN_EXPERT_ROWS: (R + E // 2) // E,
            FULLEST_EXPERT_ROWS: fullest,
            HELD_PLAN_ROWS: E * dispatch_m.shape[-1]}
    return out + ({"dispatched": kept, "dropped": n_drop, "load": {
        name: jnp.asarray(v, jnp.int32) for name, v in load.items()}},)


def _finish_residual(params, x, moe_out, aux, config: MoEConfig):
    from deepspeed_tpu.models.model import qdot
    if config.shared_expert_d_ff:
        with jax.named_scope(SCOPE_SHARED_EXPERT):
            moe_out = moe_out + _shared_expert(params, x, config)
    if config.use_residual:
        # Residual MoE (reference moe/layer.py:116-123): dense FFN beside
        # the experts, mixed by a learned per-token softmax coefficient
        dt = x.dtype
        if config.activation == "silu_glu":
            h = (jax.nn.silu(qdot(x, params["res_gate"]))
                 * qdot(x, params["res_in"]))
        else:
            h = _ungated(qdot(x, params["res_in"]), config)
        res = qdot(h, params["res_out"])
        coef = jax.nn.softmax(
            (qdot(x, params["coef_w"])
             + params["coef_b"].astype(dt)).astype(jnp.float32), axis=-1)
        coef = coef.astype(dt)
        moe_out = moe_out * coef[..., 0:1] + res * coef[..., 1:]
    return moe_out, aux


def _shared_expert(params, x, config: MoEConfig):
    """The expert every token passes through, whole on every chip; with
    ``shared_expert_gate`` scaled per token by ``sigmoid(x . w)``."""
    from deepspeed_tpu.models.model import qdot
    if config.activation == "silu_glu":
        h = jax.nn.silu(qdot(x, params["shared_gate"])) \
            * qdot(x, params["shared_in"])
    else:
        h = _ungated(qdot(x, params["shared_in"]), config)
    out = qdot(h, params["shared_out"])
    if config.shared_expert_gate:
        gate = jax.nn.sigmoid(
            qdot(x, params["shared_router"]).astype(jnp.float32))
        out = out * gate.astype(out.dtype)
    return out


@dataclass
class MoE:
    """API-parity bundle (reference deepspeed.moe.layer.MoE)."""
    config: MoEConfig
    params: Optional[dict] = None

    def init(self, rng):
        self.params = init_moe_params(self.config, rng)
        return self.params

    def __call__(self, x, params=None, train=True, rng=None):
        return moe_layer(params or self.params, x, self.config, train, rng)


def is_moe_param_path(path: tuple) -> bool:
    """True for param-tree paths under a MoE experts subtree (reference
    moe/utils.py is_moe_param uses an ``allreduce=False`` tag; here the tree
    path carries the information)."""
    return any(getattr(p, "key", None) in ("w_in", "w_out", "w_gate", "moe")
               for p in path)
