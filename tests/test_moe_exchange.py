"""The expert-parallel exchange of the grouped dispatch
(moe/layer.py ``_exchanged_grouped_moe``, moe/mappings.py) on a four-device
host mesh, at toy size on the CPU: the exchanged layer against the
one-device grouped layer over all experts — output, ``dx``, every ``dw``,
the router's gradient; the guide's sum-of-shares test (the four held
shares' partial results, each from ``experts_held`` / ``expert_offset``
alone with no exchange, add up to the uncut layer written plainly, and so
does the exchanged layer); a planted skew that passes a bound is counted,
not dropped silently, and the rows that were kept give every gradient
theirs; the gates' own gradient — formed where the experts are, sent home
by the narrow exchange's transpose — against ``<dcombined, y>`` written
plainly; two all-to-alls of rows and one of gates a pass, five and three
under rematerialisation, and no capacity einsum in the compiled text; a
one-wide ``expert`` axis never reaches the
exchange; the table every chip derives its slices from, against the same
written as loops; the receive buffer against the parent's (rows by sender,
then sorted and gathered into the held plan); the device gate.

With ``real_kernels`` the grouped kernels and ``ds_rowsum`` run in Pallas'
interpreter inside the exchange's manual region; elsewhere their jnp forms
stand in."""
import math
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import (MeshTopology, reset_topology,
                                     set_topology)
from deepspeed_tpu.moe import layer as moe_layer_module
from deepspeed_tpu.moe import mappings
from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params, moe_layer,
                                     moe_logical_specs,
                                     resolve_dispatch_mode)
from deepspeed_tpu.telemetry import tracing

D, F, E, K = 32, 16, 8, 2
B, S = 8, 8
#: float32 lanes of the array that carries the gates beside the rows
LANES = moe_layer_module._GATE_LANES
CONFIG = MoEConfig(d_model=D, d_ff=F, num_experts=E, top_k=K,
                   dispatch_mode="grouped", aux_loss_coef=1e-2,
                   load_balance="all_choices", held_rows_factor=4)


@pytest.fixture(autouse=True)
def _isolation():
    tracing.reset_programs()
    yield
    reset_topology()
    tracing.reset_programs()


@pytest.fixture
def real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")


def params_and_x(config=CONFIG, seed=0, router_scale=20.0):
    params = init_moe_params(config, jax.random.PRNGKey(seed))
    params = {k: v * (router_scale if k == "router" else 5.0)
              for k, v in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, D))
    return params, x


def weighted(config):
    """A scalar of the layer's output and router loss whose gradient
    reaches every element differently."""
    def loss(params, x):
        out, aux, stats = moe_layer(params, x, config, train=True,
                                    return_stats=True)
        w = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
            out.shape)
        return jnp.sum(out * w) + aux, (out, stats)
    return loss


def on_one_device(config, params, x):
    set_topology(MeshTopology(devices=jax.devices()[:1]))
    return jax.jit(jax.value_and_grad(weighted(config), argnums=(0, 1),
                                      has_aux=True))(params, x)


def four_wide(config, params, x, data=1):
    """(the jitted function, its arguments placed) on expert 4 x data."""
    topo = MeshTopology(devices=jax.devices()[:4 * data],
                        expert_parallel_size=4)
    set_topology(topo)
    placed = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(topo.mesh, s)),
        params, moe_logical_specs(config))
    xs = jax.device_put(x, NamedSharding(
        topo.mesh, P(tuple(topo.data_parallel_axes))))
    return jax.jit(jax.value_and_grad(weighted(config), argnums=(0, 1),
                                      has_aux=True)), (placed, xs)


def host(tree):
    return jax.tree.map(np.asarray, tree)


@pytest.mark.parametrize("kernels", ["jnp_forms", "interpreted_kernels"])
@pytest.mark.parametrize("data", [1, 2])
def test_the_exchanged_layer_is_the_one_device_layer(kernels, data,
                                                     monkeypatch):
    if kernels == "interpreted_kernels":
        monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    params, x = params_and_x()
    (want, (want_out, want_stats)), want_grads = host(
        on_one_device(CONFIG, params, x))
    fn, args = four_wide(CONFIG, params, x, data=data)
    (got, (out, stats)), grads = host(fn(*args))
    assert int(stats["dropped"]) == 0 == int(want_stats["dropped"])
    assert int(stats["dispatched"]) == B * S * K
    assert abs(got - want) < 1e-5 * abs(want)
    np.testing.assert_allclose(out, want_out, atol=1e-5)
    for name in ("router", "w_gate", "w_in", "w_out"):
        scale = np.abs(want_grads[0][name]).max()
        assert scale > 0
        np.testing.assert_allclose(grads[0][name], want_grads[0][name],
                                   atol=1e-5 * scale, err_msg=name)
    np.testing.assert_allclose(grads[1], want_grads[1],
                               atol=1e-5 * np.abs(want_grads[1]).max())


def plain_layer(params, x, config):
    """The uncut layer written plainly: softmax, the top k, their weights
    over their sum, every token through its chosen experts by a dense
    masked sum — no plan, no sort, no exchange."""
    h = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(h @ params["router"], axis=-1)
    _, chosen = jax.lax.top_k(probs, config.top_k)
    sent = jax.nn.one_hot(chosen, config.num_experts).sum(1)
    weights = probs * sent
    weights = weights / weights.sum(-1, keepdims=True)
    every = jnp.einsum(
        "tef,efd->ted",
        jax.nn.silu(jnp.einsum("td,edf->tef", h, params["w_gate"]))
        * jnp.einsum("td,edf->tef", h, params["w_in"]), params["w_out"])
    return jnp.einsum("te,ted->td", weights, every).reshape(x.shape)


def test_the_shares_and_the_exchange_add_up_to_the_uncut_layer(real_kernels):
    """The guide's test: chip d's share (experts 2d, 2d + 1, from
    ``experts_held`` / ``expert_offset`` alone, no exchange) for d = 0..3
    add up to the uncut layer; the exchanged layer IS that sum, forward and
    in ``x``'s and the weights' gradients."""
    params, x = params_and_x()
    set_topology(MeshTopology(devices=jax.devices()[:1]))
    with jax.default_matmul_precision("highest"):
        want = plain_layer(params, x, CONFIG)
        want_grads = jax.grad(lambda p, x: jnp.sum(
            plain_layer(p, x, CONFIG) ** 2), argnums=(0, 1))(params, x)
    total = jnp.zeros_like(x)
    grads = jax.tree.map(jnp.zeros_like, (params, x))
    for d in range(4):
        share = replace(CONFIG, experts_held=2, expert_offset=2 * d)
        held = {k: (w[2 * d:2 * d + 2] if k != "router" else w)
                for k, w in params.items()}
        part, _, stats = moe_layer(held, x, share, return_stats=True)
        assert int(stats["dropped"]) == 0
        total = total + part
    np.testing.assert_allclose(total, want, atol=1e-5 * float(
        jnp.abs(want).max()))

    def squared(p, x):
        return jnp.sum(moe_layer(p, x, CONFIG)[0] ** 2)

    topo = MeshTopology(devices=jax.devices()[:4], expert_parallel_size=4)
    set_topology(topo)
    placed = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(topo.mesh, s)),
        params, moe_logical_specs(CONFIG))
    xs = jax.device_put(x, NamedSharding(topo.mesh, P("expert")))
    exchanged = jax.jit(lambda p, x: moe_layer(p, x, CONFIG)[0])(placed, xs)
    np.testing.assert_allclose(np.asarray(exchanged), want, atol=1e-5 * float(
        jnp.abs(want).max()))
    grads = host(jax.jit(jax.grad(squared, argnums=(0, 1)))(placed, xs))
    for name in ("router", "w_gate", "w_in", "w_out"):
        np.testing.assert_allclose(
            grads[0][name], want_grads[0][name], err_msg=name,
            atol=2e-5 * float(jnp.abs(want_grads[0][name]).max()))
    np.testing.assert_allclose(grads[1], want_grads[1], atol=2e-5 * float(
        jnp.abs(want_grads[1]).max()))


def test_a_skew_past_the_bound_is_counted_not_dropped_silently(monkeypatch):
    """Every token's two choices land on chip 0's experts: at factor 1 a
    chip has room for the rows even routing sends it, a quarter of what
    arrives here — the rest is counted, by the chips whose rows found no
    room, and the tokens whose rows were kept still get those rows'
    results."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    config = replace(CONFIG, held_rows_factor=1)
    params, x = params_and_x(config)
    params["router"] = jnp.zeros((D, E)).at[:, :2].set(1.0)
    x = jnp.abs(x)                          # experts 0 and 1 win everywhere
    fn, args = four_wide(config, params, x)
    (_, (out, stats)), _ = host(fn(*args))
    rows = B * S * K
    assert gg.held_rows_bound(rows, 2, E, factor=1) == rows // 4 == 32
    # chip 0 has room for 32 rows: the first sender's, nobody else's
    assert int(stats["dispatched"]) == 32
    assert int(stats["dropped"]) == rows - 32
    kept_tokens = np.abs(out.reshape(B * S, D)).sum(-1) > 0
    assert kept_tokens.sum() == 32 // K
    assert kept_tokens[:16].all() and not kept_tokens[16:].any()


def test_one_senders_skew_uses_the_room_the_others_leave(monkeypatch):
    """The bound is on what a chip receives in all: chip 0's tokens all
    choose chip 0's experts (four times a pair's even share), the other
    chips' tokens none of them — and at factor 1 none of chip 0's is over."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    config = replace(CONFIG, held_rows_factor=1)
    params, x = params_and_x(config)
    x = jnp.abs(x)
    # chip 0 holds the first 16 tokens: theirs to experts 0 and 1, every
    # other token's to experts 2 and 3 (chip 1)
    first = (jnp.arange(B * S) < 16).reshape(B, S, 1)
    x = jnp.concatenate([jnp.where(first, 1.0, 0.0),
                         jnp.where(first, 0.0, 1.0), x[..., 2:]], axis=-1)
    router = jnp.zeros((D, E)).at[0, :2].set(50.0).at[1, 2:4].set(50.0)
    params["router"] = router
    fn, args = four_wide(config, params, x)
    (_, (out, stats)), _ = host(fn(*args))
    rows = B * S * K
    # chip 1 is sent 96 rows and has room for 32; chip 0 is sent 32, all
    # by itself — four times what one chip sends another under even routing
    assert int(stats["dropped"]) == 96 - 32
    assert int(stats["dispatched"]) == rows - 64
    # chip 0's own rows (32: its whole room, from one sender) all kept
    assert (np.abs(out.reshape(B * S, D)[:16]).sum(-1) > 0).all()


def test_two_row_all_to_alls_a_pass_and_no_capacity_einsum(monkeypatch):
    """On a TPU the exchange is ``lax.ragged_all_to_all``; the CPU has no
    such collective and moves the same rows by ``lax.all_to_all``
    (``mappings._ragged``).  Counted here at the call: forward two of rows
    (out of the sender's layout, back out of the receiver's) and the gates
    beside the rows out, a float32 a row over 128 lanes; backward the three
    cotangents'
    — and nothing of the capacity formulation in the text."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    calls = []
    ragged = mappings._ragged

    def counting(rows, *a, **k):
        calls.append(rows.shape)
        return ragged(rows, *a, **k)

    monkeypatch.setattr(mappings, "_ragged", counting)
    params, x = params_and_x()
    fn, args = four_wide(CONFIG, params, x)
    with tracing.step_account("toy"):
        text = fn.lower(*args).compile().as_text()
    tokens = B * S
    routed = tokens // 4 * K
    bound = gg.held_rows_bound(4 * routed, 2, E,
                               factor=CONFIG.held_rows_factor)
    tile = gg.default_block_m()
    # the rows leave a plan of (token, chip) elements over the four chips
    # and land a row a (token, sender); the lanes leave a plan of the
    # routed elements over all experts and land in the plan of those held
    landed = tokens
    sent = -(-landed // tile) * tile + 4 * tile
    lanes_sent = -(-routed // tile) * tile + E * tile
    received = bound + E // 4 * tile
    assert sorted(calls) == sorted(2 * [(sent, D), (landed, D)]
                                   + [(lanes_sent, LANES),
                                      (received, LANES)]), calls
    assert not re.search(rf"\[(?:{tokens}|{tokens // 4}),{E},\d+\]", text)
    (call,) = tracing.exchange_calls("toy")
    assert call["pairs"] == 4 and call["experts_held"] == 2
    assert call["tokens"] == tokens // 4 and call["routed_rows"] == routed
    assert call["receive_rows"] == bound
    # what a row on the wire is, and how many a call can put there at most
    assert call["row_unit"] == "token_chip"
    assert call["landed_rows"] == landed
    assert call["wire_rows_bound"] == 3 * (tokens // 4)
    assert call["wire_bytes"] == 3 * (tokens // 4) * D * 4
    # what a call initialises of a plan-sized buffer: a tile a held expert
    assert call["receive_fill"] == "padding_tiles"
    assert call["zeroed_rows_per_call"] == E // 4 * tile
    assert call["row_calls_per_pass"] == {"forward": 2, "recompute": 1,
                                          "backward": 2}
    assert call["gate_calls_per_pass"] == {"forward": 1, "recompute": 1,
                                           "backward": 1}
    # the account names the collective that was traced: here the stand-in
    assert call["path"] == mappings.exchange_path() == "all_to_all"
    table = tracing.parse_program_text(text)
    scopes = [row["scope"] for row in table.values()
              if row["collective"] and "/exchange/" in (row["scope"] or "")]
    assert scopes and all(
        re.search(r"/exchange/exchange_(send|return)/", s)
        for s in scopes), scopes
    assert {row["collective"] for row in table.values()
            if "/exchange/" in (row["scope"] or "")
            and row["collective"]} == {"all-to-all"}


def _output_alone(config):
    """As :func:`weighted` without the router's loss: what the rows that
    came back, and nothing else, give every leaf."""
    def loss(params, x):
        out, _, stats = moe_layer(params, x, config, train=True,
                                  return_stats=True)
        w = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
            out.shape)
        return jnp.sum(out * w), (out, stats)
    return loss


def _unequal_gates():
    """A router so sharp that a token's first gate is most of its weight."""
    params, x = params_and_x(router_scale=60.0)
    probs = jax.nn.softmax(x.reshape(-1, D) @ params["router"], axis=-1)
    top = jax.lax.top_k(probs, K)[0]
    assert float(jnp.mean(top[:, 0] / top.sum(-1))) > 0.8
    want = on_one_device(CONFIG, params, x)
    fn, args = four_wide(CONFIG, params, x)
    return want, fn(*args), B * S * K, 0


def _zero_gate_padding_tokens():
    """Three tokens over four chips: the fourth chip's is a row of zeros
    whose gates are zero, sent, multiplied and summed like any other."""
    from deepspeed_tpu.comm.mesh import sharding_pin_scope
    params, whole = params_and_x()
    x = whole[:1, :3]
    want = on_one_device(CONFIG, params, x)
    fn, (placed, _) = four_wide(CONFIG, params, whole)
    with sharding_pin_scope(False):
        return want, fn(placed, x), 3 * K, 0


def _rows_over_a_tight_bound():
    """Every token chooses chip 0's two experts, the first with 0.62 of
    its weight: at factor 1 chip 0 has room for its own 16 tokens' rows and
    nobody else's.  The other 48 tokens' rows are counted, and add nothing
    to the output or to any gradient — the gates' among them: what is
    left is the plain layer over the first 16 tokens."""
    config = replace(CONFIG, held_rows_factor=1, aux_loss_coef=0.0)
    params, x = params_and_x(config)
    params["router"] = jnp.zeros((D, E)).at[:, 0].set(0.05).at[:, 1].set(
        0.03)
    x = jnp.abs(x)
    kept = (jnp.arange(B * S) < 16).reshape(B, S, 1)

    def plainly(params, x):
        out = plain_layer(params, x, config) * kept
        w = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
            out.shape)
        return jnp.sum(out * w), (out, {"dropped": 48 * K})

    set_topology(MeshTopology(devices=jax.devices()[:1]))
    want = jax.jit(jax.value_and_grad(plainly, argnums=(0, 1),
                                      has_aux=True))(params, x)
    fn, args = four_wide(config, params, x)
    return want, jax.jit(jax.value_and_grad(
        _output_alone(config), argnums=(0, 1), has_aux=True))(*args), \
        16 * K, 48 * K


@pytest.mark.parametrize("case", [_unequal_gates, _zero_gate_padding_tokens,
                                  _rows_over_a_tight_bound],
                         ids=lambda case: case.__name__.lstrip("_"))
def test_every_gradient_with_the_gate_applied_at_the_experts(case,
                                                             monkeypatch):
    """The exchanged layer against the layer on one device (and, where
    rows are cut, against the plain layer over the tokens that were kept):
    output, counts, and every gradient by name — the router's, which
    reaches it through the gates alone, and ``w_out``'s, whose product now
    takes weighted rows."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    ((want, (want_out, _)), want_grads), ((got, (out, stats)), grads), \
        dispatched, dropped = host(case())
    assert int(stats["dispatched"]) == dispatched
    assert int(stats["dropped"]) == dropped
    np.testing.assert_allclose(got, want, rtol=2e-5)
    np.testing.assert_allclose(out, want_out, atol=2e-5 * np.abs(
        want_out).max())
    for name in ("router", "w_gate", "w_in", "w_out"):
        scale = np.abs(want_grads[0][name]).max()
        assert scale > 0, name
        np.testing.assert_allclose(grads[0][name], want_grads[0][name],
                                   atol=2e-5 * scale, err_msg=name)
    np.testing.assert_allclose(grads[1], want_grads[1],
                               atol=2e-5 * np.abs(want_grads[1]).max())


def test_the_gates_gradient_is_the_returned_row_dot_its_tokens_cotangent(
        monkeypatch):
    """``dgates[t, j] = <dcombined[t], y[t, j]>`` with ``y`` the un-gated
    output of token ``t``'s ``j``-th expert, written plainly in float32 —
    against the exchanged layer's, which never forms that dot: the
    expert's chip sums ``dh · silu(gate) · up`` over the 16 hidden columns
    and the narrow exchange's transpose carries the number home.  Read as
    the gradient by an array of zeros added to the chosen gates."""
    params, x = params_and_x()
    route = moe_layer_module._route

    def loss(params, x, nudge):
        def nudged(*args, **kwargs):
            routing = route(*args, **kwargs)
            return routing._replace(gate_weights=routing.gate_weights
                                    + nudge.reshape(-1, K))
        monkeypatch.setattr(moe_layer_module, "_route", nudged)
        out = moe_layer(params, x, CONFIG, train=True)[0]
        monkeypatch.setattr(moe_layer_module, "_route", route)
        w = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
            out.shape)
        return jnp.sum(out * w)

    _, (placed, xs) = four_wide(CONFIG, params, x)
    got = np.asarray(jax.jit(jax.grad(loss, argnums=2))(
        placed, xs, jax.device_put(jnp.zeros((B, S, K)), xs.sharding))
    ).reshape(-1, K)
    h = x.reshape(-1, D)
    chosen = np.asarray(route(params, h @ params["router"], CONFIG, True,
                              None).expert_idx)
    every = jnp.einsum(
        "tef,efd->ted",
        jax.nn.silu(jnp.einsum("td,edf->tef", h, params["w_gate"]))
        * jnp.einsum("td,edf->tef", h, params["w_in"]), params["w_out"])
    y = np.take_along_axis(np.asarray(every), chosen[:, :, None], axis=1)
    dcombined = np.cos(np.arange(h.size, dtype=np.float32)).reshape(h.shape)
    want = np.einsum("td,tkd->tk", dcombined, y)
    assert np.abs(want).min() > 0
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


class _Gauges:
    """A registry tap that keeps every value a gauge was set to."""
    def __init__(self):
        self.seen = {}

    def set_gauge(self, name, value, **labels):
        self.seen.setdefault(name, []).append(value)

    def inc(self, name, value=1.0, **labels):
        pass


def _planted_choices(monkeypatch, choose, gate=None):
    """The layer's (params, x) with every token's two experts planted:
    ``choose(token)`` -> its pair, ``gate(token)`` -> its gates or None for
    the router's own."""
    params, x = params_and_x()
    chosen = jnp.asarray([choose(t) for t in range(B * S)], jnp.int32)
    route = moe_layer_module._route

    def planted(*a, **k):
        routing = route(*a, **k)._replace(expert_idx=chosen)
        if gate is None:
            return routing
        mask, value = (jnp.asarray([gate(t)[i] for t in range(B * S)])
                       for i in range(2))
        return routing._replace(gate_weights=jnp.where(
            mask, value.astype(jnp.float32), routing.gate_weights))

    monkeypatch.setattr(moe_layer_module, "_route", planted)
    return params, x


@pytest.mark.parametrize("case, choose, crossing", [
    # a token's two experts are one chip's, the next chip's: it crosses once
    ("both_on_the_next_chip",
     lambda t: (2 * ((t // 16 + 1) % 4), 2 * ((t // 16 + 1) % 4) + 1), 64),
    # one expert at home, one on the next chip
    ("one_at_home", lambda t: (2 * (t // 16), 2 * ((t // 16 + 1) % 4)), 64),
    # both at home: nothing on the wire
    ("both_at_home", lambda t: (2 * (t // 16), 2 * (t // 16) + 1), 0),
    # two other chips
    ("two_other_chips",
     lambda t: (2 * ((t // 16 + 1) % 4), 2 * ((t // 16 + 2) % 4) + 1), 128),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_token_crosses_to_a_chip_once(case, choose, crossing, monkeypatch):
    """The rows on the wire are the distinct (token, other chip) pairs —
    ``moe/exchange_wire_rows_per_routed_row`` times the routed rows, summed
    over the four chips — whatever number of a chip's experts a token
    chose, and the layer is the one-device layer all the same."""
    params, x = _planted_choices(monkeypatch, choose)
    (want, (want_out, _)), want_grads = host(on_one_device(CONFIG, params, x))
    gauges = _Gauges()
    monkeypatch.setattr(moe_layer_module, "_metrics_registry", gauges)
    fn, args = four_wide(CONFIG, params, x)
    (got, (out, stats)), grads = host(fn(*args))
    jax.effects_barrier()
    shares = gauges.seen[moe_layer_module.EXCHANGE_WIRE_ROWS_PER_ROUTED_ROW]
    # forward alone sets them: one value a chip
    assert len(shares) == 4
    routed = B * S // 4 * K
    assert round(sum(shares) * routed) == crossing
    assert all(share * routed <= 3 * (B * S // 4) for share in shares)
    assert sum(gauges.seen[moe_layer_module.EXCHANGE_ROWS_SENT]) \
        == sum(gauges.seen[moe_layer_module.EXCHANGE_ROWS_RECEIVED]) \
        == crossing + (64 if "at_home" in case else 0)
    assert int(stats["dispatched"]) == B * S * K and not int(stats["dropped"])
    np.testing.assert_allclose(out, want_out, atol=1e-5 * np.abs(
        want_out).max())
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=1e-5 * np.abs(w).max())


def test_every_token_to_every_chip_fills_the_wire_and_no_more(monkeypatch):
    """Top 4 of 8 experts, one on each chip: every token crosses to the
    three other chips, which is all a wire can be asked to carry — the
    bound the step's account states — where a row a (token, expert) would
    have put the same three on it, and top 8 of 8 no more."""
    for k in (4, 8):
        config = replace(CONFIG, top_k=k)
        params, x = params_and_x(config)
        chosen = jnp.asarray([[(2 * c + t) % E if k == 4 else c
                               for c in range(k)] for t in range(B * S)],
                             jnp.int32)
        route = moe_layer_module._route
        monkeypatch.setattr(
            moe_layer_module, "_route",
            lambda *a, _route=route, **kw: _route(*a, **kw)._replace(
                expert_idx=chosen))
        want = host(on_one_device(config, params, x))
        gauges = _Gauges()
        monkeypatch.setattr(moe_layer_module, "_metrics_registry", gauges)
        fn, args = four_wide(config, params, x)
        with tracing.step_account("toy"):
            got = host(fn(*args))
        jax.effects_barrier()
        monkeypatch.setattr(moe_layer_module, "_route", route)
        monkeypatch.setattr(moe_layer_module, "_metrics_registry", None)
        (call,) = tracing.exchange_calls("toy")
        tracing.reset_programs()
        shares = gauges.seen[
            moe_layer_module.EXCHANGE_WIRE_ROWS_PER_ROUTED_ROW]
        wire = [round(share * (B * S // 4) * k) for share in shares]
        assert wire == 4 * [call["wire_rows_bound"]] == 4 * [3 * 16]
        # (the load is four plans' against one plan's: not the layer's)
        for tree in (got, want):
            del tree[0][1][1]["load"]
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(g, w, rtol=2e-4,
                                       atol=2e-5 * np.abs(w).max())


def test_a_gate_of_exact_zero_is_still_a_chosen_expert(monkeypatch):
    """Every third token's first gate is exactly 0.0 (and a padding
    token's gates all are): the row is sent, multiplied and counted like
    any other — the place a row landed in travels beside its gate, not in
    it — and the gate's own gradient is the row's dot with its token's
    cotangent, not zero."""
    zeroed = lambda t: ((t % 3 == 0, False), (0.0, 0.0))    # noqa: E731
    params, x = _planted_choices(
        monkeypatch, lambda t: (t % 8, (t + 3) % 8), zeroed)
    (want, (want_out, want_stats)), want_grads = host(
        on_one_device(CONFIG, params, x))
    fn, args = four_wide(CONFIG, params, x)
    (got, (out, stats)), grads = host(fn(*args))
    assert int(stats["dispatched"]) == B * S * K \
        == int(want_stats["dispatched"])
    assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(out, want_out, atol=1e-5 * np.abs(
        want_out).max())
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=1e-5 * np.abs(w).max())
    # the gates' own gradient, by an array of zeros added to them
    route = moe_layer_module._route

    def loss(params, x, nudge):
        monkeypatch.setattr(
            moe_layer_module, "_route", lambda *a, **k: (
                lambda r: r._replace(gate_weights=r.gate_weights
                                     + nudge.reshape(-1, K)))(route(*a, **k)))
        out = moe_layer(params, x, CONFIG, train=True)[0]
        monkeypatch.setattr(moe_layer_module, "_route", route)
        return jnp.sum(out * jnp.cos(jnp.arange(
            out.size, dtype=jnp.float32)).reshape(out.shape))

    nudge = jnp.zeros((B, S, K))
    set_topology(MeshTopology(devices=jax.devices()[:1]))
    want_dgates = np.asarray(jax.jit(jax.grad(loss, argnums=2))(
        params, x, nudge)).reshape(-1, K)
    _, (placed, xs) = four_wide(CONFIG, params, x)
    dgates = np.asarray(jax.jit(jax.grad(loss, argnums=2))(
        placed, xs, jax.device_put(nudge, xs.sharding))).reshape(-1, K)
    assert np.abs(want_dgates[::3, 0]).min() > 0
    np.testing.assert_allclose(dgates, want_dgates,
                               atol=2e-5 * np.abs(want_dgates).max())


@pytest.mark.parametrize("config", [
    replace(CONFIG, top_k=1), replace(CONFIG, num_experts=4),
    replace(CONFIG, num_experts=4, top_k=1)],
    ids=["top_1", "an_expert_a_chip", "top_1_an_expert_a_chip"])
def test_top_one_and_one_expert_a_chip_run(config, real_kernels):
    """The same code where a token has one row anyway (``k = 1``) and
    where a chip is an expert (``E / n = 1``): what it sent before."""
    params, x = params_and_x(config)
    (want, (want_out, want_stats)), want_grads = host(
        on_one_device(config, params, x))
    fn, args = four_wide(config, params, x)
    (got, (out, stats)), grads = host(fn(*args))
    assert int(stats["dispatched"]) == B * S * config.top_k
    assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(out, want_out, atol=1e-5 * np.abs(
        want_out).max())
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=1e-5 * max(np.abs(w).max(),
                                                          1e-30))


def test_a_rematerialised_layer_runs_five_row_exchanges_and_three_of_gates():
    """The layer's gradient under ``jax.checkpoint`` as the CPU compiles
    it, the exchange by its stand-in (a call of ``mappings._ragged`` is one
    ``all-to-all`` of whole buffers, ``[1, rows, width]`` a chip, beside
    two of int32 offsets): five of rows — forward 2, recompute 1, backward
    2 — and three of gates.  The recompute returns nothing: no residual of
    the backward pass is a row that came back.  (The same count in the
    text a v5e's compiler writes: tests/test_chip_compile.py.)"""
    params, x = params_and_x()
    _, (placed, xs) = four_wide(CONFIG, params, x)

    @jax.checkpoint
    def block(params, x):
        with jax.named_scope(tracing.SCOPE_BLOCK):
            out, aux = moe_layer(params, x, CONFIG, train=True)
        return x + out, aux

    def loss(params, x):
        out, aux = block(params, x)
        return jnp.sum(out ** 2) + 2.0 * aux

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        placed, xs).compile().as_text()
    widths = sorted(int(w) for w in re.findall(
        r"= \(f32\[1,\d+,(\d+)\]\S*, .* all-to-all\(", text))
    assert widths == 5 * [D] + 3 * [LANES], widths
    phases = sorted(tracing.phase_of(op_name) for op_name in re.findall(
        rf'= \(f32\[1,\d+,{D}\]\S*, .* all-to-all\(.*op_name="([^"]*)"', text))
    assert phases == 2 * ["backward"] + 2 * ["forward"] + ["recompute"], \
        phases


def _ragged_widths(text):
    """(element type, row width) of every ``ragged_all_to_all`` of a
    lowered text, by its operand; rows first.  A row into a receive buffer
    travels in the collective's own shape (``mappings._as_sent``: bf16
    ``[., 2, width / 2]``, float32 ``[., 1, width]``): its elements."""
    found = re.findall(
        r"@ragged_all_to_all\(.*?: \(tensor<\d+x((?:\d+x)+)([a-z]\w*)>", text)
    return sorted(((dtype, math.prod(map(int, dims.split("x")[:-1])))
                   for dims, dtype in found), key=lambda found: -found[1])


def _sorts(text):
    """The operand types of every ``stablehlo.sort`` of a lowered text."""
    return re.findall(r'"stablehlo\.sort".*?\}\) : \(([^)]*)\)', text,
                      flags=re.S)


def test_on_a_tpu_the_ragged_collective_is_traced_and_named(monkeypatch):
    """The same layer traced as a TPU traces it (jax 0.9.0's CPU backend
    lowers ``lax.ragged_all_to_all`` and cannot compile it: XLA:CPU's
    ThunkEmitter has no such opcode): the lowered text holds the ragged
    collective, not the stand-in's segments, and the account says so."""
    monkeypatch.setattr(mappings, "exchange_path",
                        lambda: mappings.RAGGED_ALL_TO_ALL)
    params, x = params_and_x()
    fn, args = four_wide(CONFIG, params, x)
    with tracing.step_account("toy"):
        text = fn.lower(*args).as_text()
    (call,) = tracing.exchange_calls("toy")
    assert call["path"] == "ragged_all_to_all"
    assert call["slices_per_pair"] == 2 == call["experts_held"]
    assert call["receive_layout"] == "grouped"
    # forward: rows out, rows back; backward: the two cotangents' — the
    # gates out beside the rows, their cotangent home — and nothing
    # carries the experts' numbers: the table says where rows land
    assert sorted(_ragged_widths(text)) == sorted(
        4 * [("f32", D)] + 2 * [("f32", LANES)])
    assert "stablehlo.all_to_all" not in text


def test_at_the_cells_shapes_no_sort_is_as_long_as_the_bound(monkeypatch):
    """mellum2-12b-a2.5b-ep4's expert layer as a TPU traces it (8,192
    tokens a chip, 64 experts over four chips, top 8, a bound of three
    times the even share: 196,608 rows): of rows **three**
    ``ragged_all_to_all`` a layer where there were four — the gradient
    alone is asked for here, and no residual of the backward pass is a row
    that came back any more, so the forward's return is dead code before
    XLA sees it — each of a row a (token, chip), 4 slices — and two of
    lanes, 16 slices a pair; and the sorts are the sender's — the receiver
    sorts nothing."""
    monkeypatch.setattr(mappings, "exchange_path",
                        lambda: mappings.RAGGED_ALL_TO_ALL)
    config = MoEConfig(d_model=2304, d_ff=896, num_experts=64, top_k=8,
                       dispatch_mode="grouped", held_rows_factor=3)
    topo = MeshTopology(devices=jax.devices()[:4], expert_parallel_size=4)
    set_topology(topo)
    shapes = jax.eval_shape(lambda: init_moe_params(
        config, jax.random.PRNGKey(0)))
    params = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16, sharding=NamedSharding(topo.mesh, spec)),
        shapes, moe_logical_specs(config))
    x = jax.ShapeDtypeStruct((4, 8192, 2304), jnp.bfloat16,
                             sharding=NamedSharding(topo.mesh, P("expert")))
    with tracing.step_account("cell"):
        text = jax.jit(jax.grad(lambda p, x: jnp.sum(moe_layer(
            p, x, config)[0].astype(jnp.float32)), argnums=(0, 1))).lower(
                params, x).as_text()
    (call,) = tracing.exchange_calls("cell")
    assert call["receive_rows"] == 196608 and call["routed_rows"] == 65536
    assert call["receive_fill"] == "padding_tiles"
    assert call["zeroed_rows_per_call"] == 2048
    assert call["slices_per_pair"] == 16
    assert _ragged_widths(text) == 3 * [("bf16", 2304)] \
        + 2 * [("f32", LANES)]
    assert call["row_unit"] == "token_chip"
    assert call["landed_rows"] == 32768
    assert call["wire_rows_bound"] == 3 * 8192
    assert call["wire_bytes"] == 3 * 8192 * 2304 * 2
    # a narrow call has 64 slices, 16 for each of the four chips; a
    # row-wide one four, one a pair
    slices = lambda n: len(re.findall(                      # noqa: E731
        r"ragged_all_to_all.*" + ", ".join(4 * [f"tensor<{n}xi32>"]), text))
    assert (slices(64), slices(4)) == (2, 3)
    # no operand of a row-wide call is longer than a row a (token, chip)
    # and a tile a chip: 33,280 rows where the parent sent 73,728
    operands = [int(rows) for rows in re.findall(
        r"@ragged_all_to_all\(.*?: \(tensor<(\d+)x(?:2x1152|2304)xbf16>",
        text)]
    assert len(operands) == 3 and max(operands) <= 32768 + 4 * 128, operands
    sorts = _sorts(text)
    # the sender's alone: of its 32,768 (token, chip) elements and of its
    # 65,536 routed ones (the plan's, and the same turned round for the
    # gates' way home) — the receiver sorts nothing
    assert sorts and all(
        re.fullmatch(r"tensor<(65536|32768)xi32>"
                     r"(, tensor<(65536|32768)xi32>)*", operands)
        for operands in sorts), sorts
    assert "196608xi32" not in "".join(sorts)


def test_the_program_map_reads_a_ragged_all_to_all():
    """What the chip's text holds, by hand: the kind, the scope, and as
    wire bytes the operand's rows (the result is a buffer sized by a
    bound) times 3 / 4."""
    text = """HloModule jit_train_step

ENTRY %main (p: bf16[65536,2304]) -> bf16[131072,2304] {
  %p = bf16[65536,2304]{1,0} parameter(0)
  %z = bf16[131072,2304]{1,0} broadcast(bf16[] %c), dimensions={}
  %ragged-all-to-all.1 = bf16[131072,2304]{1,0} ragged-all-to-all(%p, %z, %a, %b, %c, %d), replica_groups={{0,1,2,3}}, metadata={op_name="jit(train_step)/ds.fwd_bwd/jvp()/ds.block/mlp/shard_map/exchange/exchange_send/ragged_all_to_all"}
  %ragged-all-to-all.2 = bf16[65536,2304]{1,0} ragged-all-to-all(%ragged-all-to-all.1, %p, %a, %b, %c, %d), replica_groups={{0,1,2,3}}, metadata={op_name="jit(train_step)/ds.fwd_bwd/jvp()/ds.block/mlp/shard_map/exchange/exchange_return/ragged_all_to_all"}
}
"""
    table = tracing.parse_program_text(text)
    for name in ("ragged-all-to-all.1", "ragged-all-to-all.2"):
        assert table[name]["collective"] == "ragged-all-to-all"
        assert table[name]["wire_bytes"] == 65536 * 2304 * 2 * 3 // 4
        assert table[name]["phase"] == "forward"
    assert "/exchange/exchange_send/" in table["ragged-all-to-all.1"]["scope"]


def test_a_one_wide_expert_axis_never_reaches_the_exchange(monkeypatch):
    """One device, and four devices that are all ``data``: the layer is
    the program it was (``_exchanged_grouped_moe`` is not called, no
    all-to-all is traced)."""
    def never(*a, **k):
        raise AssertionError("the exchange on a one-wide expert axis")
    monkeypatch.setattr(moe_layer_module, "_exchanged_grouped_moe", never)
    params, x = params_and_x()
    (want, _), _ = host(on_one_device(CONFIG, params, x))
    topo = MeshTopology(devices=jax.devices()[:4])
    set_topology(topo)
    assert dict(topo.mesh.shape)["expert"] == 1
    assert resolve_dispatch_mode(CONFIG, train=True) == "grouped"
    fn = jax.jit(weighted(CONFIG))
    xs = jax.device_put(x, NamedSharding(topo.mesh, P("data")))
    got, _ = fn(params, xs)
    assert abs(float(got) - want) < 1e-5 * abs(want)
    assert "all-to-all" not in fn.lower(params, xs).as_text()


@pytest.mark.parametrize("mode, train, want", [
    ("grouped", True, "grouped"), ("grouped", False, "grouped"),
    ("auto", True, "einsum"), ("auto", False, "einsum"),
    ("einsum", True, "einsum")])
def test_dispatch_resolution_on_an_expert_axis(mode, train, want):
    """A grouped request stays grouped on a four-wide expert axis (it was
    turned into the einsum); ``auto`` never picks the exchange."""
    set_topology(MeshTopology(devices=jax.devices()[:4],
                              expert_parallel_size=4))
    assert resolve_dispatch_mode(replace(CONFIG, dispatch_mode=mode),
                                 train=train) == want


def test_tokens_the_chips_cannot_split_are_made_up_and_cut_off():
    """Three tokens over four chips (a decode step of a small batch): rows
    of zero gate make up the fourth, and the layer's output, its counts and
    every gradient are the one-device layer's over the three."""
    params, whole = params_and_x()
    x = whole[:1, :3]
    (want, (_, want_stats)), want_grads = host(
        on_one_device(CONFIG, params, x))
    fn, (placed, _) = four_wide(CONFIG, params, whole)
    from deepspeed_tpu.comm.mesh import sharding_pin_scope
    with sharding_pin_scope(False):
        (got, (out, stats)), grads = host(fn(placed, x))
    assert out.shape == (1, 3, D)
    assert int(stats["dispatched"]) == int(want_stats["dispatched"]) == 3 * K
    assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_what_the_exchange_cannot_split_is_refused_by_name():
    params, x = params_and_x()
    set_topology(MeshTopology(devices=jax.devices()[:4],
                              expert_parallel_size=4))
    held = replace(CONFIG, experts_held=2, expert_offset=2)
    with pytest.raises(ValueError, match="no held subset"):
        moe_layer({k: (w[2:4] if k != "router" else w)
                   for k, w in params.items()}, x, held)


def on_four(body, *per_chip):
    """``body`` on each of four chips of an ``expert`` axis, each argument
    and result one row a chip."""
    from deepspeed_tpu.utils.jax_compat import shard_map
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("expert",))

    def one(*rows):
        out = body(*(r[0] for r in rows))
        return jax.tree.map(lambda a: a[None], out)

    return host(jax.jit(shard_map(
        one, mesh=mesh, in_specs=P("expert"), out_specs=P("expert"),
        check_vma=False))(*map(jnp.asarray, per_chip)))


def layout(counts, bound_rows, bm):
    """A held plan's groups written plainly: (start, padded size) of each
    expert — its rows rounded up to tiles, a tile at least, cut in expert
    order so that each later expert keeps one."""
    blocks_left = -(-bound_rows // bm) + len(counts)
    starts, sizes, at = [], [], 0
    for e, c in enumerate(counts):
        blocks = min(max(-(-c // bm), 1), blocks_left - (len(counts) - 1 - e))
        starts.append(at)
        sizes.append(blocks * bm)
        at += blocks * bm
        blocks_left -= blocks
    return starts, sizes


#: rows chip j has for each of 8 experts (two a chip), a bound of rows a
#: chip receives, the M-tile
TABLES = {
    "skewed": (np.array([[5, 1, 0, 2, 3, 3, 1, 1], [9, 4, 1, 0, 0, 0, 1, 1],
                         [1, 1, 6, 2, 2, 2, 1, 1], [0, 3, 3, 3, 3, 0, 2, 2]],
                        np.int32), 32, 4),
    # nobody routes to experts 3 and 4: a tile each all the same
    "an_empty_expert": (np.array(
        [[4, 4, 4, 0, 0, 2, 1, 1], [2, 6, 1, 0, 0, 5, 1, 1],
         [8, 0, 0, 0, 0, 0, 4, 4], [3, 3, 3, 0, 0, 3, 2, 2]],
        np.int32), 32, 4),
    # chip 0 is sent 29 rows and has room for 12: sender 0's nine and
    # three of sender 1's expert-0 rows; chips 2 and 3 are sent 15 and 13
    "a_cut_at_the_bound": (np.array(
        [[5, 4, 0, 1, 2, 1, 2, 1], [6, 4, 1, 1, 0, 0, 2, 2],
         [3, 3, 0, 0, 4, 4, 1, 1], [2, 2, 2, 2, 2, 2, 2, 2]],
        np.int32), 12, 4),
}


def _choices(table, tokens, seed=5):
    """[chips, tokens, E] bool: for each chip, tokens that chose expert
    ``e`` ``table[chip, e]`` times in all, whichever they are."""
    rng = np.random.default_rng(seed)
    chosen = np.zeros(table.shape[:1] + (tokens,) + table.shape[1:], bool)
    for j, counts in enumerate(table):
        for e, count in enumerate(counts):
            chosen[j, rng.permutation(tokens)[:count], e] = True
    return chosen


@pytest.mark.parametrize("case", sorted(TABLES))
def test_every_slice_leaves_and_lands_where_the_table_says(case, monkeypatch):
    """The table every chip derives from one all-gather, against the same
    written as loops.  **The lanes**, a row a (token, expert): sender
    ``j``'s slice for expert ``e`` leaves ``j``'s layout (a held plan of its
    own routed elements over all experts) where that expert's group begins,
    and lands in the group of ``e`` in its chip's layout behind the rows of
    the senders before ``j``; a chip's room goes to the senders in their
    order and a pair's to its experts in theirs, what passes it is cut and
    counted by its sender — the parent's count (a pair's rows past
    ``bound`` less the rows of the senders before).  **The rows**, one a
    (token, chip): a pair's slice is the tokens that chose any expert of
    the chip, once each — so what leaves a chip is the count of distinct
    (token, other chip) pairs, never more than three times its tokens —
    and lands in its sender's slot.  **The runs**: the plan rows of an
    expert whose landed row lies in a block of the landing buffer."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    table, bound, bm = TABLES[case]
    monkeypatch.setattr(gg, "default_block_m", lambda: bm)
    n, E = table.shape
    held, routed, tokens, block = E // n, int(table[0].sum()), 12, 8
    assert (table.sum(1) == routed).all()
    chosen = _choices(table, tokens)
    got = on_four(lambda chosen: mappings.make_exchange_sizes(
        chosen, routed, bound, block), chosen)
    kept = np.zeros((n, n, held), np.int32)
    lands = np.zeros((n, n, held), np.int32)
    groups = np.zeros((n, held), np.int32)
    for d in range(n):
        room = bound
        for j in range(n):
            for e in range(held):
                kept[j, d, e] = min(room, table[j, held * d + e])
                room -= kept[j, d, e]
        starts, sizes = layout(kept[:, d].sum(0), bound, bm)
        groups[d] = starts
        for e in range(held):
            assert kept[:, d, e].sum() <= sizes[e]
            lands[:, d, e] = starts[e] + np.cumsum(kept[:, d, e]) \
                - kept[:, d, e]
    leaves = np.array([layout(table[j], routed, bm)[0]
                       for j in range(n)]).reshape(n, n, held)
    np.testing.assert_array_equal(got.lanes.send_at, leaves.reshape(n, -1))
    np.testing.assert_array_equal(got.lanes.send, kept.reshape(n, -1))
    np.testing.assert_array_equal(got.lanes.land_at, lands.reshape(n, -1))
    by_receiver = lambda a: a.transpose(1, 0, 2).reshape(n, -1)  # noqa: E731
    np.testing.assert_array_equal(got.lanes.held_at, by_receiver(lands))
    np.testing.assert_array_equal(got.lanes.held, by_receiver(kept))
    np.testing.assert_array_equal(got.lanes.home_at, by_receiver(leaves))
    np.testing.assert_array_equal(got.counts, kept.sum(0))
    pair = table.reshape(n, n, held).sum(-1)
    before = np.cumsum(pair, 0) - pair
    parents = (pair - np.clip(bound - before, 0, pair)).sum(1)
    np.testing.assert_array_equal(got.over.reshape(-1), parents)
    assert (parents.sum() > 0) == (case == "a_cut_at_the_bound")
    # the rows: a token once a chip, in its sender's slot
    to_chip = chosen.reshape(n, tokens, n, held).any(-1)   # [from, token, to]
    crossing = to_chip.sum(1)                              # [from, to]
    assert (crossing < table.reshape(n, n, held).sum(-1)).any()
    leaving = np.array([layout(crossing[j], n * tokens, bm)[0]
                        for j in range(n)])
    np.testing.assert_array_equal(got.rows.send_at, leaving)
    np.testing.assert_array_equal(got.rows.send, crossing)
    np.testing.assert_array_equal(
        got.rows.land_at, np.arange(n)[:, None] * tokens + np.zeros(n, int))
    np.testing.assert_array_equal(
        got.rows.held_at, np.zeros((n, 1), int) + np.arange(n) * tokens)
    np.testing.assert_array_equal(got.rows.held, crossing.T)
    np.testing.assert_array_equal(got.rows.home_at, leaving.T)
    wire = crossing.sum(1) - np.diag(crossing)
    assert (wire <= (n - 1) * tokens).all()
    assert wire.sum() == sum(to_chip[j, :, d].sum() for j in range(n)
                             for d in range(n) if d != j)
    # the runs: an expert's plan rows by the block their landed row is in
    blocks = -(-n * tokens // block)
    for d in range(n):
        for e in range(held):
            where = []                  # landed rows of the group, in order
            for j in range(n):
                at = np.cumsum(to_chip[j, :, d]) - to_chip[j, :, d]
                mine = at[chosen[j, :, held * d + e]][:kept[j, d, e]]
                where += list(j * tokens + mine)
            assert where == sorted(where)
            upto = [int(np.sum(np.array(where, int) < b * block))
                    for b in range(blocks + 1)]
            np.testing.assert_array_equal(got.first[d][:, e],
                                          groups[d, e] + np.array(upto[:-1]))
            np.testing.assert_array_equal(got.end[d][:, e],
                                          groups[d, e] + np.array(upto[1:]))
    # the receive plan from the counts is the held plan of those rows
    for d in range(n):
        experts = np.repeat(np.arange(held), kept[:, d].sum(0))
        want, over = jax.jit(lambda e: gg.make_held_group_plan(
            e, 0, held, bound))(jnp.asarray(experts, jnp.int32))
        plan, cut = jax.jit(lambda c: gg.make_counted_group_plan(
            c, bound))(jnp.asarray(got.counts[d]))
        assert int(over) == int(cut) == 0
        assert (plan.padded_rows, plan.num_blocks, plan.live_only) \
            == (want.padded_rows, want.num_blocks, True)
        for name in ("group_sizes", "block_group_ids", "used_blocks",
                     "counts"):
            np.testing.assert_array_equal(getattr(plan, name),
                                          getattr(want, name), err_msg=name)


#: tokens a chip whose two choices are drawn by these weights of the 8
#: experts (two a chip): a chip's receive plan has room for 40 rows, the
#: M-tile is 4
DRAWS = {
    "skewed": np.array([9, 4, 1, 2, 3, 3, 1, 1], float),
    # nobody routes to experts 3 and 4: a tile each all the same
    "an_empty_expert": np.array([4, 4, 4, 0, 0, 2, 1, 1], float),
}


@pytest.mark.parametrize("buffer", ["zeros", "nan"])
@pytest.mark.parametrize("case", ["skewed", "an_empty_expert"])
def test_the_receive_buffer_is_the_parents_held_plan_of_the_rows(
        case, buffer, monkeypatch):
    """What the way out leaves on a chip (``_rows_to_experts``: a row a
    (token, chip) landed, then gathered into the plan by the places the
    lanes brought) is, bit for bit **over the live prefix** — the groups'
    padding rows among it, exact zeros — what the parent built there in
    three steps: a row a (token, expert) as it arrived (by sender, a
    sender's for this chip in its routed order), their experts' numbers
    beside them, then ``make_held_group_plan`` and ``dispatch_held_rows``
    over that buffer — kept here as the parent ran them.  Behind the prefix
    the buffer is nobody's (``nan``: born of NaN here, as a chip's is born
    of whatever its memory held) and is not compared.  And the way back —
    the plan's rows summed by landed row, home, summed by token — gives
    every token its own row once for each of its choices."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    bound, bm, tokens, width, n = 40, 4, 8, 8, 4
    monkeypatch.setattr(gg, "default_block_m", lambda: bm)
    if buffer == "nan":
        monkeypatch.setattr(gg, "_unwritten", _born_of_nan)
    held = E // n
    rng = np.random.default_rng(7)
    weights = DRAWS[case] / DRAWS[case].sum()
    eids = np.stack([[rng.choice(E, K, replace=False, p=weights)
                      for _ in range(tokens)] for _ in range(n)]).astype(
                          np.int32)                         # [n, tokens, K]
    rows = rng.standard_normal((n, tokens, width)).astype(np.float32)
    # a token whose two experts are one chip's crosses to it once
    assert (eids[..., 0] // held == eids[..., 1] // held).any()

    def body(eids, rows):
        out = moe_layer_module._rows_to_experts(
            rows, eids, jnp.ones(eids.shape, jnp.float32), E, bound)
        runs = (out.sizes.first, out.sizes.end)
        summed = gg.sum_into_landed_rows(out.x_pad, out.plan, out.source,
                                         runs, n * tokens)
        home = mappings.exchange_back(summed, out.sizes.rows,
                                      out.by_chip.padded_rows)
        return (out.x_pad, gg.live_rows(out.plan),
                gg.sum_held_rows(home, out.by_chip, n),
                jnp.sum(out.sizes.rows.send), out.lanes[:, 0])

    @jax.jit
    def parents(experts, arrived):
        plan, over = gg.make_held_group_plan(experts, 0, held, bound)
        return (gg.dispatch_held_rows(arrived, plan, 1), over,
                gg.live_rows(plan), gg.dispatch_held_rows(
                    jnp.ones((arrived.shape[0], 1)), plan, 1)[:, 0])

    received, live, returned, sent, gates = on_four(body, eids, rows)
    set_topology(MeshTopology(devices=jax.devices()[:1]))
    for d in range(n):
        here = [(eids[j] // held).reshape(-1) == d for j in range(n)]
        arrived = np.concatenate([np.repeat(rows[j], K, axis=0)[here[j]]
                                  for j in range(n)])
        experts = np.concatenate([eids[j].reshape(-1)[here[j]] % held
                                  for j in range(n)])
        assert len(arrived) <= bound
        pad = bound - len(arrived)
        want, over, live_rows, ones = parents(
            jnp.asarray(np.concatenate([experts, np.full(pad, held)]),
                        jnp.int32),
            jnp.asarray(np.concatenate(
                [arrived, np.zeros((pad, width), np.float32)])))
        assert int(over) == 0 and int(live_rows) == live[d]
        np.testing.assert_array_equal(received[d][:live[d]],
                                      np.asarray(want)[:live[d]])
        # a gate a row that arrived, zero on a group's padding rows
        np.testing.assert_array_equal(gates[d][:live[d]],
                                      np.asarray(ones)[:live[d]])
        # (the lanes land as the parent's rows did, in a buffer of the
        # plan's length that the collective alone writes)
        behind = gates[d][live[d]:]
        assert np.isnan(behind).all() if buffer == "nan" else not behind.any()
        # fewer rows left the chip than it routed: distinct (token, chip)
        pairs = {(t, c) for t in range(tokens) for c in eids[d, t] // held}
        assert sent[d] == len(pairs) < tokens * K
        # back at the sender: a token's row once a choice, summed
        np.testing.assert_allclose(returned[d], K * rows[d], rtol=1e-6)


def _born_of_nan(shape, dtype, after, what):
    """``grouped_gemm._unwritten`` as a chip has it, at its worst: a buffer
    that holds what nobody wrote."""
    return jnp.full(shape, jnp.nan, dtype)


def _poisoned_receive_buffers(monkeypatch, seen):
    """``grouped_gemm.zeroed_padding`` with NaN where its buffer is zeros
    off the chip — its alone: the live-prefix loops keep theirs."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    zeroed_padding = gg.zeroed_padding

    def poisoned(counts, shape, dtype, after, what):
        seen.append((what, shape))
        with monkeypatch.context() as only_here:
            only_here.setattr(gg, "_unwritten", _born_of_nan)
            return zeroed_padding(counts, shape, dtype, after, what)

    monkeypatch.setattr(gg, "zeroed_padding", poisoned)


#: token -> its two experts (of 8, two a chip; the M-tile is 8 rows), by the
#: routing each case plants; tokens a chip
ROUTINGS = {
    # 12 rows an expert, 3 from every chip: a tile and a half, 4 padding rows
    "even": (lambda t: (t % 8, (t + 3) % 8), 12),
    # chip 0's tokens choose chip 0's experts, nobody else does
    "one_senders_skew": (lambda t: (0, 1) if t < 12 else (
        2 + t % 6, 2 + (t + 1) % 6), 12),
    # nobody chooses expert 3: its one tile is padding from end to end
    "an_expert_with_no_rows": (lambda t: (
        (0, 1, 2, 4, 5, 6, 7)[t % 7], (0, 1, 2, 4, 5, 6, 7)[(t + 2) % 7]), 12),
    # 16 rows an expert: two tiles to the last row, no padding row at all
    "a_full_last_tile": (lambda t: (t % 8, (t + 1) % 8), 16),
}


def _planted(case, monkeypatch):
    """The exchanged layer's (params, x) under ``ROUTINGS[case]``, the
    grouped kernels interpreted: the router's weights stay its own, its
    choices are the table's."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    choose, per_chip = ROUTINGS[case]
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    params, x = params_and_x()
    x = x[:, :per_chip // 2]                    # [8, ., D]: two rows a chip
    chosen = jnp.asarray([choose(t) for t in range(4 * per_chip)], jnp.int32)
    route = moe_layer_module._route
    monkeypatch.setattr(
        moe_layer_module, "_route", lambda *a, **k: route(*a, **k)._replace(
            expert_idx=chosen))
    return params, x


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_a_poisoned_receive_buffer_changes_nothing(case, monkeypatch):
    """On a chip the buffer an exchange lands in is born unwritten, and all
    it is given before the rows arrive is zeros in its groups' last tiles
    (``grouped_gemm.zeroed_padding``).  Here, on the stand-in path with the
    grouped kernels interpreted: NaN where that buffer is zeros off the
    chip — rows, gates and cotangents — and the layer's output, its counts
    and every gradient are the clean run's bit for bit."""
    params, x = _planted(case, monkeypatch)
    fn, args = four_wide(CONFIG, params, x)
    clean = host(fn(*args))
    seen = []
    _poisoned_receive_buffers(monkeypatch, seen)
    fn, args = four_wide(CONFIG, params, x)     # traced anew
    dirty = host(fn(*args))
    assert sorted({what for what, _ in seen}) == ["cotangents", "gates",
                                                  "rows"], seen
    (_, (_, stats)), _ = clean
    rows = args[1].shape[0] * args[1].shape[1] * K
    assert int(stats["dispatched"]) == rows and int(stats["dropped"]) == 0
    for a, b in zip(jax.tree.leaves(clean), jax.tree.leaves(dirty)):
        assert np.isfinite(b).all()
        np.testing.assert_array_equal(a, b)


def test_padding_tiles_left_unzeroed_poison_the_weights_gradient(
        monkeypatch):
    """That the test above can fail: the same poisoned buffer with its
    padding tiles left as they were born — ``ds_ggemm_dw`` sums over a
    group's padding rows, and ``w_in``'s gradient is not finite."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    _poisoned_receive_buffers(monkeypatch, [])
    monkeypatch.setattr(gg, "_zero_tiles", lambda buf, tiles, block_m: buf)
    fn, args = four_wide(CONFIG, *_planted("even", monkeypatch))
    _, (dparams, _) = host(fn(*args))
    assert not np.isfinite(dparams["w_in"]).all()


@pytest.mark.parametrize("case", sorted(TABLES))
def test_the_padding_tiles_are_each_groups_last(case):
    """``grouped_gemm._padding_tiles`` against the layout written as loops:
    the tile that ends each group — every padding row of the live prefix
    lies in one of them, and none of them behind it."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    table, bound, bm = TABLES[case]
    held = table.shape[1] // table.shape[0]
    for d in range(table.shape[0]):
        counts = np.minimum(table[:, held * d:held * (d + 1)].sum(0), bound)
        starts, sizes = layout(counts, bound, bm)
        padded_rows = -(-bound // bm) * bm + held * bm
        tiles = np.asarray(gg._padding_tiles(jnp.asarray(counts),
                                             padded_rows, bm))
        np.testing.assert_array_equal(
            tiles, [(s + z) // bm - 1 for s, z in zip(starts, sizes)])
        padding = np.ones(padded_rows, bool)
        for s, c in zip(starts, counts):
            padding[s:s + c] = False
        live = starts[-1] + sizes[-1]
        covered = np.zeros(padded_rows, bool)
        for t in tiles:
            covered[t * bm:(t + 1) * bm] = True
        assert not (padding[:live] & ~covered[:live]).any()
        assert not covered[live:].any()


@pytest.mark.parametrize("shape, dtype", [
    ((96, 256), jnp.bfloat16), ((96, 2, 128), jnp.bfloat16),
    ((96, 128), jnp.float32)], ids=["rows", "rows_as_sent", "gates"])
def test_the_kernel_zeroes_its_tiles_and_writes_nothing_else(shape, dtype):
    """``ds_zeroed_padding_<what>`` in Pallas' interpreter, whose
    uninitialised results are NaN: the padding tiles exact zeros, every
    other row as it was born — in the buffer's own shape and in the shape
    the chip's collective moves it in (``mappings._as_sent``)."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    bm = 8
    counts = jnp.asarray([5, 0, 8, 17], jnp.int32)
    tiles = gg._padding_tiles(counts, shape[0], bm)
    np.testing.assert_array_equal(tiles, [0, 1, 2, 5])
    out = np.asarray(gg._pallas_zeroed_tiles(
        tiles, bm, shape, dtype, jnp.ones((3,)), "test",
        interpret=True).astype(jnp.float32)).reshape(shape[0] // bm, -1)
    for tile, rows in enumerate(out):
        assert (rows == 0).all() if tile in (0, 1, 2, 5) \
            else np.isnan(rows).all(), tile


def test_a_row_travels_as_whole_tiles_of_its_own(monkeypatch):
    """``mappings._as_sent``: on the chip a bf16 row of 2,304 is
    ``[2, 1152]`` and a float32 row of 128 ``[1, 128]``; a width that is no
    whole tiles, and any row off the chip, is as it is."""
    rows = lambda width, dtype: jnp.zeros((8, width), dtype)  # noqa: E731
    assert mappings._as_sent(rows(2304, jnp.bfloat16)) == (2304,)
    monkeypatch.setattr(mappings, "exchange_path",
                        lambda: mappings.RAGGED_ALL_TO_ALL)
    assert mappings._as_sent(rows(2304, jnp.bfloat16)) == (2, 1152)
    assert mappings._as_sent(rows(LANES, jnp.float32)) == (1, LANES)
    assert mappings._as_sent(rows(D, jnp.float32)) == (D,)
    assert mappings._as_sent(rows(128, jnp.bfloat16)) == (128,)


def test_the_device_gate_asks_where_the_call_is():
    """Eight devices in the process: a grouped call traced under plain
    ``jit`` is not on one device; inside a ``shard_map`` over every axis of
    its mesh it is."""
    from deepspeed_tpu.ops.pallas import vmem
    from deepspeed_tpu.utils.jax_compat import shard_map
    assert jax.device_count() > 1 and not vmem.call_on_one_device()
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                             ("a", "b"))
    seen = {}

    def body(x):
        seen["inside"] = vmem.call_on_one_device()
        return x

    shard_map(body, mesh=mesh, in_specs=P("a", "b"), out_specs=P("a", "b"),
              check_vma=False)(jnp.zeros((4, 4)))
    assert seen["inside"] is True
