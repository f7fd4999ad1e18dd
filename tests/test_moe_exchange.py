"""The expert-parallel exchange of the grouped dispatch
(moe/layer.py ``_exchanged_grouped_moe``, moe/mappings.py) on a four-device
host mesh, at toy size on the CPU: the exchanged layer against the
one-device grouped layer over all experts — output, ``dx``, every ``dw``,
the router's gradient; the guide's sum-of-shares test (the four held
shares' partial results, each from ``experts_held`` / ``expert_offset``
alone with no exchange, add up to the uncut layer written plainly, and so
does the exchanged layer); a planted skew that passes a bound is counted,
not dropped silently; two all-to-alls of rows a pass and no capacity
einsum in the compiled text; a one-wide ``expert`` axis never reaches the
exchange; the plan's maps; the device gate.

With ``real_kernels`` the grouped kernels and ``ds_rowsum`` run in Pallas'
interpreter inside the exchange's manual region; elsewhere their jnp forms
stand in."""
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
CONFIG = MoEConfig(d_model=D, d_ff=F, num_experts=E, top_k=K,
                   dispatch_mode="grouped", aux_loss_coef=1e-2,
                   load_balance="all_choices", held_rows_factor=4)


@pytest.fixture(autouse=True)
def _isolation(monkeypatch):
    monkeypatch.setattr(moe_layer_module, "_metrics_registry", None)
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
    such collective and moves the same rows by all-gathers
    (``mappings._ragged``).  Counted here at the call: forward two of rows
    (out and back) and one of the experts' numbers, backward the two
    cotangents' — and nothing of the capacity formulation in the text."""
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
    assert sorted(calls) == sorted([(routed, D), (routed,), (bound, D),
                                    (bound, D), (routed, D)]), calls
    assert not re.search(rf"\[(?:{tokens}|{tokens // 4}),{E},\d+\]", text)
    (call,) = tracing.exchange_calls("toy")
    assert call["pairs"] == 4 and call["experts_held"] == 2
    assert call["tokens"] == tokens // 4 and call["routed_rows"] == routed
    assert call["receive_rows"] == bound
    assert call["wire_bytes"] == 3 * (routed // 4) * D * 4
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
    # forward: rows out, their experts' numbers, rows back; backward: the
    # two cotangents'
    assert text.count("ragged_all_to_all") == 5
    assert "stablehlo.all_to_all" not in text


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


def test_the_plans_maps_are_each_others_inverse():
    rng = np.random.default_rng(0)
    eids = jnp.asarray(rng.integers(0, 8, size=40), jnp.int32)
    plan = mappings.make_exchange_plan(eids, experts_held=2, pairs=4)
    by_chip, place = map(np.asarray, (plan.by_chip, plan.place))
    dest = np.asarray(eids) // 2
    np.testing.assert_array_equal(plan.sizes, np.bincount(dest, minlength=4))
    np.testing.assert_array_equal(place[by_chip], np.arange(40))
    np.testing.assert_array_equal(by_chip[place], np.arange(40))
    # by chip, a chip's rows in routed order
    np.testing.assert_array_equal(by_chip, np.argsort(dest, kind="stable"))
    np.testing.assert_array_equal(plan.local_expert,
                                  np.asarray(eids)[by_chip] % 2)


def test_who_sends_whom_and_where_it_lands():
    """The table every chip derives from one all-gather: a sender's rows
    for a chip lie behind its rows for the chips before; at the receiver
    behind those of the senders before; what would pass the bound is cut
    from the end and counted by its sender."""
    from deepspeed_tpu.utils.jax_compat import shard_map
    table = np.array([[2, 2, 2, 2], [8, 0, 0, 0], [1, 3, 0, 4],
                      [0, 0, 4, 4]], np.int32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("expert",))

    def body(sizes):
        got = mappings.make_exchange_sizes(sizes.reshape(-1), bound=9)
        return jax.tree.map(lambda a: a.reshape(1, -1), got)

    got = host(shard_map(body, mesh=mesh, in_specs=P("expert"),
                         out_specs=P("expert"), check_vma=False)(
                             jnp.asarray(table)))
    starts = np.cumsum(table, 1) - table
    lands = np.cumsum(table, 0) - table
    kept = np.clip(9 - lands, 0, table)
    np.testing.assert_array_equal(got.send_at, starts)
    np.testing.assert_array_equal(got.send, kept)
    np.testing.assert_array_equal(got.land_at, lands)
    np.testing.assert_array_equal(got.held, kept.T)
    np.testing.assert_array_equal(got.held_at, lands.T)
    np.testing.assert_array_equal(got.home_at, starts.T)
    # chip 0 is sent 11 rows and has room for 9: chip 2's one row and one
    # of chip 1's eight are cut; chip 3 is sent 10: chip 3's last is cut
    np.testing.assert_array_equal(got.over.reshape(-1), [0, 1, 1, 1])
    assert kept.sum(0).max() <= 9


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
