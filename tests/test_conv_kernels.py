"""The short causal convolution's Mosaic kernels (ops/pallas/
causal_conv.py) in interpret mode on the CPU: values against a float32
per-token oracle and against the XLA form, the gradients of ``x``, ``w``
and the bias against ``jax.grad`` of the XLA form — both orientations, with
and without bias and ``silu``, document boundaries at a slab's first
positions, at ``K - 1`` tokens from the start, around one-token documents
and none; which lowering a call takes and what it tells the step's
account; how often the kernels stand in a toy hybrid's step under full
remat."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import linear_attention as la
from deepspeed_tpu.ops import state_space as ss
from deepspeed_tpu.ops.linear_attention import causal_conv
from deepspeed_tpu.ops.pallas import causal_conv as kernels
from deepspeed_tpu.telemetry import tracing
from tests.util import kernel_names

B, S, C = 2, 512, 256          # two tiles of positions, two channel groups
F32_TOL = 1e-5                 # max |a - b| / max |b|; measured <= 1e-6
ORIENTATIONS = kernels.ORIENTATIONS

#: document lengths of the two sequences (each sums to S)
LAYOUTS = {
    "at_a_slabs_first_positions": [[1, 2, 509], [2, 1, 1, 508]],
    "k_minus_1_from_the_start": [[3, 509], [4, 508]],
    "around_one_token_documents": [[255, 1, 256], [100, 1, 1, 1, 409]],
    "at_a_tiles_edge": [[256, 256], [257, 255]],
    "one_document": [[S], [S]],
}


def _segments(layout, S=S):
    return jnp.asarray(np.stack([
        np.repeat(np.arange(len(lengths)), lengths)
        for lengths in LAYOUTS[layout]]).astype(np.int32))[:, :S]


def _inputs(seed=0, dtype=jnp.float32, K=4, S=S, C=C):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (B, S, C)).astype(dtype),
            (jax.random.normal(k[1], (K, C)) / 2).astype(dtype),
            (jax.random.normal(k[2], (C,)) / 2).astype(dtype))


def _oracle(x, w, seg, bias, activation):
    """Token by token, float32 (numpy's float64 sums rounded at the end)."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    K = w.shape[0]
    seg = np.zeros(x.shape[:2], np.int32) if seg is None else np.asarray(seg)
    u = np.zeros_like(x)
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            for j in range(K):
                s = t - (K - 1) + j
                if s >= 0 and seg[b, s] == seg[b, t]:
                    u[b, t] += w[j] * x[b, s]
    if bias is not None:
        u = u + np.asarray(bias, np.float64)
    if activation == "silu":
        u = u / (1.0 + np.exp(-u))
    return u.astype(np.float32)


def _close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    assert err < tol, err


def _conv(seg, activation, positions, interpret, with_bias=True, first=0):
    return lambda x, w, b: causal_conv(
        x, w, seg, b if with_bias else None, activation, positions,
        first_channel=first, interpret=interpret)


def _grads(fn, args):
    weight = jax.random.normal(jax.random.PRNGKey(9),
                               jax.eval_shape(fn, *args).shape)
    return jax.grad(lambda *a: jnp.sum(weight * fn(*a).astype(jnp.float32)),
                    argnums=(0, 1, 2))(*args)


@pytest.mark.parametrize("positions", ORIENTATIONS)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_kernels_are_the_oracle_and_the_xla_form(layout, positions):
    args, seg = _inputs(), _segments(layout)
    got = _conv(seg, "silu", positions, True)(*args)
    _close(got, _oracle(*args[:2], seg, args[2], "silu"), F32_TOL)
    _close(got, _conv(seg, "silu", positions, False)(*args), F32_TOL)


@pytest.mark.parametrize("positions", ORIENTATIONS)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_gradient_of_the_kernels(layout, positions):
    args, seg = _inputs(1), _segments(layout)
    got = _grads(_conv(seg, "silu", positions, True), args)
    want = _grads(_conv(seg, "silu", positions, False), args)
    for name, g, w in zip(("dx", "dw", "db"), got, want):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        _close(g, w, F32_TOL)


@pytest.mark.parametrize("positions", ORIENTATIONS)
@pytest.mark.parametrize("with_bias,activation", [
    (False, None), (True, None), (False, "silu")])
def test_without_bias_or_activation(with_bias, activation, positions):
    args, seg = _inputs(2), _segments("around_one_token_documents")
    kernel = _conv(seg, activation, positions, True, with_bias)
    xla = _conv(seg, activation, positions, False, with_bias)
    _close(kernel(*args), _oracle(*args[:2], seg,
                                  args[2] if with_bias else None, activation),
           F32_TOL)
    for g, w in zip(_grads(kernel, args)[:2 + with_bias],
                    _grads(xla, args)):
        _close(g, w, F32_TOL)


@pytest.mark.parametrize("positions", ORIENTATIONS)
def test_without_segments(positions):
    args = _inputs(3)
    kernel = _conv(None, "silu", positions, True)
    _close(kernel(*args), _oracle(*args[:2], None, args[2], "silu"), F32_TOL)
    for g, w in zip(_grads(kernel, args),
                    _grads(_conv(None, "silu", positions, False), args)):
        _close(g, w, F32_TOL)


@pytest.mark.parametrize("positions", ORIENTATIONS)
@pytest.mark.parametrize("K", [2, 4, 7])
def test_other_widths(K, positions):
    args, seg = _inputs(4, K=K), _segments("k_minus_1_from_the_start")
    kernel = _conv(seg, "silu", positions, True)
    _close(kernel(*args), _oracle(*args[:2], seg, args[2], "silu"), F32_TOL)
    for g, w in zip(_grads(kernel, args),
                    _grads(_conv(seg, "silu", positions, False), args)):
        _close(g, w, F32_TOL)


@pytest.mark.parametrize("positions", ORIENTATIONS)
@pytest.mark.parametrize("first", [0, 128, 256])
def test_a_part_of_a_wider_array(first, positions):
    """``x`` wider than the weights: the kernels read the channels from
    ``first_channel`` on by their blocks' index, the gradient of the
    channels they did not read is zero, and the rest is the convolution of
    the slice."""
    x, w, b = _inputs(8, C=512)
    w, b = w[:, :C], b[:C]
    seg = _segments("around_one_token_documents")
    cut = x[..., first:first + C]
    got = _conv(seg, "silu", positions, True, first=first)(x, w, b)
    _close(got, _oracle(cut, w, seg, b, "silu"), F32_TOL)
    dx, dw, db = _grads(_conv(seg, "silu", positions, True, first=first),
                        (x, w, b))
    want = _grads(_conv(seg, "silu", positions, False), (cut, w, b))
    for g, v in zip((dx[..., first:first + C], dw, db), want):
        _close(g, v, F32_TOL)
    assert dx.shape == x.shape
    outside = jnp.concatenate([dx[..., :first], dx[..., first + C:]], -1)
    assert float(jnp.abs(outside).max()) == 0.0


@pytest.mark.parametrize("positions", ORIENTATIONS)
def test_bfloat16_rounds_once_at_the_write(positions):
    """The model's dtype: float32 in registers, so the kernels are within
    bfloat16's rounding of the float32 oracle — where the XLA form, which
    multiplies and adds in bfloat16, is itself — and their gradients within
    the XLA form's own distance from float32."""
    args, seg = _inputs(5, jnp.bfloat16), _segments("around_one_token_documents")
    f32 = lambda t: t.astype(jnp.float32)
    got = _conv(seg, "silu", positions, True)(*args)
    assert got.dtype == jnp.bfloat16
    want = _oracle(*(f32(a) for a in args[:2]), seg, f32(args[2]), "silu")
    xla = _conv(seg, "silu", positions, False)(*args)
    err = lambda a: float(np.max(np.abs(np.asarray(f32(a)) - want)))
    assert err(got) <= 2.0 ** -8 * float(np.max(np.abs(want)))   # one rounding
    assert err(got) <= err(xla)
    exact = _grads(_conv(seg, "silu", positions, False),
                   tuple(f32(a) for a in args))
    kernel = _grads(_conv(seg, "silu", positions, True), args)
    rounded = _grads(_conv(seg, "silu", positions, False), args)
    for name, g, r, e in zip(("dx", "dw", "db"), kernel, rounded, exact):
        assert g.dtype == r.dtype == jnp.bfloat16, name
        off = lambda a: float(jnp.max(jnp.abs(f32(a) - e)) / jnp.max(jnp.abs(e)))
        assert off(g) <= max(off(r), 2.0 ** -8), (name, off(g), off(r))


@pytest.mark.parametrize("positions", ORIENTATIONS)
def test_a_document_sees_nothing_of_the_one_before(positions):
    x, w, b = _inputs(6)
    seg = _segments("k_minus_1_from_the_start")
    other = x.at[0, :3].add(3.0)
    kernel = _conv(seg, "silu", positions, True)
    a, c = kernel(x, w, b), kernel(other, w, b)
    assert float(jnp.abs(a[0, :3] - c[0, :3]).max()) > 0.1
    np.testing.assert_array_equal(a[0, 3:], c[0, 3:])
    np.testing.assert_array_equal(a[1], c[1])


@pytest.mark.parametrize("slab", [128, 256])
@pytest.mark.parametrize("positions", ORIENTATIONS)
def test_one_slab_and_two_give_the_same(slab, positions, monkeypatch):
    args, seg = _inputs(7), _segments("at_a_tiles_edge")
    rule = kernels.slab_width
    monkeypatch.setattr(
        kernels, "slab_width",
        lambda *a: rule(*a)._replace(slab=slab, tile=128))
    with tracing.step_account("test/conv"):
        got = _conv(seg, "silu", positions, True)(*args)
    (row,) = tracing.conv_calls("test/conv")
    assert (row["slab"], row["tile"]) == (slab, 128)
    _close(got, _conv(seg, "silu", positions, False)(*args), F32_TOL)


@pytest.mark.parametrize("why,S,C,K,positions,first,interpret", [
    ("channels that are not whole lane tiles", 256, 192, 4, "sublanes", 0,
     True),
    ("positions that are not whole sublane tiles", 200, 256, 4, "sublanes",
     0, True),
    ("positions that are not whole lane tiles", 192, 256, 4, "lanes", 0,
     True),
    ("more taps than the weights' block has rows", 256, 256, 8, "lanes", 0,
     True),
    ("a first channel inside a lane tile", 256, 128, 4, "sublanes", 64,
     True),
    ("no TPU here, nothing asked", 256, 256, 4, "sublanes", 0, None),
    ("the XLA form asked for", 256, 256, 4, "lanes", 0, False),
])
def test_calls_the_kernels_refuse_fall_back_and_say_so(
        why, S, C, K, positions, first, interpret):
    x, w, b = _inputs(0, K=K, S=S, C=C + first)
    w, b = w[:, :C], b[:C]
    with tracing.step_account("test/conv"):
        got = jax.eval_shape(
            lambda *a: causal_conv(*a[:2], None, a[2], "silu", positions,
                                   first_channel=first, interpret=interpret),
            x, w, b)
    assert tracing.conv_calls("test/conv") == [
        {"batch": B, "positions": S, "channels": C, "taps": K,
         "orientation": positions, "path": "xla"}], why
    assert got.shape == (B, S, C)


def test_more_than_one_device_takes_the_xla_form(monkeypatch):
    """No partitioning rule for the call yet: on a TPU host with more than
    one device visible the XLA form runs and the account says so; with
    one, the kernels — where the slab fits the device kind's budget."""
    from deepspeed_tpu.ops import attention
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(kernels.vmem, "device_kind", lambda: "tpu v5 lite")
    rule = lambda: la._conv_blocking(None, 8192, 8192, 4, jnp.bfloat16,
                                     "sublanes", 0)
    assert jax.device_count() > 1 and rule() == (None, False)
    x, w, b = _inputs(0)
    with tracing.step_account("test/conv"):
        jax.eval_shape(lambda *a: causal_conv(*a[:2], None, a[2]), x, w, b)
    assert tracing.conv_calls("test/conv")[0]["path"] == "xla"
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    blocking, interpret = rule()
    assert (blocking.positions, interpret) == ("sublanes", False)
    assert blocking.vmem_bytes <= kernels.vmem.budget()
    # a device kind with no budget of its own: the sequence does not fit
    monkeypatch.setattr(kernels.vmem, "device_kind", lambda: "tpu v4")
    assert rule() == (None, False)


#: each op's choice at its cell's shapes (the Nemotron-H convolution and
#: scan, Qwen3-Next's and Kimi-Linear's delta rules), bfloat16
LOWERINGS = {
    "conv": lambda asked: la._conv_blocking(
        asked, 8192, 8192, 4, jnp.bfloat16, "sublanes", 0),
    "delta_rule": lambda asked: la._kernel_blocking(
        asked, 128, 64, 2, 128, 128, jnp.bfloat16),
    # ... and Kimi-Linear's, a decay a key channel
    "delta_rule_by_channel": lambda asked: la._kernel_blocking(
        asked, 256, 64, 1, 128, 128, jnp.bfloat16, True),
    "scan": lambda asked: ss._kernel_blocking(
        asked, 64, 128, 8, 64, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("case", [
    "one_tpu", "not_a_tpu", "more_than_one_device", "over_the_budget",
    "interpret_asked_for", "xla_asked_for"])
@pytest.mark.parametrize("op", sorted(LOWERINGS))
def test_the_three_ops_choose_their_lowering_by_one_rule(op, case,
                                                         monkeypatch):
    """``vmem.lowering`` through each op's own call of it: the kernels on
    a TPU with one device and a working set inside the budget; the XLA
    form where any of the three fails, or is asked for; the kernels in
    interpret mode where that is asked for, whatever the host."""
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import vmem
    monkeypatch.setattr(vmem, "device_kind", lambda: "tpu v5 lite")
    kernel_blocking, _ = LOWERINGS[op](True)
    assert kernel_blocking.vmem_bytes <= vmem.budget()
    monkeypatch.setattr(attention, "_on_tpu", lambda: case != "not_a_tpu")
    monkeypatch.setattr(
        jax, "device_count",
        lambda: 4 if case == "more_than_one_device" else 1)
    if case == "over_the_budget":
        # no blocking fits (the convolution sizes its slab by the budget)
        monkeypatch.setattr(vmem, "budget", lambda: 0)
    asked = {"interpret_asked_for": True, "xla_asked_for": False}.get(case)
    want = {"one_tpu": (kernel_blocking, False),
            "interpret_asked_for": (kernel_blocking, True)}.get(
                case, (None, False))
    assert LOWERINGS[op](asked) == want


@pytest.mark.parametrize("S,C,itemsize,positions,first,slab,tile", [
    (8192, 2048, 2, "sublanes", 2048, 256, 128),
    (8192, 4096, 2, "lanes", 4096, 256, 1024),
    (8192, 1024, 2, "lanes", 9216, 256, 1024),
    (8192, 4096, 4, "sublanes", 0, 128, 128),
    (2048, 8192, 2, "sublanes", 128, 128, 128),
    (768, 384, 4, "lanes", 0, 128, 256), (48, 128, 4, "sublanes", 0, 128, 16)])
def test_slab_width_is_a_rule_of_shapes(S, C, itemsize, positions, first,
                                        slab, tile, monkeypatch):
    """The wider slab of 256 and 128 that divides the channels and the
    first channel and whose blocks fit the device kind's budget, the
    longest tile of positions the orientation takes that divides the
    sequence; both cells' calls take 256 channels a grid step."""
    monkeypatch.setattr(kernels.vmem, "device_kind", lambda: "tpu v5 lite")
    blocking = kernels.slab_width(S, C, itemsize, positions, first)
    assert blocking == kernels.Blocking(
        positions, slab, tile, kernels.working_set(S, slab, itemsize,
                                                   positions))
    assert blocking.vmem_bytes <= kernels.vmem.budget()


def test_the_account_says_which_lowering_ran():
    x, w, b = _inputs()
    with tracing.step_account("test/conv"):
        jax.eval_shape(_conv(None, "silu", "lanes", True), x, w, b)
        jax.eval_shape(_conv(None, "silu", "sublanes", True), x, w, b)
    row = {"batch": B, "positions": S, "channels": C, "taps": 4,
           "path": "kernel", "slab": 256}
    assert tracing.conv_calls("test/conv") == [
        {**row, "orientation": "lanes", "tile": 512},
        {**row, "orientation": "sublanes", "tile": 128}]
    assert {"ds_conv_fwd", "ds_conv_bwd"} <= kernel_names(
        _conv(None, "silu", "lanes", True), x, w, b)


def test_an_activation_the_op_does_not_know():
    x, w, b = _inputs()
    with pytest.raises(ValueError, match="gelu"):
        causal_conv(x, w, None, b, "gelu")


def _kernel_calls(jaxpr, counts):
    """pallas_call equations by kernel name, through every sub-jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, counts)
    return counts


HYBRIDS = {
    # q and k 2 * 64 = 128 channels each, v 4 * 64 = 256, over 128
    # positions; three such layers in the loop over periods
    "qwen3_next": ("80b-a3b", dict(
        num_layers=4, d_model=64, num_heads=4, num_kv_heads=2, head_dim=32,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=64, linear_value_head_dim=64, d_ff=32,
        shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
        expert_offset=8, vocab_size=512, max_seq_len=128,
        delta_rule_chunk=16, dtype="float32", remat=True), "sublanes",
        (128, 256), 3),
    # x 8 * 16 = 128 channels, B and C 2 * 64 = 128 each; one such layer
    "nemotron_h": ("3-nano-30b-a3b", dict(
        num_layers=2, hybrid_override_pattern="ME", d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=32, mamba_num_heads=8, mamba_head_dim=16,
        n_groups=2, ssm_state_size=64, chunk_size=16, d_ff=32,
        shared_expert_d_ff=64, num_experts=16, top_k=4, experts_held=4,
        expert_offset=8, vocab_size=512, max_seq_len=128, dtype="float32",
        remat=True), "lanes", (128,), 1),
}
