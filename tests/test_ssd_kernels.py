"""The state-space scan's Mosaic kernels (ops/pallas/state_space.py) in
interpret mode on the CPU: values and the gradients of all six arguments
against the literal per-token recurrence and against the XLA chunked form,
at heads of 64 over a state of 128 in two groups, with document boundaries
inside a chunk, at a chunk's edge, at the edge of a block of chunks and
around a one-token document — and with the carried state and ``dH``
crossing a grid step; which lowering a call takes and what it tells the
step's account."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.ops import state_space as ss
from deepspeed_tpu.ops.pallas import state_space as kernels
from deepspeed_tpu.ops.state_space import ssd_recurrent, ssd_scan
from deepspeed_tpu.telemetry import tracing
from tests.util import kernel_names

B, S, H, P, G, N, CHUNK = 2, 512, 4, 64, 2, 128, 128
BLOCK = 2               # chunks a grid step walks here: two blocks
VALUE_TOL = 2e-5        # max |a - b| / max |b|; measured <= 3e-6
GRAD_TOL = 1e-4         # per argument; measured <= 4e-6
NAMES = ("x", "dt", "A", "B", "C", "D")

#: document lengths of the two sequences (each sums to S); a block of
#: chunks is 256 tokens
LAYOUTS = {
    "one_document": [[S], [S]],
    "inside_a_chunk": [[100, 412], [200, 57, 255]],
    "at_a_chunks_edge": [[128, 384], [384, 128]],
    "at_a_blocks_edge": [[256, 256], [255, 1, 256]],
    "around_a_one_token_document": [[127, 1, 384], [128, 1, 383]],
}


@pytest.fixture
def two_blocks(monkeypatch):
    """The rule would walk the four chunks in one grid step."""
    monkeypatch.setattr(
        kernels, "chunks_per_step", lambda n, C, r, *a: kernels.Blocking(
            C, BLOCK, r, kernels.working_set(BLOCK, C, r, *a)))


def _inputs(seed=0, dtype=jnp.float32, S=S):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = (jax.random.normal(k[3], (B, S, G, N)) / 4).astype(dtype)
    Cm = (jax.random.normal(k[4], (B, S, G, N)) / 4).astype(dtype)
    D = jax.random.normal(k[5], (H,))
    return x, dt, A, Bm, Cm, D


def _segments(layout):
    return jnp.asarray(np.stack([
        np.repeat(np.arange(len(lengths)), lengths)
        for lengths in LAYOUTS[layout]]).astype(np.int32))


def _close(got, want, tol):
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < tol, err


def _grads(fn, args, argnums=tuple(range(6))):
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    return jax.grad(lambda *a: jnp.sum(weight * fn(*a)),
                    argnums=argnums)(*args)


def _kernel(seg):
    return lambda *a: ssd_scan(*a, seg, chunk=CHUNK, interpret=True)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_kernels_are_the_recurrence_and_the_xla_form(layout, two_blocks):
    args, seg = _inputs(), _segments(layout)
    with jax.default_matmul_precision("highest"):
        got = _kernel(seg)(*args)
        _close(got, ssd_recurrent(*args, seg), VALUE_TOL)
        _close(got, ssd_scan(*args, seg, chunk=CHUNK, interpret=False),
               VALUE_TOL)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_gradient_of_the_kernels(layout, two_blocks):
    args, seg = _inputs(1), _segments(layout)
    with jax.default_matmul_precision("highest"):
        got = _grads(_kernel(seg), args)
        oracle = _grads(lambda *a: ssd_recurrent(*a, seg), args)
        xla = _grads(lambda *a: ssd_scan(*a, seg, chunk=CHUNK,
                                         interpret=False), args)
    for name, g, w, v in zip(NAMES, got, oracle, xla):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        _close(g, w, GRAD_TOL)
        _close(g, v, GRAD_TOL)


def test_without_segments(two_blocks):
    args = _inputs(2)
    with jax.default_matmul_precision("highest"):
        _close(_kernel(None)(*args), ssd_recurrent(*args), VALUE_TOL)
        for g, w in zip(_grads(_kernel(None), args),
                        _grads(ssd_recurrent, args)):
            _close(g, w, GRAD_TOL)


def test_without_the_skip_term(two_blocks):
    x, dt, A, Bm, Cm, D = _inputs(3)
    seg = _segments("inside_a_chunk")
    five = (x, dt, A, Bm, Cm)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*five, None, seg, chunk=CHUNK, interpret=True)
        _close(got, ssd_recurrent(*five, None, seg), VALUE_TOL)
        with_d = _kernel(seg)(*five, D)
        for g, w in zip(
                _grads(lambda *a: ssd_scan(*a, None, seg, chunk=CHUNK,
                                           interpret=True), five, range(5)),
                _grads(lambda *a: ssd_recurrent(*a, None, seg), five,
                       range(5))):
            _close(g, w, GRAD_TOL)
    np.testing.assert_allclose(with_d - got, D[:, None] * x, atol=1e-5)


def test_a_ragged_tail(two_blocks):
    """450 tokens: the fourth chunk ends in tokens of step 0 that decay
    nothing and write nothing."""
    args = _inputs(4, S=450)
    seg = jnp.asarray(np.stack([np.repeat([0, 1], [300, 150]),
                                np.repeat([0, 1, 2], [129, 1, 320])])
                      .astype(np.int32))
    with jax.default_matmul_precision("highest"):
        got = _kernel(seg)(*args)
        assert got.shape == (B, 450, H, P)
        _close(got, ssd_recurrent(*args, seg), VALUE_TOL)
        for g, w in zip(_grads(_kernel(seg), args),
                        _grads(lambda *a: ssd_recurrent(*a, seg), args)):
            _close(g, w, GRAD_TOL)


def test_one_block_and_two_give_the_same(monkeypatch):
    """The state that crosses a grid step in VMEM scratch, and the one the
    forward saves for the backward's block, are the state."""
    args, seg = _inputs(5), _segments("inside_a_chunk")
    with jax.default_matmul_precision("highest"):
        one = (_kernel(seg)(*args), _grads(_kernel(seg), args))
        monkeypatch.setattr(
            kernels, "chunks_per_step",
            lambda n, C, r, *a: kernels.Blocking(C, 1, r, 0))
        four = (_kernel(seg)(*args), _grads(_kernel(seg), args))
    for a, b in zip(jax.tree_util.tree_leaves(one),
                    jax.tree_util.tree_leaves(four)):
        _close(a, b, 1e-5)


def test_a_document_sees_nothing_of_the_one_before(two_blocks):
    x, dt, A, Bm, Cm, D = _inputs(6)
    seg = _segments("inside_a_chunk")
    other = x.at[0, :100].set(x[0, :100] + 3.0)
    a = _kernel(seg)(x, dt, A, Bm, Cm, D)
    b = _kernel(seg)(other, dt, A, Bm, Cm, D)
    assert float(jnp.abs(a[0, :100] - b[0, :100]).max()) > 0.1
    np.testing.assert_array_equal(a[0, 100:], b[0, 100:])
    np.testing.assert_array_equal(a[1], b[1])


def test_bfloat16_operands_float32_state(two_blocks):
    """The model's dtype: the kernels round where the XLA form rounds, so
    the two are bfloat16's rounding apart at most, values and gradients."""
    args, seg = _inputs(7, jnp.bfloat16), _segments("inside_a_chunk")
    got = _kernel(seg)(*args)
    assert got.dtype == jnp.bfloat16
    f32 = lambda t: t.astype(jnp.float32)
    want = ssd_recurrent(*(f32(a) for a in args), seg)
    _close(f32(got), want, 3e-2)
    xla = lambda *a: f32(ssd_scan(*a, seg, chunk=CHUNK, interpret=False))
    for name, g, w in zip(NAMES, _grads(lambda *a: f32(_kernel(seg)(*a)),
                                        args), _grads(xla, args)):
        assert g.dtype == w.dtype, name
        _close(f32(g), f32(w), 3e-2)


@pytest.mark.parametrize("why,P,N,chunk,interpret", [
    ("heads that are not whole sublane tiles", 8, 128, 128, True),
    ("a state narrower than a lane tile", 64, 16, 128, True),
    ("a chunk that is not a lane tile", 64, 128, 64, True),
    ("no TPU here, nothing asked", 64, 128, 128, None),
    ("the XLA form asked for", 64, 128, 128, False),
])
def test_calls_the_kernels_refuse_fall_back_and_say_so(why, P, N, chunk,
                                                       interpret):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    S = 256
    args = (jax.random.normal(k[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, S, H))),
            -jnp.ones((H,)), jax.random.normal(k[2], (B, S, G, N)),
            jax.random.normal(k[3], (B, S, G, N)))
    with tracing.step_account("test/ssd"):
        got = jax.eval_shape(lambda *a: ssd_scan(
            *a, chunk=chunk, interpret=interpret), *args)
    (row,) = tracing.ssd_chunks("test/ssd")
    assert row["path"] == "xla" and "chunks_per_step" not in row, why
    assert got.shape == (B, S, H, P)


def test_more_than_one_device_takes_the_xla_form(monkeypatch):
    """No partitioning rule for the call yet: on a TPU host with four
    devices visible the XLA form runs; with one, the kernels."""
    from deepspeed_tpu.ops import attention
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(kernels.vmem, "device_kind", lambda: "tpu v5 lite")
    rule = lambda: ss._kernel_blocking(None, 64, 128, 8, 64, 128,
                                       jnp.bfloat16)
    assert jax.device_count() > 1 and rule() == (None, False)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    blocking, interpret = rule()
    assert (blocking.chunks, blocking.heads, interpret) == (4, 8, False)


@pytest.mark.parametrize("n,r,itemsize,chunks", [
    (64, 8, 2, 4), (64, 8, 4, 2), (64, 2, 2, 8), (6, 8, 2, 2), (5, 2, 4, 1)])
def test_chunks_per_step_is_a_rule_of_shapes(n, r, itemsize, chunks):
    """The most chunks of 8, 4, 2, 1 that divide the sequence's and whose
    blocks fit what a call is granted unasked; the cell (64 chunks of 128,
    bf16, eight heads of 64 a group) walks 4 a step."""
    blocking = kernels.chunks_per_step(n, 128, r, 64, 128, itemsize)
    assert (blocking.chunk, blocking.chunks, blocking.heads) \
        == (128, chunks, r)
    assert blocking.vmem_bytes <= kernels.vmem.UNASKED


def test_the_account_says_which_lowering_ran():
    args, seg = _inputs(), _segments("one_document")
    with tracing.step_account("test/ssd"):
        jax.eval_shape(_kernel(seg), *args)
    assert tracing.ssd_chunks("test/ssd") == [
        {"chunks": S // CHUNK, "chunk_len": CHUNK, "batch": B, "heads": H,
         "groups": G, "head_dim": P, "state": N, "path": "kernel",
         "heads_per_step": H // G, "chunks_per_step": 4}]
    assert {"ds_ssd_fwd", "ds_ssd_bwd"} <= kernel_names(_kernel(seg), *args)


@pytest.mark.parametrize("chunk, why", [(256, {"why": "chunk 256"}),
                                        (CHUNK, {})])
def test_the_account_says_when_the_chunk_alone_sent_a_call_to_xla(chunk, why):
    """Shapes the kernels take at their own chunk of 128, asked for at a
    published 256: the XLA form as before, and the row carries the
    reason; at 128, left to the rule (no TPU here: XLA too), no reason."""
    args, seg = _inputs(), _segments("one_document")
    with tracing.step_account("test/ssd"):
        jax.eval_shape(lambda *a: ssd_scan(*a, seg, chunk=chunk), *args)
    assert tracing.ssd_chunks("test/ssd") == [
        {"chunks": S // chunk, "chunk_len": chunk, "batch": B, "heads": H,
         "groups": G, "head_dim": P, "state": N, "path": "xla", **why}]


def test_a_toy_engines_step_runs_the_kernels_and_says_so(monkeypatch):
    """Nemotron-H at toy depth with a mixer the kernels take (heads of 64,
    state 128, chunk 128), the choice steered to interpret mode as it
    would fall on one TPU: the step's account reads ``path: "kernel"`` with
    the block, the step's map names both kernels under ``ssm/scan`` in the
    phases they run in, and the loss is the XLA form's."""
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.models.nemotron_h import nemotron_h_model
    from tests.util import base_config
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    toy = dict(num_layers=2, hybrid_override_pattern="MM", d_model=64,
               num_heads=4, num_kv_heads=2, head_dim=32, mamba_num_heads=4,
               mamba_head_dim=64, n_groups=2, ssm_state_size=128,
               chunk_size=128, d_ff=32, shared_expert_d_ff=64,
               num_experts=16, top_k=4, experts_held=4, expert_offset=8,
               vocab_size=512, max_seq_len=256, dtype="float32", remat=True)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 512, size=(1, 2, 256),
                                       dtype=np.int32),
             "segment_ids": np.stack([np.repeat([0, 1], [100, 156]),
                                      np.repeat([0, 1, 2], [128, 1, 127])]
                                     ).astype(np.int32)[None]}
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    rule = ss._kernel_blocking

    def losses(interpret):
        monkeypatch.setattr(
            ss, "_kernel_blocking",
            lambda asked, *a: rule(interpret if asked is None else asked,
                                   *a))
        tracing.reset_programs()
        engine, *_ = deepspeed_tpu.initialize(
            model=nemotron_h_model("3-nano-30b-a3b", **toy),
            config=base_config(train_micro_batch_size_per_gpu=2,
                               gradient_accumulation_steps=1), mesh=mesh)
        return [float(engine.train_batch(batch=batch)) for _ in range(2)]

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        want = losses(False)
        got = losses(True)
        account = tracing.ssd_chunks("train/step")
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        tracing.reset_programs()
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert account == [
        {"chunks": 2, "chunk_len": 128, "batch": 2, "heads": 4, "groups": 2,
         "head_dim": 64, "state": 128, "path": "kernel", "heads_per_step": 2,
         "chunks_per_step": 2}]
    # interpret mode leaves no Mosaic call, but the kernels' names are
    # scopes of what it runs: under ssm/scan, each in its phases
    seen = {(name, row["phase"]) for row in table.values()
            for name in ("ds_ssd_fwd", "ds_ssd_bwd")
            if f"/ssm/scan/{name}/" in (row["scope"] or "")}
    assert seen >= {("ds_ssd_fwd", "forward"), ("ds_ssd_fwd", "recompute"),
                    ("ds_ssd_bwd", "backward")}
    assert ("ds_ssd_fwd", "backward") not in seen
