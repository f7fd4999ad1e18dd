"""``models/model.py head_nll_sum`` with weights that are not ones and
zeros, and with a gradient in them (a looped model's probabilities of
leaving after a pass: models/ouro.py) — against whole logits on one chip
and on the eight virtual ones — and what that left alone: a caller whose
``scored`` is ones and zeros made from the batch gets the loss bits, the
gradients and the lowered text it had at PR 70's parent commit (taken
there: tests/data/weighted_head_step_digests.json), and the toy steps of
the six families of the benchmark that no older digest file holds lower to
the text they had (tests/flash_step_texts.py ``HEAD_FAMILIES``; gpt2,
olmoe, qwen3_next and nemotron_h are tests/test_flash_head_widths.py's,
joyai and xing tests/test_kda_neighbours.py's)."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm.mesh import MeshTopology, set_topology
from deepspeed_tpu.models import model
from deepspeed_tpu.models.model import head_nll_sum, head_token_loss
from tests import flash_step_texts

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "weighted_head_step_digests.json")) as f:
    PARENTS = json.load(f)
B, S, D, V = 8, 48, 16, 96


@pytest.fixture(params=["one_chip", "eight_chips"])
def chips(request):
    devices = jax.devices()
    topo = MeshTopology(devices=devices[:1] if request.param == "one_chip"
                        else devices)
    set_topology(topo)
    with topo.mesh:
        yield request.param


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(model, "head_chunk_tokens", lambda t, v: min(t, 32))


def _inputs(dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    h = jax.random.normal(k[0], (B, S, D)).astype(dtype)
    w = (jax.random.normal(k[1], (D, V)) * 0.3).astype(dtype)
    targets = jax.random.randint(k[2], (B, S), 0, V)
    weights = jax.random.uniform(k[3], (B, S)) * (jnp.arange(S) % 5 > 0)
    return h, w, targets, weights


def _whole(h, w, targets, weights):
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    nll = jax.scipy.special.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0]
    return jnp.sum(nll * weights), nll


def test_the_gradient_in_the_weights_is_the_per_token_nll(chips,
                                                           small_chunks):
    h, w, targets, weights = _inputs()
    want, want_grads = jax.value_and_grad(
        lambda h, w, s: _whole(h, w, targets, s)[0], argnums=(0, 1, 2))(
            h, w, weights)
    got, grads = jax.jit(jax.value_and_grad(
        lambda h, w, s: head_nll_sum(h, w, targets, s, False, "weighted"),
        argnums=(0, 1, 2)))(h, w, weights)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(grads[2]), np.asarray(_whole(h, w, targets, weights)[1]),
        atol=2e-5)


def test_weights_that_depend_on_h_reach_it_through_both_paths(
        chips, small_chunks):
    """A gate read off the same state: ``d loss / d h`` is the head's own
    plus the weights' (what the looped model's exit gates get)."""
    h, w, targets, _ = _inputs()
    gate = jax.random.normal(jax.random.PRNGKey(9), (D,)) * 0.2

    def loss(fn, h):
        return fn(h, jax.nn.sigmoid(h @ gate))

    want = jax.grad(lambda h: loss(
        lambda h, s: _whole(h, w, targets, s)[0], h))(h)
    got = jax.jit(jax.grad(lambda h: loss(
        lambda h, s: head_nll_sum(h, w, targets, s, False, "gated"), h)))(h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_no_gradient_asked_for_keeps_no_per_token_residual(small_chunks):
    """``scored`` made from the batch: the forward rule keeps ``dh`` and
    ``dw`` and nothing of [B, S] float32."""
    set_topology(MeshTopology(devices=jax.devices()[:1]))
    h, w, targets, weights = _inputs()
    _, vjp = jax.vjp(lambda h, w: head_nll_sum(
        h, w, targets, weights, False, "plain"), h, w)
    kept = [a.shape for a in jax.tree.leaves(vjp)
            if hasattr(a, "shape") and a.dtype == jnp.float32]
    assert (B, S) not in kept and (B * S,) not in kept
    _, vjp = jax.vjp(lambda h, w, s: head_nll_sum(
        h, w, targets, s, False, "weighted"), h, w, weights)
    kept = [a.shape for a in jax.tree.leaves(vjp) if hasattr(a, "shape")]
    assert (B, S) in kept


def _ones_and_zeros_case(dtype, tied):
    """(loss bits, sha256 of dh and dw, sha256 of the lowered text) of
    ``head_token_loss`` on a packed batch: as the digests were taken."""
    B, S, D, V = 4, 48, 16, 96
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    h = jax.random.normal(k[0], (B, S, D)).astype(dtype)
    w = (jax.random.normal(k[1], (V, D) if tied else (D, V)) * 0.3
         ).astype(dtype)
    ids = jax.random.randint(k[2], (B, S), 0, V)
    seg = jnp.asarray(np.tile(
        (np.arange(S) >= 17).astype(np.int32) + (np.arange(S) >= 30),
        (B, 1)))
    batch = {"input_ids": ids, "segment_ids": seg}
    set_topology(MeshTopology(devices=jax.devices()[:1]))
    f = jax.jit(jax.value_and_grad(
        lambda h, w: head_token_loss(h, w, batch, tied=tied),
        argnums=(0, 1)))
    jax.clear_caches()
    text = f.lower(h, w).as_text()
    loss, (dh, dw) = f(h, w)
    bits = int(np.asarray(loss, np.float32).view(np.uint32))
    sha = hashlib.sha256(
        np.asarray(dh.astype(jnp.float32)).tobytes()
        + np.asarray(dw.astype(jnp.float32)).tobytes()).hexdigest()
    return [bits, sha, hashlib.sha256(text.encode()).hexdigest()]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_a_ones_and_zeros_caller_is_the_parents_to_the_bit(dtype, tied):
    assert _ones_and_zeros_case(jnp.dtype(dtype), tied) == PARENTS["head"][
        f"head[{dtype}, {'tied' if tied else 'untied'}]"]


@pytest.mark.parametrize("family", sorted(flash_step_texts.HEAD_FAMILIES))
def test_a_neighbours_step_lowers_to_the_parents_text(family):
    assert flash_step_texts.digest(family) == PARENTS["steps"][family]


def test_every_digest_has_its_case_and_every_family_its_digest():
    assert set(PARENTS["steps"]) == set(flash_step_texts.HEAD_FAMILIES)
    assert len(PARENTS["head"]) == 4
    # the benchmark's twelve families, by the file that holds each
    held = set(flash_step_texts.FAMILIES) \
        | set(flash_step_texts.NEIGHBOUR_FAMILIES) \
        | set(flash_step_texts.HEAD_FAMILIES)
    assert held == {"gpt2", "olmoe", "qwen3_next", "nemotron_h", "joyai",
                    "xing", "laguna", "mellum", "kimi_linear", "phi4flash",
                    "minicpm_sala", "granite_hybrid"}
