"""Ouro (models/ouro.py) through the normal path at toy size on the CPU,
against the plain reference the benchmark uses
(benchmarks/references/ouro.py — this file imports that same file, there is
no second copy): the loss, the four passes' logits, the four exit masses
and every leaf's gradient on seeded weights and a packed batch of three
documents; nothing crosses a document; the reference itself against
float64 numpy written out by hand; one control for each thing the
configuration's file only assumes — each planted in the PROGRAM and shown
to leave the tolerance — and the precision control; the loop's account,
the published count and what is refused.

Everything is float32 with seeded weights: the two sides differ in the
order of summation alone.  The tests that build an engine are
tests/test_ouro_engine.py; the weighted head is tests/test_weighted_head.py."""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import ouro
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.models.ouro import OuroConfig, count_params, ouro_model
from deepspeed_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "ouro_reference",
    os.path.join(REPO, "benchmarks", "references", "ouro.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 5e-7; the six controls 0.02 ... 3.4
LOGIT_TOL = 2e-4        # max |a - b| over a pass's logits; measured < 1e-7
MASS_TOL = 2e-5         # max |a - b| over p; measured 1.5e-7
GRAD_TOL = 2e-4         # max |a - b| / max |b| per leaf; measured 2e-6

TOY = dict(num_layers=3, total_ut_steps=4, d_model=64, num_heads=4,
           num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=512,
           max_seq_len=128, dtype="float32", remat=True)
B, S, DOCS = 2, 72, 3


def toy_model(**overrides):
    return ouro_model("2.6b", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: norm weights away from
    their start, attention scores and logits wide, the gates away from a
    half."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if name.endswith("norm"):
            return w + 0.3 * jax.random.normal(sub, w.shape)
        if name in ("wq", "wk"):
            return w * 30.0
        if name == "w":                 # the gate: logits of std ~ 1
            return w * 5.0
        if name == "b":
            return w - 0.4
        if name == "lm_head":
            return w * 6.0
        return w * 4.0 if w.ndim == 3 else w

    return jax.tree_util.tree_map_with_path(push, params)


def packed_batch(seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TOY["vocab_size"], size=(B, S), dtype=np.int32)
    cuts = np.sort(rng.integers(1, S, size=(B, DOCS - 1)), axis=-1)
    cuts[0] = (15, 16)            # a one-token document
    segments = (np.arange(S)[None, :, None]
                >= cuts[:, None, :]).sum(-1).astype(np.int32)
    return {"input_ids": jnp.asarray(ids),
            "segment_ids": jnp.asarray(segments)}


def reference_numbers(params, batch, sizes, **kwargs):
    """(loss, gradients) of the plain reference."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: reference.micro_batch_loss(
            p, batch["input_ids"], batch.get("segment_ids"), sizes,
            block=36, **kwargs))(params)


def reference_exits(params, batch, sizes):
    """(logits [T, B, S, V], p [T, B, S]) of the plain reference."""
    with jax.default_matmul_precision("highest"):
        return reference.micro_batch_loss(
            params, batch["input_ids"], batch.get("segment_ids"), sizes,
            block=36, output="exits")


def program_numbers(model, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(model.loss))(params, batch)


@functools.lru_cache(maxsize=None)
def toy():
    """(model, seeded weights, the packed batch, the reference's (loss,
    gradients) and (logits, p) there)."""
    model = toy_model()
    params, batch = seeded_params(model), packed_batch()
    sizes = sizes_of(model)
    return (model, params, batch, reference_numbers(params, batch, sizes),
            reference_exits(params, batch, sizes))


def worst_leaf(got, want):
    """The largest max |a - b| / max |b| over the leaves, and its name."""
    rel = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (float(jnp.max(jnp.abs(a - b))
                                  / (jnp.max(jnp.abs(b)) + 1e-30)),
                            jax.tree_util.keystr(path)), got, want)
    return max(jax.tree.leaves(rel, is_leaf=lambda x: isinstance(x, tuple)))


# ------------------------------------------------- program against reference
def test_loss_and_every_gradient_leaf_are_the_references():
    model, params, batch, (want, want_grads), _ = toy()
    got, grads = program_numbers(model, params, batch)
    assert abs(float(got) - float(want)) < LOSS_TOL, (got, want)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    # no leaf is dead: the gate's bias and the final norm among them
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(want_grads))
    worst = worst_leaf(grads, want_grads)
    assert worst[0] < GRAD_TOL, worst


def test_the_four_passes_logits_and_exit_masses_are_the_references():
    model, params, batch, _, (want_logits, want_p) = toy()
    with jax.default_matmul_precision("highest"):
        logits, p = jax.jit(model.meta["exit_logits"])(params, batch)
    assert logits.shape == (B, 4, S, TOY["vocab_size"])
    for t in range(4):
        assert float(jnp.max(jnp.abs(logits[:, t] - want_logits[t]))) \
            < LOGIT_TOL, t
        assert float(jnp.max(jnp.abs(p[:, t] - want_p[t]))) < MASS_TOL, t
    np.testing.assert_allclose(np.asarray(p).sum(1), 1.0, atol=1e-6)
    # the passes differ (a loop that ran once would repeat its logits),
    # and every pass has mass to lose
    assert float(jnp.max(jnp.abs(logits[:, 3] - logits[:, 0]))) > 0.5
    assert 1e-3 < float(p.min()) and float(p.max()) < 0.99
    assert float(p.std()) > 0.1
    # model.apply is the last pass's
    with jax.default_matmul_precision("highest"):
        last = jax.jit(model.apply)(params, batch)
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits[:, 3]),
                               atol=1e-5)


def test_the_per_token_objectives_mean_is_the_loss():
    model, params, batch, (want, _), _ = toy()
    host = {k: np.asarray(v) for k, v in batch.items()}
    objectives, scored = reference.token_objectives(
        params, host, sizes_of(model), chunk=1)
    assert float(objectives[scored].mean()) == pytest.approx(
        float(want), abs=1e-5)
    last, scored_too = reference.token_losses(params, host, sizes_of(model),
                                              chunk=1)
    assert (scored == scored_too).all()
    # a one-token document scores nothing; a sequence's last position too
    assert not scored[0, 15] and not scored[:, -1].any()
    assert scored.sum() == B * S - B * DOCS


def test_nothing_crosses_a_document():
    """The tokens of the third document changed: the first two documents'
    logits of every pass, and their exit distribution, stay to the bit."""
    model, params, batch, _, _ = toy()
    seg = np.asarray(batch["segment_ids"])
    ids = np.asarray(batch["input_ids"]).copy()
    ids[seg == 2] = (ids[seg == 2] + 7) % TOY["vocab_size"]
    exits = jax.jit(model.meta["exit_logits"])
    logits, p = exits(params, batch)
    moved_logits, moved_p = exits(
        params, {"input_ids": jnp.asarray(ids),
                 "segment_ids": batch["segment_ids"]})
    same = (seg < 2)[:, None, :]
    assert np.array_equal(np.asarray(logits)[np.broadcast_to(
        same[..., None], logits.shape)], np.asarray(moved_logits)[
            np.broadcast_to(same[..., None], logits.shape)])
    assert np.array_equal(np.asarray(p)[np.broadcast_to(same, p.shape)],
                          np.asarray(moved_p)[np.broadcast_to(same, p.shape)])
    assert not np.array_equal(np.asarray(logits), np.asarray(moved_logits))


# --------------------------------------- the reference, written out by hand
def test_the_reference_is_the_equations_in_float64_numpy():
    """One layer, two passes, one packed sequence: every equation of the
    reference's docstring written out with numpy in float64."""
    model = toy_model(num_layers=1, total_ut_steps=2)
    params = seeded_params(model, seed=3)
    sizes = sizes_of(model)
    batch = {k: v[:1, :24] for k, v in packed_batch(seed=2).items()}
    ids = np.asarray(batch["input_ids"])[0]
    seg = np.asarray(batch["segment_ids"])[0]
    P = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    n, hd, H, eps = len(ids), 16, 4, sizes["norm_eps"]

    def N(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def turn(x):                                   # [n, H, hd]
        f = sizes["rope_theta"] ** (-np.arange(0, hd, 2) / hd)
        ang = np.arange(n)[:, None] * f[None]
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return np.concatenate([a * c - b * s, a * s + b * c], -1)

    L0 = {k: v[0] for k, v in P["blocks"].items()}
    x, nll, lam = P["wte"][ids], [], []
    for _ in range(2):
        a = N(x, L0["attn_norm"])
        q = turn((a @ L0["wq"]).reshape(n, H, hd))
        k = turn((a @ L0["wk"]).reshape(n, H, hd))
        v = (a @ L0["wv"]).reshape(n, H, hd)
        o = np.zeros((n, H, hd))
        for i in range(n):
            keys = [j for j in range(i + 1) if seg[j] == seg[i]]
            for h in range(H):
                sc = np.array([q[i, h] @ k[j, h] for j in keys]) \
                    / np.sqrt(hd)
                w = np.exp(sc - sc.max())
                o[i, h] = (w / w.sum()) @ v[keys, h]
        x = x + N(o.reshape(n, H * hd) @ L0["wo"], L0["attn_out_norm"])
        u = N(x, L0["mlp_norm"])
        g = u @ L0["w_gate"]
        x = x + N(((g / (1 + np.exp(-g))) * (u @ L0["w_up"]))
                  @ L0["w_down"], L0["mlp_out_norm"])
        x = N(x, P["final_norm"])
        logits = x @ P["lm_head"]
        lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
            + logits.max(-1)
        nll.append(lse - logits[np.arange(n), np.roll(ids, -1)])
        lam.append(1 / (1 + np.exp(-(x @ P["exit_gate"]["w"]
                                     + P["exit_gate"]["b"]))))
    p = np.stack([lam[0], 1 - lam[0]])
    objective = (p * np.stack(nll)).sum(0) \
        + sizes["exit_entropy_beta"] * (p * np.log(p)).sum(0)
    scored = (seg == np.roll(seg, -1)) & (np.arange(n) < n - 1)
    want = objective[scored].mean()
    got, _ = reference_numbers(params, batch, sizes)
    assert float(got) == pytest.approx(want, abs=2e-5)
    host = {k: np.asarray(v) for k, v in batch.items()}
    objectives, _ = reference.token_objectives(params, host, sizes, chunk=1)
    np.testing.assert_allclose(objectives[0][scored], objective[scored],
                               atol=1e-4)
    last, _ = reference.token_losses(params, host, sizes, chunk=1)
    np.testing.assert_allclose(last[0][scored], nll[1][scored], atol=1e-4)


# ------------------------------------------------- what the file only assumes
def _fresh_weights_a_pass(monkeypatch):
    """Passes after the first read another draw of the layers' weights."""
    apply = ouro._application

    def fresh(y, i, config, blocks, final_norm, segment_ids):
        later = i // config.num_layers > 0
        other = jax.tree.map(lambda a: jnp.where(later, jnp.flip(a, -1), a),
                             blocks)
        return apply(y, i, config, other, final_norm, segment_ids)

    monkeypatch.setattr(ouro, "_application", fresh)


def _no_norm_on_the_branch_output(monkeypatch):
    monkeypatch.setattr(ouro, "_add_branch",
                        lambda y, out, scale, eps: y + out)


def _final_norm_after_the_last_pass_alone(monkeypatch):
    monkeypatch.setattr(
        ouro, "_ends_a_pass",
        lambda i, config: i == config.applications - 1)


def _the_last_gate_decides(monkeypatch):
    """``p^T = lam^T * survival``: the remainder is nobody's."""
    def wrong(states, gate):
        z = jnp.einsum("btsd,d->bts", states, gate["w"]) + gate["b"]
        stays = jax.nn.log_sigmoid(-z)
        return jax.nn.log_sigmoid(z) + jnp.cumsum(stays, axis=1) - stays
    monkeypatch.setattr(ouro, "exit_log_probabilities", wrong)


#: name -> (a patch of the PROGRAM or None, overrides of its builder).  The
#: reference keeps the equations; the loss then has to leave the tolerance.
#: One for each key of the configuration's ``assumed`` that is an equation
#: (benchmarks/configs/ouro-2.6b.json), and for the two the catalog gives
#: that the loop rests on.
CONTROLS = {
    "three_passes_for_four": (None, dict(total_ut_steps=3)),
    "fresh_weights_a_pass": (_fresh_weights_a_pass, {}),
    "no_norm_on_the_branch_output": (_no_norm_on_the_branch_output, {}),
    "final_norm_after_the_last_pass_alone": (
        _final_norm_after_the_last_pass_alone, {}),
    "no_entropy_bonus": (None, dict(exit_entropy_beta=0.0)),
    "the_last_gate_decides": (_the_last_gate_decides, {}),
}


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_a_planted_departure_leaves_the_tolerance(name, monkeypatch):
    patch, overrides = CONTROLS[name]
    _, params, batch, (want, _), _ = toy()
    if patch:
        patch(monkeypatch)
    got, _ = program_numbers(toy_model(**overrides), params, batch)
    assert abs(float(got) - float(want)) > 50 * LOSS_TOL, (name, got, want)


def test_the_precision_control_fp8_is_outside_and_bf16_inside():
    """What drivers/train_steps_counted.py compares — the scored positions'
    last-pass losses one by one, as the root of the mean squared
    difference — of the reference with every matrix product's operands
    rounded: bf16 is the engine's own arithmetic and stays inside
    TOKEN_NLL_RMS_ATOL, fp8 e4m3 lands outside; and bf16 arithmetic fails
    the gradient comparison the float32 program passes."""
    model, seeded, batch, (_, want_grads), _ = toy()
    host = {k: np.asarray(v) for k, v in batch.items()}
    sizes = sizes_of(model)
    # at toy size the weights as drawn, scaled up until the logits matter
    params = jax.tree.map(lambda a: a * 2.5,
                          model.init(jax.random.PRNGKey(0)))
    exact, scored = reference.token_losses(params, host, sizes, chunk=1)

    def rms(dtype):
        got, _ = reference.token_losses(params, host, sizes, chunk=1,
                                        matmul_dtype=dtype)
        return float(np.sqrt(np.mean(np.square(got - exact)[scored])))

    bf16, fp8 = rms(jnp.bfloat16), rms(jnp.float8_e4m3fn)
    assert bf16 < reference.TOKEN_NLL_RMS_ATOL < fp8, (bf16, fp8)
    _, rounded = reference_numbers(seeded, batch, sizes,
                                   matmul_dtype=jnp.bfloat16)
    assert worst_leaf(rounded, want_grads)[0] > 5 * GRAD_TOL


# -------------------------------------------- the loop, the count, refusals
def test_the_loop_sums_a_leafs_four_uses_into_one_stacked_gradient():
    """ONE scan over the 12 applications, whose backward pass carries one
    accumulator a stacked leaf (a scan of passes around a scan of layers
    would carry two levels of them)."""
    model, params, batch, _, _ = toy()
    jaxpr = jax.make_jaxpr(jax.grad(model.loss))(params, batch)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    loops = [e for e in scans if e.params["length"] == 12]
    assert len(loops) == 2 and not [
        e for e in scans if e.params["length"] in (3, 4)]   # fwd and bwd
    backward = loops[-1]
    stacked = [v.aval.shape for v in backward.outvars
               if v.aval.shape[:1] == (3,)]
    assert sorted(stacked) == sorted(
        a.shape for a in jax.tree.leaves(params["blocks"]))


def test_layer_loops_and_head_chunks_state_what_was_traced():
    model, params, batch, _, _ = toy()
    with tracing.step_account("train/step"):
        jax.eval_shape(jax.grad(model.loss), params, batch)
    (loop,) = tracing.layer_loops()
    assert loop == {
        "name": "ouro", "passes": 4, "layers": 3, "applications": 12,
        "shared_param_bytes": 4 * sum(
            a.size for a in jax.tree.leaves(params["blocks"])),
        "saved_carry_bytes": 12 * B * S * 64 * 4}
    (head,) = tracing.head_chunks()
    assert (head["name"], head["tokens"], head["vocab"], head["tied"]) \
        == ("exits", 4 * B * S, 512, False)
    tracing.reset_programs()


def test_the_published_count_to_the_digit():
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    whole = 48 * layer + 2 * 49152 * 2048 + 2048 + 2049
    assert whole == 2_667_974_657 == count_params(OuroConfig())
    assert count_params(OuroConfig(num_layers=12)) == 817_991_681
    assert count_params(OuroConfig(num_layers=8)) == 612_438_017
    model = ouro_model("2.6b", num_layers=12)
    applied = 4 * (12 * (layer - 4 * 2048) + 2048 * 49152 + 2048)
    assert model.meta["n_params"] == 817_991_681
    assert model.meta["applied_params"] == applied == 2_868_912_128
    assert model.meta["ut_steps"] == 4
    assert model.flops_per_token == 6.0 * applied
    # the head is 14.0% of the weights a token multiplies here, 3.9% whole
    assert 4 * 2048 * 49152 / applied == pytest.approx(0.140, abs=1e-3)
    assert 2048 * 49152 / (48 * (layer - 8192) + 2048 * 49152 + 2048) \
        == pytest.approx(0.039, abs=1e-3)


@pytest.mark.parametrize("entry", ["init_cache_fn", "prefill_fn",
                                   "decode_fn", "verify_fn"])
def test_serving_refuses_by_name(entry):
    with pytest.raises(NotImplementedError, match="ouro: .* a key/value "
                                                  "cache a \\(pass, layer\\)"):
        getattr(toy_model(), entry)()


def test_param_streaming_is_refused_and_bad_sizes_too():
    model, params, batch, _, _ = toy()
    with param_stream_scope(True, mode="gather", layer_specs=[]):
        with pytest.raises(NotImplementedError, match="ouro: ZeRO-3"):
            model.loss(params, batch)
    with pytest.raises(ValueError, match="at least one layer and one pass"):
        toy_model(total_ut_steps=0)
    with pytest.raises(ValueError, match="unknown size"):
        ouro_model("2.6B")
