"""The plain Granite 4.0-H reference against a dense computation by hand at
a tiny size (einsums over whole arrays, a Python loop over tokens, every
expert computed for every token and masked: nothing of the reference's own
structure) and against models/granite_hybrid.py, float32, on the CPU — the
gradients, the shares' sums and the four scalars' controls are
tests/test_granite_hybrid.py's, on this same file — and the controls its
two tolerances have to catch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.granite_hybrid import granite_hybrid_model
from references import granite_hybrid as reference

TOY = dict(num_layers=4,
           layer_types=("mamba", "attention", "mamba", "mamba"), d_model=64,
           num_heads=8, num_kv_heads=4, head_dim=8, mamba_num_heads=8,
           mamba_head_dim=8, ssm_state_size=16, chunk_size=16, d_ff=32,
           shared_expert_d_ff=64, num_experts=16, top_k=4, experts_held=4,
           expert_offset=4, mamba_heads_held=4, attn_heads_held=4,
           kv_heads_held=2, head_share=1, held_rows_factor=4,
           vocab_size=512, max_seq_len=128, dtype="float32")


def _setup(scale=1.0, **overrides):
    model = granite_hybrid_model("4.0-h-small", **{**TOY, **overrides})
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("A_log", "dt_bias") else
        a * scale, model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    gas, batch, seq = 2, 3, 72
    ids = rng.integers(0, 512, size=(gas, batch, seq), dtype=np.int32)
    cuts = np.sort(rng.integers(1, seq, size=(gas, batch, 3)), axis=-1)
    cuts[0, 0] = (15, 16, 17)     # a one-token document inside a chunk
    cuts[0, 1] = (16, 32, 48)     # boundaries at the chunks' edges
    data = {"input_ids": ids,
            "segment_ids": (np.arange(seq)[None, None, :, None]
                            >= cuts[:, :, None, :]).sum(-1).astype(np.int32)}
    sizes = {k: getattr(model.config, k) for k in reference.SIZES}
    return model, params, data, sizes


def _model_loss(model, params, data):
    with jax.default_matmul_precision("highest"):
        return np.mean([float(model.loss(
            params, {k: jnp.asarray(v[g]) for k, v in data.items()}))
            for g in range(2)])


def by_hand(params, ids, seg, s):
    """The section-1 equations for one sequence, written out whole: ids,
    seg [S] -> (mean cross-entropy inside documents + the router loss)."""
    S = len(ids)
    norm = lambda x, w: x / np.sqrt((x * x).mean(-1, keepdims=True)
                                    + s["norm_eps"]) * w
    silu = lambda x: x / (1 + np.exp(-x))
    same = seg[:, None] == seg[None, :]
    E = np.asarray(params["wte"], np.float64)
    x = s["embedding_multiplier"] * E[ids]
    seen, balance = {"ssm": 0, "attn": 0}, 0.0
    for letter in s["layer_kinds"]:
        kind = reference.KINDS[letter]
        p = jax.tree.map(lambda a: np.asarray(a[0, seen[kind]], np.float64),
                         params["blocks"][kind])
        seen[kind] += 1
        h = norm(x, p["norm"])
        if kind == "attn":
            H, KV, hd = s["attn_heads_held"], s["kv_heads_held"], \
                s["head_dim"]
            q = (h @ p["wq"]).reshape(S, H, hd)
            k = np.repeat((h @ p["wk"]).reshape(S, KV, hd), H // KV, 1)
            v = np.repeat((h @ p["wv"]).reshape(S, KV, hd), H // KV, 1)
            scores = np.einsum("qhd,khd->hqk", q, k) \
                * s["attention_multiplier"]
            scores = np.where(same & np.tri(S, dtype=bool), scores, -np.inf)
            probs = np.exp(scores - scores.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            out = np.einsum("hqk,khd->qhd", probs, v).reshape(S, -1) \
                @ p["wo"]
        else:
            Hm, Pd, N = s["mamba_heads_held"], s["mamba_head_dim"], \
                s["ssm_state_size"]
            d = Hm * Pd
            zxbcdt = h @ p["w_in"]
            z, xbc = zxbcdt[:, :d], zxbcdt[:, d:2 * d + 2 * N]
            dt = np.log1p(np.exp(zxbcdt[:, 2 * d + 2 * N:] + p["dt_bias"]))
            conv = np.zeros_like(xbc)
            for t in range(S):
                for back in range(4):
                    if t - back >= 0 and seg[t - back] == seg[t]:
                        conv[t] += xbc[t - back] * p["conv_w"][3 - back]
            xbc = silu(conv + p["conv_b"])
            xs, Bm, Cm = xbc[:, :d].reshape(S, Hm, Pd), xbc[:, d:d + N], \
                xbc[:, d + N:]
            A = -np.exp(p["A_log"])
            state, y = np.zeros((Hm, Pd, N)), np.zeros((S, Hm, Pd))
            for t in range(S):
                if t == 0 or seg[t] != seg[t - 1]:
                    state = np.zeros_like(state)
                state = np.exp(dt[t] * A)[:, None, None] * state \
                    + (dt[t][:, None] * xs[t])[:, :, None] * Bm[t]
                y[t] = state @ Cm[t] + p["D"][:, None] * xs[t]
            out = norm(y.reshape(S, d) * silu(z), p["gate_norm"]) \
                @ p["w_out"]
        x = x + s["residual_multiplier"] * out
        h, moe = norm(x, p["mlp_norm"]), p["moe"]
        logits = h @ moe["router"]
        chosen = np.argsort(-logits, -1)[:, :s["top_k"]]
        sent = np.zeros_like(logits)
        np.put_along_axis(sent, chosen, 1.0, -1)
        top = np.where(sent > 0, logits, -np.inf)
        weights = np.exp(top - top.max(-1, keepdims=True))
        weights /= weights.sum(-1, keepdims=True)
        ffn = lambda g, u, dn: (silu(h @ g) * (h @ u)) @ dn
        out = ffn(moe["shared_gate"], moe["shared_in"], moe["shared_out"])
        for e in range(s["experts_held"]):
            out = out + weights[:, s["expert_offset"] + e, None] * ffn(
                moe["w_gate"][e], moe["w_in"][e], moe["w_out"][e])
        x = x + s["residual_multiplier"] * out
        soft = np.exp(logits - logits.max(-1, keepdims=True))
        soft /= soft.sum(-1, keepdims=True)
        balance += s["num_experts"] * (sent.mean(0) * soft.mean(0)).sum()
    logits = norm(x, np.asarray(params["final_norm"], np.float64)) @ E.T \
        / s["logits_scaling"]
    nll = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1) - logits[np.arange(S), np.roll(ids, -1)]
    scored = (seg == np.roll(seg, -1)) & (np.arange(S) < S - 1)
    return nll[scored].mean() + s["aux_loss_coef"] * balance, nll, scored


def test_reference_is_the_equations_written_out_by_hand():
    """One packed sequence of a share (four of eight heads of each kind,
    experts 4..7 of 16), float64 numpy against the float32 reference: the
    loss and every scored position's."""
    _, params, data, sizes = _setup(scale=3.0)
    ids, seg = data["input_ids"][0, :1], data["segment_ids"][0, :1]
    want, want_nll, scored = by_hand(params, ids[0], seg[0], sizes)
    got = reference.step_loss(
        params, {"input_ids": ids[None], "segment_ids": seg[None]}, sizes,
        chunk=1)
    assert abs(got - want) < 2e-5, (got, want)
    nll, ref_scored = reference.token_losses(
        params, {"input_ids": ids, "segment_ids": seg}, sizes, chunk=1)
    np.testing.assert_array_equal(ref_scored[0], scored)
    assert np.abs(nll[0] - want_nll)[scored].max() < 1e-4


@pytest.mark.parametrize("packed", [False, True])
def test_reference_matches_the_model(packed):
    model, params, data, sizes = _setup()
    if not packed:
        data = {"input_ids": data["input_ids"]}
    got = reference.step_loss(params, data, sizes, chunk=1)
    want = _model_loss(model, params, data)
    # float32 both sides; the chunked scan against the per-token one
    assert abs(got - want) < 2e-5, (got, want)


def test_reference_matches_the_model_uncut():
    model, params, data, sizes = _setup(
        experts_held=None, expert_offset=0, mamba_heads_held=None,
        attn_heads_held=None, kv_heads_held=None, head_share=0)
    got = reference.step_loss(params, data, sizes, chunk=1)
    assert abs(got - _model_loss(model, params, data)) < 2e-5


def test_token_by_token_catches_fp8_and_not_bf16():
    """The control (PERF.md section 2, PR 66) on what
    drivers/train_steps_counted.py compares: the scored positions' losses
    one by one, as the root of the mean squared difference, of the
    reference with every matrix product's operands rounded to a lower
    precision.  bf16 is the engine's own arithmetic and has to stay inside
    TOKEN_NLL_RMS_ATOL; the next precision below, fp8 e4m3, has to land
    outside.  At toy size the weights are scaled up until the logits are
    as loud as the cell's (std 0.08).  The per-token losses' mean is
    step_loss's cross-entropy."""
    _, params, data, sizes = _setup(scale=4.0)
    micro = {k: v[0] for k, v in data.items()}
    exact, scored = reference.token_losses(params, micro, sizes, chunk=1)
    mean = reference.step_loss(
        params, {k: v[:1] for k, v in data.items()},
        {**sizes, "aux_loss_coef": 0.0}, chunk=1)
    assert float(exact[scored].mean()) == pytest.approx(mean, abs=1e-5)

    def rms(dtype):
        got, _ = reference.token_losses(params, micro, sizes, chunk=1,
                                        matmul_dtype=dtype)
        return float(np.sqrt(np.mean(np.square(got - exact)[scored])))

    bf16, fp8 = rms(jnp.bfloat16), rms(jnp.float8_e4m3fn)
    assert bf16 < reference.TOKEN_NLL_RMS_ATOL < fp8, (bf16, fp8)


def test_the_mean_loss_keeps_bf16_inside():
    """LOSS_ATOL on the first step's mean loss: the bf16 control stays
    inside it (whether fp8 lands outside is the chip's reading: PERF.md
    section 2, PR 66)."""
    _, params, data, sizes = _setup()
    exact = reference.step_loss(params, data, sizes, chunk=1)
    bf16 = reference.step_loss(params, data, sizes, chunk=1,
                               matmul_dtype=jnp.bfloat16)
    assert abs(bf16 - exact) < reference.LOSS_ATOL, bf16 - exact
