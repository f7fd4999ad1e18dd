"""Kimi-Linear's share of an expert-parallel layer and the rest of what
the family states (tests/test_kimi_linear.py's toy model, seeded weights,
packed batch and reference): the shares' parts add up to the uncut layer
and the uncut model is the uncut reference; the routed rows' count; what it
refuses by name; the published size and the configuration file's cut.  A
file of its own so that ``--dist loadfile`` gives the family's tests to
three workers."""
import functools
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import kimi_linear
from deepspeed_tpu.models.kimi_linear import (KDA, MLA, KimiLinearConfig,
                                              count_params,
                                              kimi_linear_model)
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.moe import layer as moe_layer
from tests.test_kimi_linear import (  # noqa: F401 (the fixtures come by name)
    B, LOSS_TOL, REPO, S, _isolation, micro, packed_batch, reference_loss,
    seeded_params, seeded_toy, sizes_of, toy_model)


# ------------------------------------------------------- the share's sums
def _held(params, offset, n):
    return {k: (w[offset:offset + n] if k in ("w_in", "w_out", "w_gate")
                else w) for k, w in params.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test on a whole expert layer of each kind: the
    routed parts of all four shares (4 experts of 16 each) plus what every
    chip computes alike — the mixer and the shared expert, counted once —
    are the uncut block's output, and the uncut block is the uncut
    reference layer's; the router loss is the same on every share."""
    whole_model = toy_model(experts_held=None, expert_offset=0)
    cfg = whole_model.config
    params = seeded_params(whole_model)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    routed_only = replace(cfg.moe, shared_expert_d_ff=0)
    for kind in (KDA, MLA):
        layer = jax.tree.map(lambda a: a[0, 0], params["blocks"]["run1"][kind])
        whole, (aux, _) = jax.jit(lambda x, layer: kimi_linear._expert_block(
            x, layer, cfg, kind, train=True))(x, layer)
        mixed = kimi_linear._mixed(x, layer, cfg, kind, None)
        h = kimi_linear._rms_norm(mixed, layer["mlp_norm"], cfg.norm_eps)
        total = mixed + moe_layer.moe_layer(layer["moe"], h, cfg.moe)[0] \
            - moe_layer.moe_layer(layer["moe"], h, routed_only)[0]
        for i in range(4):
            part_cfg = replace(routed_only, expert_offset=4 * i,
                               experts_held=4)
            part, aux_i, stats = moe_layer.moe_layer(
                _held(layer["moe"], 4 * i, 4), h, part_cfg,
                return_stats=True)
            assert int(stats["dropped"]) == 0
            assert float(aux_i) == pytest.approx(float(aux), rel=1e-5)
            assert float(jnp.abs(part).max()) > 0
            total = total + part
        np.testing.assert_allclose(total, whole, atol=1e-5 * float(
            jnp.abs(whole).max()))


def test_the_uncut_model_is_the_uncut_reference():
    # the lead, K K M: both kinds of expert layer
    model = toy_model(experts_held=None, expert_offset=0, num_layers=4)
    params, mb = seeded_params(model), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(model.loss)(params, mb))
        want = float(jax.jit(functools.partial(
            reference_loss, sizes=sizes_of(model)))(params, mb))
    assert abs(got - want) < LOSS_TOL, (got, want)


# ------------------------------------------------------- the rest of it
def test_zero3_and_streaming_refuse_clearly():
    model, _, mb = seeded_toy()
    params = model.init(jax.random.PRNGKey(0))
    with param_stream_scope(True, mode="gather"):
        with pytest.raises(NotImplementedError, match="ZeRO stage 0-2"):
            model.loss(params, mb)


@pytest.mark.parametrize("entry", ["init_cache_fn", "prefill_fn",
                                   "decode_fn", "verify_fn"])
def test_serving_entry_points_name_the_missing_piece(entry):
    with pytest.raises(NotImplementedError, match="recurrent state"):
        getattr(toy_model(), entry)(None, None, None)


def test_routed_rows_count_every_choice_of_every_expert_layer():
    model = toy_model(num_layers=4)
    rows = jax.jit(model.meta["routed_rows"])(
        seeded_params(model), micro(packed_batch()))
    assert rows.shape == (3, 16)
    assert [int(r) for r in rows.sum(1)] == [B * S * 4] * 3


def test_the_size_is_the_published_one_and_the_cut_is_the_files():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    whole = KimiLinearConfig()
    assert count_params(whole) == config["published"]["n_params"] \
        == 49_122_681_728
    assert whole.layer_kinds.count("K") == 20
    assert whole.layer_kinds.count("M") == 7
    model = kimi_linear_model(**config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        assert have == want, key
    assert model.meta["n_params"] == 903_464_896
    # every width under the source's own key
    cut, linear = model.config, config["linear_attn_config"]
    assert (cut.d_model, cut.d_ff_dense, cut.d_ff, cut.kda_num_heads,
            cut.kda_head_dim, cut.short_conv_kernel_size, cut.num_heads,
            cut.kv_lora_rank, cut.qk_nope_head_dim, cut.qk_rope_head_dim,
            cut.v_head_dim, cut.num_experts, cut.top_k,
            cut.routed_scaling_factor, cut.norm_eps) == (
        config["hidden_size"], config["intermediate_size"],
        config["moe_intermediate_size"], linear["num_heads"],
        linear["head_dim"], linear["short_conv_kernel_size"],
        config["num_attention_heads"], config["kv_lora_rank"],
        config["qk_nope_head_dim"], config["qk_rope_head_dim"],
        config["v_head_dim"], config["published"]["num_experts"],
        config["num_experts_per_token"], config["routed_scaling_factor"],
        config["rms_norm_eps"])
    assert config["q_lora_rank"] is None and config["mla_use_nope"] is True
    assert list(cut.kda_layers) == linear["kda_layers"]
    assert list(cut.full_attn_layers) == linear["full_attn_layers"]
    assert (cut.num_layers, cut.experts_held, cut.vocab_size) == (
        config["num_hidden_layers"], config["num_experts"],
        config["vocab_size"]) == (8, 8, 20480)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    # 6 KDA : 2 MLA, the published 3 : 1
    assert cut.layer_kinds == "KKKMKKKM"
    # every routed row of a micro-batch fits the held plan
    assert cut.held_rows_factor * cut.experts_held == cut.num_experts
