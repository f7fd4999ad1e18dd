"""The lowered text of a toy training step's forward and backward (the
flash kernels in Pallas' interpreter, so their bodies are in the text) for
each family the benchmark held before the kernels took a value head of
another width than the score head — what
tests/test_flash_head_widths.py holds to the digests taken at the parent
commit (tests/data/flash_step_digests.json).

The text depends on the process's devices (the tests' eight virtual ones
pin intermediate layouts), so the digests are taken in the tests' own
environment, from the root of a checkout:

    python -c "import tests.conftest, json; \
        from tests import flash_step_texts as f; \
        print(json.dumps({n: f.digest(n) for n in f.FAMILIES}, indent=1))"

tests/data/held_prefix_step_digests.json (PR 39) holds the same at that
PR's parent commit (``gpt2`` and ``olmoe`` taken again at PR 49 with
flash_step_digests.json: the flash kernels' tile bodies changed; ``olmoe``
again at PR 59, in both files: the full plan weights a row where its
expert is and its way back is un-gated) with the grouped kernels
interpreted as well, for every family here and in ``HELD_FAMILIES``:
``f.digest(n, grouped_kernels=True)``.

tests/data/kda_neighbour_step_digests.json (PR 60) holds, at that PR's
parent commit, the two families whose ``latent_attention`` learnt to do
without a query latent and without rotary (``NEIGHBOUR_FAMILIES``:
``f.digest(n)``) and the gated delta rule's own call with a decay a head,
as its XLA form and as its kernels interpreted (``f.delta_rule_digest``),
from before the rule took a decay a key channel.

Every family's digest that the tests hold a step to was taken again at PR
69, in all three files (``gpt2``, ``olmoe``, ``qwen3_next`` and
``nemotron_h`` in flash_step_digests.json; ``gpt2`` and ``olmoe`` in both
halves of held_prefix_step_digests.json; ``joyai`` and ``xing`` in
kda_neighbour_step_digests.json): each toy step ends in a loss, and the
loss no longer forms ``[tokens, vocabulary]`` logits — the head's product,
the cross-entropy and their two gradients run a chunk of tokens at a time
(``models/model.py head_token_loss``, a ``custom_vjp`` whose forward finds
both gradients), so every step's text changed on purpose (the layers'
code did not; the values' numbering follows what comes after).
tests/test_head_loss.py holds the new head to ``token_loss`` of whole
logits — loss, ``dh`` and ``dw``; the gated delta rule's two digests
hold no loss and stood.  (Taken twice at PR 69: its review moved the sum
of the chips' shares of the head's gradient, and its rounding, from the
forward rule to the backward rule.)

The four of flash_step_digests.json, and ``gpt2`` and ``olmoe`` in both
halves of held_prefix_step_digests.json, were taken again at PR 71: every
toy batch here is packed, and a packed flash call now hands its kernels
the documents' loop bounds (``ds_flash_attention.document_block_tables``:
one more operand a call, one more bound a tile loop) — ``gpt2`` too, whose
step this file lowers with ``segment_ids``.  What stood: the call with no
``segment_ids`` (tests/data/flash_dense_call_digest.json, taken at that
PR's parent by tests/test_flash_document_skip.py ``dense_call_digests``),
and every family of the other two files, whose toy steps at 64 positions
take the XLA attention.  tests/test_flash_document_skip.py holds the new
packed calls to the old ones' results, to the bit.
"""
import functools
import hashlib

import jax
import jax.numpy as jnp


def _gpt2():
    from deepspeed_tpu.models.gpt2 import gpt2_model
    return gpt2_model(size="custom", vocab_size=128, max_seq_len=64,
                      num_layers=2, num_heads=4, d_model=32, dtype="float32",
                      attention_impl="flash", remat=True)


def _olmoe():
    from deepspeed_tpu.models.mixtral import mixtral_model
    return mixtral_model(
        size="olmoe-1b-7b", num_layers=2, d_model=64, num_heads=2,
        num_kv_heads=2, d_ff=64, num_experts=4, top_k=2, vocab_size=512,
        max_seq_len=128, moe_dispatch="grouped", remat=True,
        attention_impl="flash")


def _qwen3_next():
    from deepspeed_tpu.models.qwen3_next import qwen3_next_model
    return qwen3_next_model(
        "80b-a3b", num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16, d_ff=32,
        shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
        expert_offset=8, vocab_size=512, max_seq_len=128,
        delta_rule_chunk=16, dtype="float32", remat=True,
        attention_impl="flash")


def _nemotron_h():
    from deepspeed_tpu.models.nemotron_h import nemotron_h_model
    return nemotron_h_model(
        "3-nano-30b-a3b", num_layers=5, hybrid_override_pattern="MEM*E",
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=32,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=16, d_ff=32, shared_expert_d_ff=64, num_experts=16,
        top_k=4, experts_held=4, expert_offset=8, vocab_size=512,
        max_seq_len=128, dtype="float32", remat=True,
        attention_impl="flash")


def _joyai():
    from deepspeed_tpu.models.joyai import joyai_model
    return joyai_model(
        "llm-flash", num_layers=3, d_model=64, num_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=10000.0, d_ff_dense=96, d_ff=32,
        shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
        expert_offset=8, vocab_size=512, max_seq_len=128, dtype="float32",
        remat=True)


def _xing():
    from deepspeed_tpu.models.xing import xing_model
    return xing_model(
        "4.0-29b-a4b", num_layers=3, num_dense_layers=1, d_model=64,
        num_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_factor=8.0,
        original_max_position_embeddings=16, d_ff_dense=96, d_ff=32,
        shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
        expert_offset=8, vocab_size=512, max_seq_len=128, dtype="float32",
        remat=True)


def _tiny(module, function, size):
    """A family's own ``tiny`` preset, float32, rematerialised."""
    def build():
        import importlib
        return getattr(importlib.import_module(
            "deepspeed_tpu.models." + module), function)(
                size, **TINY_SIZES[module], dtype="float32", remat=True)
    return build


#: the toy sizes of the families below: their ``tiny`` presets as they
#: stood when the digests were taken (written out, so that a preset that
#: moves does not move a digest)
TINY_SIZES = {
    "laguna": dict(
        vocab_size=256, max_seq_len=128, num_layers=5, d_model=32,
        num_heads_full=4, num_heads_sliding=6, num_kv_heads=2, head_dim=16,
        sliding_window=8, original_max_position_embeddings=16,
        d_ff_dense=64, d_ff=16, num_experts=8, top_k=2,
        shared_expert_d_ff=16),
    "mellum": dict(
        vocab_size=256, max_seq_len=128, num_layers=4, d_model=32,
        num_heads=4, num_kv_heads=2, head_dim=16, sliding_window=8,
        original_max_position_embeddings=16, d_ff=16, num_experts=8,
        top_k=2),
    "kimi_linear": dict(
        vocab_size=256, max_seq_len=128, num_layers=8, d_model=32,
        kda_num_heads=2, kda_head_dim=8, kda_gate_rank=8,
        delta_rule_chunk=16, num_heads=2, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        d_ff_dense=64, d_ff=16, num_experts=8, top_k=2,
        shared_expert_d_ff=16),
    "phi4flash": dict(
        vocab_size=256, max_seq_len=256, num_layers=8, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
        sliding_window=16, mamba_dt_rank=4, scan_chunk=16),
    "minicpm_sala": dict(
        vocab_size=256, max_seq_len=128, num_layers=4,
        mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                     "lightning-attn"),
        d_model=64, d_ff=128, num_heads=4, num_kv_heads=2, head_dim=16,
        block_size=4, kernel_size=2, kernel_stride=1, topk=4,
        init_blocks=1, window_size=8, dense_len=32, attend_query_chunk=16,
        attend_key_spans=2, lightning_heads=4, lightning_head_dim=16,
        scan_chunk=16, mlp_token_tile=32),
    "granite_hybrid": dict(
        vocab_size=256, max_seq_len=128, num_layers=4,
        layer_types=("mamba", "attention", "mamba", "attention"),
        d_model=32, num_heads=4, num_kv_heads=2, head_dim=16,
        mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
        chunk_size=16, d_ff=16, num_experts=8, top_k=2,
        shared_expert_d_ff=32),
}
#: the families of the benchmark that none of the three sets below holds
#: (tests/test_weighted_head.py: their steps at PR 70's parent commit,
#: from before ``head_nll_sum`` took weights that are not ones and zeros)
HEAD_FAMILIES = {
    "laguna": _tiny("laguna", "laguna_model", "s-2.1"),
    "mellum": _tiny("mellum", "mellum_model", "12b-a2.5b"),
    "kimi_linear": _tiny("kimi_linear", "kimi_linear_model", "48b-a3b"),
    "phi4flash": _tiny("phi4flash", "phi4flash_model", "mini-flash"),
    "minicpm_sala": _tiny("minicpm_sala", "minicpm_sala_model", "9b"),
    "granite_hybrid": _tiny("granite_hybrid", "granite_hybrid_model",
                            "4.0-h-small"),
}


FAMILIES = {"gpt2": _gpt2, "olmoe": _olmoe, "qwen3_next": _qwen3_next,
            "nemotron_h": _nemotron_h}
#: the families whose expert layers hold a subset of the experts — their
#: steps run ``moe/layer.py _held_grouped_moe`` — by the family of
#: ``FAMILIES`` or the builder; tests/test_held_live_prefix.py holds the
#: others to the parent's text and these to having left it
HELD_FAMILIES = {"qwen3_next": _qwen3_next, "nemotron_h": _nemotron_h,
                 "joyai": _joyai}
#: the families that run ``joyai.latent_attention`` (with a query latent
#: and rotary: the form they had before models/kimi_linear.py's)
NEIGHBOUR_FAMILIES = {"joyai": _joyai, "xing": _xing}


def delta_rule_digest(interpret: bool) -> str:
    """sha256 of the lowered text of the gated delta rule's value and
    gradient with a decay a head ([2, 128] packed, two key heads of 128
    serving four value heads): ``interpret`` False its XLA form, True its
    Mosaic kernels in Pallas' interpreter."""
    jax.clear_caches()      # as digest() below: from no earlier trace
    from deepspeed_tpu.ops.linear_attention import gated_delta_rule
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    B, S, Hk, Hv, d = 2, 128, 2, 4, 128

    def loss(q, k, v, g, beta, seg):
        return jnp.sum(gated_delta_rule(
            q, k, v, g, beta, seg, chunk=64, interpret=interpret,
            l2norm_scales=(d ** -0.5, 1.0)))

    text = jax.jit(jax.value_and_grad(loss, argnums=range(5))).lower(
        shape(B, S, Hk, d), shape(B, S, Hk, d), shape(B, S, Hv, d),
        shape(B, S, Hv), shape(B, S, Hv),
        jax.ShapeDtypeStruct((B, S), jnp.int32)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def digest(family: str, grouped_kernels: bool = False) -> str:
    """sha256 of the lowered text of ``value_and_grad(loss)`` on a packed
    [2, 64] batch, the Pallas calls interpreted; ``grouped_kernels``: the
    ``ds_ggemm_*`` kernels too, where ``ragged_dot`` stands in for them
    off the chip."""
    import os
    from unittest import mock
    from jax.experimental import pallas as pl
    from deepspeed_tpu.moe import layer as moe_layer
    real = pl.pallas_call
    pl.pallas_call = functools.partial(real, interpret=True)
    # what the process traced before decides which inner jitted functions
    # of the step share one traced body, and so the text: start from none
    jax.clear_caches()
    # a metrics tap an earlier test of the process left installed would
    # put its host callbacks into the text
    tap, moe_layer._metrics_registry = moe_layer._metrics_registry, None
    with mock.patch.dict(os.environ):
        os.environ.pop("DS_GGEMM_INTERPRET", None)
        if grouped_kernels:
            os.environ["DS_GGEMM_INTERPRET"] = "1"
        try:
            model = {**FAMILIES, **HELD_FAMILIES, **NEIGHBOUR_FAMILIES,
                     **HEAD_FAMILIES}[family]()
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            batch = {"input_ids": jnp.zeros((2, 64), jnp.int32),
                     "segment_ids": jnp.zeros((2, 64), jnp.int32)}
            text = jax.jit(jax.value_and_grad(model.loss)).lower(
                shapes, batch).as_text()
        finally:
            pl.pallas_call = real
            moe_layer._metrics_registry = tap
    assert "while" in text      # the interpreted kernels' grid loops
    return hashlib.sha256(text.encode()).hexdigest()

