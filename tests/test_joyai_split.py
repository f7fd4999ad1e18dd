"""JoyAI's toy step after its blocks were rebuilt on the branch functions
that models/xing.py shares (``latent_attention``, ``dense_mlp``,
``expert_branch``: the residual add split out of them, PR 56): the same
kernels in the compiled step, instruction for instruction, and the same
first-step loss to the last bit as the tree before the split gave
(recorded there, on the CPU, float32, tests/test_joyai.py's toy model and
seeded weights)."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.telemetry import tracing
from tests.test_joyai import (  # noqa: F401 (the fixtures come by name)
    B, GAS, _isolation, one_device, packed_batch, real_kernels, seeded_toy,
    toy_model)
from tests.util import base_config

#: float32 bits of the first step's loss, and the step's kernels by name
#: with the instructions that carry each, at the parent of PR 56 — the
#: loss one place above that (…736, 14.361671447753906) since PR 57: ``k``
#: leaves a product of ``[c_kv | k_r]``, and this CPU's float32 dot sums
#: its 40 terms, eight of them exact zeros, in another order than 32;
#: two places above that again (…738, 14.361673355102539) since PR 69:
#: both heads' losses come from float32 logits a chunk of tokens at a time
#: (``models/model.py head_token_loss``: a logsumexp less the target's
#: logit, summed and then divided, where optax's form took a mean of
#: per-token differences — tests/test_head_loss.py holds the two together).
#: The flash kernels' instructions are PR 71's (255 / 198 / 117 before it):
#: the toy batch is packed, and a packed call's tile loops take one more
#: bound, from the documents' table in SMEM — the loss's bits stood
LOSS_BITS = 1097189738      # 14.361673355102539
KERNELS = {"ds_flash_fwd": 257, "ds_flash_bwd_dkv": 183,
           "ds_flash_bwd_dq": 120, "ds_ggemm_fwd": 221, "ds_ggemm_dx": 108,
           "ds_ggemm_dw": 160, "ds_rowsum": 312}


def test_the_split_left_joyais_toy_step_alone(interpret_pallas, real_kernels):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=toy_model(attention_impl="flash"), config=base_config(
                train_micro_batch_size_per_gpu=B,
                gradient_accumulation_steps=GAS, seed=3,
                zero_optimization={"stage": 2}), mesh=one_device())
        start = jax.tree.map(jnp.copy, seeded_toy()[1])
        engine.state["params"] = jax.tree.map(
            lambda new, old: jax.device_put(new.astype(old.dtype),
                                            old.sharding),
            start, engine.state["params"])
        loss = np.float32(engine.train_batch(batch=packed_batch()))
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    # in Pallas' interpreter a kernel's instructions carry its name in
    # their scope path
    kernels = dict(collections.Counter(
        name for row in table.values()
        for name in set(re.findall(r"ds_[a-z_]+", row["scope"] or ""))))
    assert int(loss.view(np.uint32)) == LOSS_BITS, float(loss)
    assert kernels == KERNELS
