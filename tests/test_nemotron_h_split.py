"""Nemotron-H's toy loss and gradients after its mixers were rebuilt on the
branch functions models/granite_hybrid.py shares (``ssm_branch``,
``attention_branch``: the residual add split out of them, PR 66): the
first micro-batch's loss and every leaf's gradient to the last bit as the
tree before the split gave them (recorded there, on the CPU, float32,
tests/test_nemotron_h.py's toy model, seeded weights, packed batch and
fixture: the grouped GEMM kernels in Pallas' interpreter)."""
import hashlib

import jax
import numpy as np

from tests.test_nemotron_h import (  # noqa: F401 (the fixture comes by name)
    micro, packed_batch, real_kernels, seeded_params, toy_model)

#: float32 bits of the loss, and the SHA-256 of every gradient leaf's bytes
#: in the tree's order, at the parent of PR 66 — the loss; the gradients'
#: digest was taken again at PR 69 (there bbd042dd...969e7c): the head's two
#: gradients are found in its forward pass from ``softmax - hit`` scaled
#: by the count afterwards (``models/model.py head_nll_sum``), where
#: autodiff scaled first, so the last bits of every leaf behind it moved;
#: tests/test_head_loss.py holds the new head to the old one's loss, ``dh``
#: and ``dw`` at 1e-5, tests/test_nemotron_h.py these gradients to the
#: plain reference's
LOSS_BITS = 1093894671      # 11.219253
GRADS_SHA256 = (
    "2ecf5971b102925177bdf1ec7c08b452271b6aa6fdbdf13f903bee9f606630fe")


def loss_and_gradient_bits():
    model = toy_model()
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(
        seeded_params(model), micro(packed_batch()))
    digest = hashlib.sha256()
    for leaf in jax.tree.leaves(grads):
        digest.update(np.asarray(leaf, np.float32).tobytes())
    return int(np.float32(loss).view(np.uint32)), digest.hexdigest()


def test_the_split_left_nemotron_hs_toy_loss_and_gradients_alone():
    assert loss_and_gradient_bits() == (LOSS_BITS, GRADS_SHA256)

