"""What the gated delta rule's decay a key channel and latent attention's
form without a query latent left alone: the rule's own call with a decay a
head — its XLA form and its kernels interpreted — and JoyAI's and Xing4.0's
toy steps lower to the text they had at PR 60's parent commit (digests
taken there: tests/flash_step_texts.py says how; the two families' taken
again at PR 69, whose two head passes form no whole logits).  Qwen3-Next's toy step is
held by tests/test_flash_head_widths.py and tests/test_held_live_prefix.py,
JoyAI's first loss to the last bit by tests/test_joyai_split.py."""
import json
import os

import pytest

from tests import flash_step_texts

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "kda_neighbour_step_digests.json")) as f:
    PARENTS = json.load(f)


@pytest.mark.parametrize("family", sorted(flash_step_texts.NEIGHBOUR_FAMILIES))
def test_a_latent_attention_family_lowers_to_the_parents_text(family):
    assert flash_step_texts.digest(family) == PARENTS[family]


@pytest.mark.parametrize("lowering", ["xla", "kernels"])
def test_the_decay_a_head_lowers_to_the_parents_text(lowering):
    assert flash_step_texts.delta_rule_digest(lowering == "kernels") \
        == PARENTS[f"gated_delta_rule[head, {lowering}]"]


def test_every_digest_has_its_case():
    assert set(PARENTS) == set(flash_step_texts.NEIGHBOUR_FAMILIES) | {
        "gated_delta_rule[head, xla]", "gated_delta_rule[head, kernels]"}
