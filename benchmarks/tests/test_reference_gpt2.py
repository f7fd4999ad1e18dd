"""The plain reference against models/gpt2.py at a tiny size, float32, on
the CPU: same parameters, same batch, same loss — unpacked, packed, and
accumulated over micro-batches."""
import jax
import numpy as np
import pytest

from deepspeed_tpu.models.gpt2 import gpt2_model
from references import gpt2 as reference


@pytest.mark.parametrize("packed", [False, True])
def test_reference_matches_the_model(packed):
    model = gpt2_model("custom", num_layers=3, d_model=64, num_heads=4,
                       vocab_size=257, max_seq_len=96, dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    gas, batch, seq = 2, 3, 80            # seq < max_seq_len: table sliced
    ids = rng.integers(0, 257, size=(gas, batch, seq), dtype=np.int32)
    data = {"input_ids": ids}
    if packed:
        cuts = np.sort(rng.integers(1, seq, size=(gas, batch, 3)), axis=-1)
        data["segment_ids"] = (np.arange(seq)[None, None, :, None]
                               >= cuts[:, :, None, :]).sum(-1).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.mean([float(model.loss(
            params, {k: v[g] for k, v in data.items()}))
            for g in range(gas)])
    got = reference.step_loss(
        params, data, {"num_heads": 4, "layer_norm_eps": 1e-5}, chunk=2)
    # float32 both sides; only the order of summation differs
    assert abs(got - want) < 2e-5, (got, want)


def test_tolerance_would_catch_a_lower_precision():
    """bf16 keeps 8 significant bits; a type with 4 (fp8 e4m3) rounds 16x
    coarser.  The tolerance sits between what bf16 showed on the chip at
    full width (<= 2.2e-4, PERF.md PR 23) and 16x that."""
    assert 2.2e-4 < reference.LOSS_ATOL < 16 * 2.2e-4
