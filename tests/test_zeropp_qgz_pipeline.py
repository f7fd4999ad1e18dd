"""ZeRO++'s quantized gradient reduce (qgZ) under the pipeline schedules, on
tests/test_zeropp.py's helpers: GPipe and the chunked schedule train to
parity, and 1F1B's restriction bears load.  A file of its own so that
``--dist loadfile`` gives ZeRO++'s tests to three workers."""
import numpy as np

import deepspeed_tpu

from tests.util import tiny_gpt2
from tests.test_zeropp import (  # noqa: F401 (the fixtures come by name)
    _pipe_cfg, _pipe_train)


def test_qgz_under_pipeline_gpipe(devices8):
    """round-3 VERDICT item 4: the quantized gradient exchange composes
    with the scanned-GPipe pipeline (the tier's shard_map keeps the pipe
    axis auto); parity with the dense pipeline run + int8 on the wire."""
    from deepspeed_tpu.runtime.pipe.pipeline import pipeline_model
    gas = 4
    ref, *_ = deepspeed_tpu.initialize(
        model=pipeline_model(tiny_gpt2(), num_stages=2),
        config=_pipe_cfg(gas, qgz=False))
    qgz, *_ = deepspeed_tpu.initialize(
        model=pipeline_model(tiny_gpt2(), num_stages=2),
        config=_pipe_cfg(gas, qgz=True))
    assert qgz._get_qgz_plan() is not None, "qgZ did not engage under PP"
    l_ref = _pipe_train(ref, gas, steps=3, seed=81)
    l_qgz = _pipe_train(qgz, gas, steps=3, seed=81)
    np.testing.assert_allclose(l_qgz, l_ref, rtol=0.05, atol=0.05)
    batch = qgz._shard_batch(
        {"input_ids": np.zeros((gas, 4, 16), np.int32)}, stacked=True)
    fn = qgz._get_compiled("train_step")
    with qgz._train_scope():
        hlo = fn.lower(qgz.state, batch,
                       qgz._next_rng()).compile().as_text()
    comm = [l for l in hlo.splitlines()
            if "all-to-all" in l or "all-gather" in l]
    assert any("s8[" in l for l in comm), comm[:5]


def test_qgz_under_pipeline_chunked(devices8):
    """Chunked GPipe (num_pipe_buffers) + qgZ: the tier scans pipeline
    chunks and still tracks the dense run."""
    from deepspeed_tpu.runtime.pipe.pipeline import pipeline_model
    gas = 4
    ref, *_ = deepspeed_tpu.initialize(
        model=pipeline_model(tiny_gpt2(), num_stages=2),
        config=_pipe_cfg(gas, qgz=False, num_pipe_buffers=2))
    qgz, *_ = deepspeed_tpu.initialize(
        model=pipeline_model(tiny_gpt2(), num_stages=2),
        config=_pipe_cfg(gas, qgz=True, num_pipe_buffers=2))
    assert qgz._get_qgz_plan() is not None
    l_ref = _pipe_train(ref, gas, steps=3, seed=83)
    l_qgz = _pipe_train(qgz, gas, steps=3, seed=83)
    np.testing.assert_allclose(l_qgz, l_ref, rtol=0.05, atol=0.05)


def test_qgz_1f1b_restriction_is_loadbearing(devices8):
    """1F1B's manual interleave bypasses the exchange tier: the plan must
    refuse (warn-and-degrade) and training must still run dense — the
    documented restriction, asserted (round-3 VERDICT item 4)."""
    from deepspeed_tpu.runtime.pipe.pipeline import pipeline_model
    gas = 4
    engine, *_ = deepspeed_tpu.initialize(
        model=pipeline_model(tiny_gpt2(), num_stages=2),
        config=_pipe_cfg(gas, qgz=True, schedule="1f1b"))
    assert engine._get_qgz_plan() is None
    assert np.isfinite(_pipe_train(engine, gas, steps=1, seed=85)[0])
