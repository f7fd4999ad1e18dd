"""The fused decode path through the continuous-batching scheduler, on the
helpers of tests/test_fused_decode.py: greedy parity fused against unfused
across int8 KV and weights, the other families, MoE grouped dispatch, the
prefix cache, speculative rollback and chunked prefill.  A file of its own
so that ``--dist loadfile`` gives the fused decode's tests to two workers."""
import os

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.ops.pallas.fused_decode import fused_decode_scope
from deepspeed_tpu.runtime.config import ServingConfig
from deepspeed_tpu.serving import ContinuousBatchingScheduler, SamplingParams

from tests.util import tiny_gpt2
from tests.test_fused_decode import (  # noqa: F401 (the fixtures come by name)
    _cb_outputs, _debug_invariant, _parity_fused_vs_unfused)


def test_cb_parity_gpt2_fused_ref():
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    _parity_fused_vs_unfused(m, eng.params)


def test_cb_parity_gpt2_fused_kernel_interpret():
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    _parity_fused_vs_unfused(m, eng.params, interpret=True, n=2)


def test_cb_parity_gpt2_int8_kv(monkeypatch):
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 120, (int(L),)).astype(np.int32)
               for L in rng.integers(4, 12, 3)]
    max_new = [5, 4, 6]

    def run(fused):
        os.environ["DS_FUSED_DECODE_INTERPRET"] = "1" if fused else "0"
        try:
            with fused_decode_scope(fused):
                cfg = ServingConfig(block_size=8, num_blocks=64,
                                    max_num_seqs=4,
                                    max_num_batched_tokens=256)
                sched = ContinuousBatchingScheduler(
                    m, eng.params, cfg, kv_cache_dtype="int8")
                reqs = [sched.submit(p,
                                     SamplingParams(max_new_tokens=mn))
                        for p, mn in zip(prompts, max_new)]
                sched.run_until_idle()
                return [np.asarray(r.output_ids) for r in reqs]
        finally:
            os.environ.pop("DS_FUSED_DECODE_INTERPRET", None)

    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(a, b)


def test_cb_parity_int8_weights_qgemm_interpret(monkeypatch):
    """int8 WEIGHTS composition: fused (megakernel in-kernel dequant,
    interpret) vs unfused (interpret qgemm route) — token-identical."""
    monkeypatch.setenv("DS_QGEMM_INTERPRET", "1")
    m = tiny_gpt2()
    engq = deepspeed_tpu.init_inference(
        model=m, config={"dtype": "float32", "quant": {"enabled": True}})
    _parity_fused_vs_unfused(m, engq.params, interpret=True, n=2)


def test_cb_parity_llama_and_bloom_fused_ref():
    from deepspeed_tpu.models.bloom import bloom_model
    from deepspeed_tpu.models.llama import llama_model
    for m in (llama_model("tiny", vocab_size=128, max_seq_len=64),
              bloom_model("custom", vocab_size=128, max_seq_len=64,
                          num_layers=2, num_heads=4, d_model=32)):
        eng = deepspeed_tpu.init_inference(model=m,
                                           config={"dtype": "float32"})
        _parity_fused_vs_unfused(m, eng.params, n=3)


def test_cb_parity_neox_fused_ref():
    from deepspeed_tpu.models.neox import neox_model
    m = neox_model("custom", vocab_size=128, max_seq_len=64,
                   num_layers=2, num_heads=4, d_model=32)
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    _parity_fused_vs_unfused(m, eng.params, n=3)


def test_cb_parity_mixtral_moe_grouped(monkeypatch):
    """MoE composition: the megakernel covers the attention half
    (mlp="none") while the routed experts keep the grouped-GEMM slot
    kernels (interpret) — token-identical to the unfused composition."""
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    monkeypatch.setenv("DS_MOE_DISPATCH", "grouped")
    from deepspeed_tpu.models.mixtral import mixtral_model
    m = mixtral_model("1b-moe", vocab_size=128, max_seq_len=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      d_model=32, d_ff=64, num_experts=4)
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    _parity_fused_vs_unfused(m, eng.params, interpret=True, n=2)


def test_cb_parity_fused_prefix_cache_cow():
    """Prefix-cache composition: shared prefixes + the COW fork of the
    last matched block, fused vs unfused — token-identical and the
    fused run actually hits the cache."""
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    rng = np.random.default_rng(13)
    shared = rng.integers(1, 120, (16,)).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(1, 120, (int(t),)).astype(
                                   np.int32)]) for t in (3, 5, 0, 2)]
    max_new = [5, 4, 3, 6]
    cfgk = dict(prefix_cache={"enabled": True, "min_prefix_blocks": 1})

    def run(fused):
        with fused_decode_scope(fused):
            outs, sched = _cb_outputs(m, eng.params, prompts, max_new,
                                      cfgk)
            return outs, sched.metrics.counters["prefix_cache_hit"]

    base, _hits0 = run(False)
    fused, hits = run(True)
    for a, b in zip(base, fused):
        np.testing.assert_array_equal(a, b)
    assert hits > 0


def test_cb_parity_fused_spec_rollback():
    """Speculative decoding composition: ngram drafts verified through
    the batched-window program with the fused path on — greedy output
    token-identical to plain unfused cb, with real rollbacks."""
    from deepspeed_tpu.serving.spec import NgramProposer
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    rng = np.random.default_rng(17)
    motif = rng.integers(1, 120, (6,)).astype(np.int32)
    prompts = [np.concatenate([motif, motif,
                               rng.integers(1, 120, (3,)).astype(np.int32),
                               motif])
               for _ in range(3)]
    max_new = [8, 6, 7]
    cfgk = dict(spec={"mode": "ngram", "max_draft_tokens": 4})
    with fused_decode_scope(False):
        base, _ = _cb_outputs(m, eng.params, prompts, max_new)
    with fused_decode_scope(True):
        spec_out, sched = _cb_outputs(
            m, eng.params, prompts, max_new, cfgk,
            proposer=NgramProposer(ngram_max=3, ngram_min=1))
    for a, b in zip(base, spec_out):
        np.testing.assert_array_equal(a, b)
    assert sched.metrics.counters["spec_verify_steps"] > 0
    assert sched.metrics.counters["window_steps"] > 0


def test_cb_parity_fused_chunked_prefill():
    """Chunked-prefill composition: a long prompt serviced in bounded
    chunks THROUGH the batched-window program (decode rows riding the
    same passes), fused vs unfused — token-identical, bounded, and the
    chunks demonstrably ride the window surface."""
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, 120, (40,)).astype(np.int32),
               rng.integers(1, 120, (5,)).astype(np.int32)]
    max_new = [4, 8]
    cfgk = dict(chunked_prefill={"enabled": True, "chunk_tokens": 16},
                max_num_batched_tokens=64)

    def run(fused):
        with fused_decode_scope(fused):
            return _cb_outputs(m, eng.params, prompts, max_new, cfgk)

    base, sched0 = run(False)
    fused, sched = run(True)
    for a, b in zip(base, fused):
        np.testing.assert_array_equal(a, b)
    assert sched.metrics.counters["window_chunk_tokens"] >= 24
    assert sched.metrics.counters["window_steps"] > 0
    assert sched.metrics.counters["prefill_tokens"] == 45
