"""Serving benchmark: steady-state decode tokens/s through the
InferenceEngine (KV cache + Pallas decode kernel), plus the
continuous-batching mode (SERVE_MODE=cb) comparing the
`deepspeed_tpu/serving/` scheduler against the static-batch baseline on
a mixed-length workload.

On-chip queue item (PERF.md): MoE int8-KV serving rate, plus rates for
the new serving families (NeoX/GPT-J/BLOOM/GPT-Neo).

    python scripts/serve_bench.py                          # gpt2 125m
    SERVE_MODEL=mixtral:1b-moe SERVE_KV=int8 python scripts/serve_bench.py
    SERVE_MODEL=bloom:560m SERVE_B=8 python scripts/serve_bench.py
    SERVE_MODE=cb SERVE_REQS=16 python scripts/serve_bench.py
    SERVE_MODE=spec SERVE_REQS=16 python scripts/serve_bench.py
    SERVE_MODE=prefix SERVE_REQS=24 python scripts/serve_bench.py
    SERVE_MODE=tier SERVE_REQS=16 python scripts/serve_bench.py
    SERVE_MODE=lora SERVE_TENANTS=4 python scripts/serve_bench.py
    SERVE_MODE=moe python scripts/serve_bench.py            # mixtral A/B
    SERVE_MODE=moe SERVE_INT8_WEIGHTS=1 python scripts/serve_bench.py
    SERVE_MODE=slo SERVE_LONG_LEN=8192 python scripts/serve_bench.py
    SERVE_MODE=fleet SERVE_REPLICAS=2 python scripts/serve_bench.py
    SERVE_MODE=fused python scripts/serve_bench.py   # megakernel A/B
    SERVE_MODE=cb python scripts/serve_bench.py --json out.json

``--json out.json`` (ISSUE 7 satellite) additionally writes the result
record to a file — the machine-readable form ``scripts/
bench_compare.py`` diffs across rounds, so the bench trajectory stops
being prose-only in PERF.md.

Static mode prints one JSON line: prefill ms + steady decode tokens/s.
CB mode prints one JSON line: continuous-batching vs static-batch tok/s
on the same mixed-length workload + p50/p99 TTFT.
Spec mode (ISSUE 5) runs the ngram-proposer speculative path vs plain cb
on a mixed-length repetitive-suffix workload and reports tokens per
weight pass + acceptance rate (the ISSUE 5 acceptance columns).
Prefix mode (ISSUE 6) runs the cb scheduler on a SHARED-PREFIX workload
(N requests over M shared system prompts + distinct tails) with the
prefix cache on vs off and reports TTFT p50/p99, cache hit rate,
prefill tokens computed, and serving_goodput — the ISSUE 6 acceptance
columns (identical outputs asserted between the two runs).
MoE mode (ISSUE 8) runs a Mixtral cb workload with grouped (megablocks
ragged-GEMM) vs einsum (GShard capacity) expert dispatch — token-
identical greedy outputs asserted — and, with SERVE_INT8_WEIGHTS=1,
reports the ``weights_floor_moe`` accounting (dense int8 bytes + top-k-
distinct-expert bytes per decode step — the floor the grouped int8
path streams at; the einsum path streams ALL E experts).
SLO mode (ISSUE 9) runs the ADVERSARIAL heavy-prefill workload: a
steady pool of short chat streams decoding while a few long prompts
arrive mid-flight (step-scheduled, identical in both runs), A/B'd with
chunked prefill ON vs OFF — token-identical greedy outputs asserted —
reporting p50/p99 TPOT and TTFT per SLO class.  The acceptance shape:
with chunking OFF the chat class's p99 TPOT spikes at each long-prompt
arrival (the whole prefill runs in one scheduler iteration); with
chunking ON it stays bounded near p50.
Tier mode (ISSUE 16) runs a shared-prefix workload under a deliberately
small hot cache (LRU pressure demotes released prefixes HBM→host→NVMe)
with tiered KV ON vs OFF — token-identical greedy outputs asserted —
and reports prefill tokens saved by cold-tier swap-ins vs the
evict-and-re-prefill baseline, per-tier hit counts, and the
swap/achieved_vs_floor bandwidth rows when DS_NVME_GBPS is declared.
Fleet mode (ISSUE 11) routes a shared-prefix workload across N replica
schedulers (each with its own prefix cache) through the fleet Router,
A/B'ing the prefix-aware scored policy vs round-robin — token-identical
outputs asserted — and reports the aggregate prefix-cache hit rate per
policy (the acceptance column: scored routing concentrates same-prefix
traffic on the replica that already holds it, round-robin scatters it).
Off-TPU this still runs (tiny default shapes) as a plumbing smoke.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

# value-fetch sync
from scripts.bench_util import fetch


def emit(result: dict, json_path=None) -> dict:
    """Print the one-line JSON record (the existing convention),
    persist it with --json for bench_compare.py, and — when
    DS_BENCH_LEDGER is armed — append it (BenchRecord meta envelope
    attached) to the BENCH/ ledger history (ISSUE 13).  Every record
    gains the memory observatory's ``mem_peak_*`` watermarks
    (ISSUE 14) and the communication observatory's ``comm_*``
    per-axis wire bytes / achieved GB/s (ISSUE 19) INSIDE ``detail``
    — that is the half of a record ``bench_compare`` lifts into
    comparable metrics, so the history gates memory and interconnect
    regressions like latency ones."""
    from scripts.bench_util import comm_fields, mem_peak_fields
    detail = result.setdefault("detail", {})
    if isinstance(detail, dict):
        for k, v in mem_peak_fields().items():
            detail.setdefault(k, v)
        for k, v in comm_fields().items():
            detail.setdefault(k, v)
    print(json.dumps(result))
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=2)
        print(f"# wrote {json_path}", file=sys.stderr)
    from scripts.bench_util import emit_ledger
    emit_ledger(result)
    return result


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="serve_bench",
        description="serving benchmark (workload shape via SERVE_* env "
                    "vars — see module docstring)")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="also write the result record to PATH "
                        "(bench_compare.py input)")
    args = p.parse_args(argv)
    json_path = args.json
    on_tpu = "tpu" in str(jax.devices()[0]).lower()
    if os.environ.get("SERVE_MODE") == "moe":
        # the dispatch A/B needs a routed-expert model
        default_model = "mixtral:1b-moe" if on_tpu else "mixtral:tiny"
    else:
        default_model = "gpt2:125m" if on_tpu else "gpt2:custom"
    spec = os.environ.get("SERVE_MODEL", default_model)
    B = int(os.environ.get("SERVE_B", 4))
    prompt_len = int(os.environ.get("SERVE_PROMPT", 128 if on_tpu else 8))
    new_tokens = int(os.environ.get("SERVE_TOKENS", 256 if on_tpu else 8))
    kv_dtype = os.environ.get("SERVE_KV") or None
    quant = bool(int(os.environ.get("SERVE_INT8_WEIGHTS", "0")))
    # int8-qgemm mode (default on): SERVE_QGEMM=0 falls back to the
    # layer-granularity maybe_stream dequant + scan-threshold defense —
    # the A/B pair for the fused-dequant kernel rows in PERF.md
    if "SERVE_QGEMM" in os.environ:
        os.environ["DS_QGEMM"] = os.environ["SERVE_QGEMM"]

    from deepspeed_tpu import models as M

    def _opt_model(size, **kw):
        # OPT serves through the gpt2-family scaffold (pre-LN + ReLU —
        # what opt_from_hf converts onto); this is the native-arch
        # equivalent for rate measurement
        return M.gpt2_model(size, activation="relu", **kw)

    def _internlm_model(size, **kw):
        # InternLM = llama block + biased q/k/v/o (llama_from_hf alias);
        # "1b" picks InternLM-1.8B-like dims (no in-tree llama preset
        # at this scale)
        if size in ("1b", ""):
            kw = dict(num_layers=16, num_heads=16, num_kv_heads=16,
                      d_model=2048, d_mlp=5504, vocab_size=50000, **kw)
            size = "custom"
        return M.llama_model(size, attn_bias=True, **kw)

    arch, _, size = spec.partition(":")
    registry = {"gpt2": M.gpt2_model, "llama": M.llama_model,
                "mixtral": M.mixtral_model, "neox": M.neox_model,
                "bloom": M.bloom_model, "gptneo": M.gptneo_model,
                "opt": _opt_model, "megatron": M.gpt2_model,
                "internlm": _internlm_model}
    if on_tpu:
        kwargs = {}
    elif arch in ("llama", "mixtral", "internlm"):
        # these archs have their own tiny presets with consistent
        # kv-heads/ffn dims — the generic tiny kwargs would not apply
        size = size or "tiny"
        kwargs = {}
    elif os.environ.get("SERVE_MODE") in ("cb", "spec", "prefix", "moe",
                                          "slo", "fleet", "fused",
                                          "tier", "lora"):
        # cb vs static is a scheduling comparison: a 2-layer d=32 toy is
        # ALL dispatch overhead and measures nothing — use the smallest
        # shape where device compute is non-trivial
        kwargs = dict(vocab_size=1024, num_layers=4, num_heads=4,
                      d_model=128)
    else:
        kwargs = dict(vocab_size=256, num_layers=2, num_heads=4,
                      d_model=32)
    # cb/spec modes size their own workloads (spec's motif-tiled prompts
    # run a little longer than cb's heavy tail off-TPU)
    _mode = os.environ.get("SERVE_MODE")
    if _mode not in ("cb", "spec", "prefix", "moe", "slo", "fleet",
                     "fused", "tier", "lora"):
        cb_ctx = 0
    elif _mode == "slo":
        # headroom for the adversarial long prompts (heavy-prefill
        # overload is the whole point of this mode)
        cb_ctx = int(os.environ.get(
            "SERVE_LONG_LEN", 8192 if on_tpu else 640)) + 256
    elif on_tpu:
        cb_ctx = 768 + 384
    elif _mode in ("prefix", "fleet"):
        # headroom for the shared system prompts — the long-shared-head
        # short-tail regime is the whole point of these modes
        cb_ctx = int(os.environ.get("SERVE_SYS_LEN", 512)) + 128
    elif _mode == "tier":
        # same shared-head regime, but the CPU smoke keeps the heads
        # short: the point is demote/swap-in plumbing, not prefill mass
        cb_ctx = int(os.environ.get("SERVE_SYS_LEN",
                                    512 if on_tpu else 64)) + 128
    else:
        cb_ctx = 96 if _mode in ("cb", "moe") else 128
    model = registry[arch](size or "custom", dtype="bfloat16" if on_tpu
                           else "float32",
                           max_seq_len=max(2048 if on_tpu else 64,
                                           prompt_len + new_tokens, cb_ctx),
                           **kwargs)

    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    cfg = DeepSpeedInferenceConfig(
        dtype="bfloat16" if on_tpu else "float32",
        quant={"enabled": quant},
        kv_cache_dtype=kv_dtype)
    params = None
    n_params = model.meta.get("n_params", 0)
    if quant and n_params * 2 > 8e9 and model.numpy_init_fn is not None:
        # int8 serving of models beyond HBM at full precision (the MoQ
        # big-model path): init on HOST, quantize leaf-by-leaf on device
        # — device-side init would materialize the full bf16 tree first
        print(f"# host-init {n_params/1e9:.1f}B params for int8 serving",
              file=sys.stderr)
        params = model.numpy_init_fn(seed=0)
    eng = InferenceEngine(model, cfg, model_parameters=params)

    if os.environ.get("SERVE_MODE") == "cb":
        return bench_continuous_batching(model, eng, spec, kv_dtype, on_tpu,
                                         json_path)
    if os.environ.get("SERVE_MODE") == "spec":
        return bench_spec_decoding(model, eng, spec, kv_dtype, on_tpu,
                                   json_path)
    if os.environ.get("SERVE_MODE") == "prefix":
        return bench_prefix_cache(model, eng, spec, kv_dtype, on_tpu,
                                  json_path)
    if os.environ.get("SERVE_MODE") == "tier":
        return bench_kv_tiering(model, eng, spec, kv_dtype, on_tpu,
                                json_path)
    if os.environ.get("SERVE_MODE") == "lora":
        return bench_lora_multitenant(model, eng, spec, kv_dtype, on_tpu,
                                      json_path)
    if os.environ.get("SERVE_MODE") == "moe":
        return bench_moe_dispatch(model, eng, spec, kv_dtype, quant,
                                  on_tpu, json_path)
    if os.environ.get("SERVE_MODE") == "slo":
        return bench_slo_chunked(model, eng, spec, kv_dtype, on_tpu,
                                 json_path)
    if os.environ.get("SERVE_MODE") == "fleet":
        return bench_fleet_routing(model, eng, spec, kv_dtype, on_tpu,
                                   json_path)
    if os.environ.get("SERVE_MODE") == "fused":
        return bench_fused_ab(model, eng, spec, kv_dtype, on_tpu,
                              json_path)

    rng = np.random.default_rng(0)
    prompts = rng.integers(1, model.config.vocab_size,
                           (B, prompt_len)).astype(np.int32)
    # decode rate = SLOPE between two generate lengths (min over repeats):
    # a one-shot (full - prefill) difference carries the fixed round-trip
    # jitter twice and swings run to run;
    # the slope between two lengths measured min-of-3 cancels prefill and
    # every fixed cost
    small = max(1, new_tokens // 4)

    def timed(n, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            fetch(eng.generate(prompts, max_new_tokens=n,
                                    do_sample=False))
            best = min(best, time.time() - t0)
        return best

    # warmup/compile all program shapes
    fetch(eng.generate(prompts, max_new_tokens=1, do_sample=False))
    fetch(eng.generate(prompts, max_new_tokens=small, do_sample=False))
    fetch(eng.generate(prompts, max_new_tokens=new_tokens,
                            do_sample=False))
    t_prefill = timed(1)
    t_small = timed(small)
    t_full = timed(new_tokens)
    decode_s = t_full - t_small
    toks = B * (new_tokens - small)
    if decode_s <= 0:
        # timing noise swamped the marginal decode time (tiny smoke
        # shapes) — emit null rather than a garbage rate
        rate = None
    else:
        rate = round(toks / decode_s, 1)
    from deepspeed_tpu.models.serving import qgemm_enabled
    emit({
        "metric": f"{spec}_serve"
                  + ("_int8kv" if kv_dtype == "int8" else "")
                  + (("_int8w_qgemm" if qgemm_enabled() else "_int8w_dq")
                     if quant else ""),
        "value": rate,
        "unit": "decode_tokens_per_sec",
        "detail": {"batch": B, "prompt_len": prompt_len,
                   "new_tokens": new_tokens,
                   "prefill_ms": round(t_prefill * 1e3, 2),
                   "total_s": round(t_full, 3)},
    }, json_path)


def bench_fused_ab(model, eng, spec, kv_dtype, on_tpu, json_path=None):
    """Fused-megakernel on/off A/B through the cb scheduler (ISSUE 12):
    the same mixed-length greedy workload twice — fused off (per-op
    composition) vs on (``ds_fused_layer`` per layer) — with
    token-identical outputs ASSERTED, so the A/B isolates launches and
    scaffolding.  Off-TPU the fused path runs the jnp reference
    composition (structural A/B only, no launch win — the CPU-crossover
    caveat in docs/tutorials/serving.md); the on-chip rows are queued in
    PERF.md.  ``--json`` emits both rows for bench_compare gating."""
    import time as _time
    from deepspeed_tpu.ops.pallas.fused_decode import fused_decode_scope
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)

    n_reqs = int(os.environ.get("SERVE_REQS", 16 if on_tpu else 8))
    max_seqs = int(os.environ.get("SERVE_B", 8 if on_tpu else 4))
    p_lo, p_hi = ((32, 512) if on_tpu else (4, 24))
    n_lo, n_hi = ((8, 128) if on_tpu else (2, 12))
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    workload = [
        (rng.integers(1, V, (int(pl),)).astype(np.int32), int(nn))
        for pl, nn in zip(rng.integers(p_lo, p_hi, n_reqs),
                          rng.integers(n_lo, n_hi, n_reqs))]
    useful = sum(nn for _, nn in workload)
    max_len = max(p.size + nn for p, nn in workload)
    bs = 16 if on_tpu else 4
    need = -(-max_len // bs) + 1
    cfg = ServingConfig(block_size=bs, max_num_seqs=max_seqs,
                        num_blocks=1 + need * max_seqs,
                        max_num_batched_tokens=1 << 30)

    def run(fused):
        with fused_decode_scope(fused):
            sched = ContinuousBatchingScheduler(
                model, eng.params, cfg, kv_cache_dtype=kv_dtype)

            def once():
                t0 = _time.time()
                reqs = [sched.submit(p, SamplingParams(max_new_tokens=nn))
                        for p, nn in workload]
                sched.run_until_idle()
                return (_time.time() - t0,
                        [np.asarray(r.output_ids) for r in reqs])

            once()                          # compile warm
            best, outs = min((once() for _ in range(2)),
                             key=lambda r: r[0])
        return best, outs

    off_s, off_out = run(False)
    on_s, on_out = run(True)
    for a, b in zip(off_out, on_out):       # the A/B contract
        np.testing.assert_array_equal(a, b)
    return emit({
        "bench": "serve_fused_ab", "model": spec,
        "kv": kv_dtype or "native", "device": jax.devices()[0].device_kind,
        "requests": n_reqs, "useful_tokens": useful,
        "token_identical": True,
        "unfused": {"wall_s": round(off_s, 3),
                    "tok_s": round(useful / off_s, 1)},
        "fused": {"wall_s": round(on_s, 3),
                  "tok_s": round(useful / on_s, 1)},
        "fused_speedup": round(off_s / on_s, 3),
    }, json_path)


def bench_continuous_batching(model, eng, spec, kv_dtype, on_tpu,
                              json_path=None):
    """Mixed-length workload through the iteration-level scheduler vs the
    static-batch baseline (rectangular pad, batch drains as a unit).

    The static baseline processes the same requests in arrival order in
    batches of ``max_num_seqs``, padded to the batch max prompt and
    decoding the batch max new_tokens — what `generate` alone offers.
    Useful tokens (each request's own max_new_tokens) over wall time."""
    import time as _time
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)

    n_reqs = int(os.environ.get("SERVE_REQS", 32 if on_tpu else 16))
    max_seqs = int(os.environ.get("SERVE_B", 8 if on_tpu else 4))
    # heavy-tailed lengths — the regime continuous batching exists for
    # (a static batch pads every row to the batch max in BOTH dims)
    p_lo, p_hi = ((32, 768) if on_tpu else (4, 48))
    n_lo, n_hi = ((8, 384) if on_tpu else (2, 48))
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    workload = [
        (rng.integers(1, V, (int(pl),)).astype(np.int32), int(nn))
        for pl, nn in zip(rng.integers(p_lo, p_hi, n_reqs),
                          rng.integers(n_lo, n_hi, n_reqs))]
    useful = sum(nn for _, nn in workload)
    max_len = max(p.size + nn for p, nn in workload)
    bs = 16 if on_tpu else 4
    need = -(-(max_len) // bs) + 1
    cfg = ServingConfig(
        block_size=bs, max_num_seqs=max_seqs,
        num_blocks=1 + need * max_seqs,     # full batch fits: measures
        max_num_batched_tokens=1 << 30)     # scheduling, not preemption

    sched = ContinuousBatchingScheduler(
        model, eng.params, cfg, kv_cache_dtype=kv_dtype)

    def run_cb():
        # one scheduler across warmup+measurement: its jitted step fns
        # (and their compiles) persist, as in a long-lived server
        t0 = _time.time()
        reqs = [sched.submit(p, SamplingParams(max_new_tokens=nn))
                for p, nn in workload]
        sched.run_until_idle()
        dt = _time.time() - t0
        assert all(len(r.output_ids) == nn
                   for r, (_, nn) in zip(reqs, workload))
        ttfts = sorted(r.ttft_s for r in reqs)
        return dt, ttfts

    def run_static():
        t0 = _time.time()
        ttfts = []
        for i in range(0, n_reqs, max_seqs):
            batch = workload[i:i + max_seqs]
            plen = max(p.size for p, _ in batch)
            new = max(nn for _, nn in batch)
            toks = np.zeros((len(batch), plen), np.int32)
            for j, (p, _) in enumerate(batch):
                toks[j, :p.size] = p        # right-padded rectangle
            t_b = _time.time()
            fetch(eng.generate(toks, max_new_tokens=new,
                                    do_sample=False))
            # static batches emit every token before ANY request returns:
            # TTFT = the whole batch latency, for every request in it
            ttfts.extend([_time.time() - t_b] * len(batch))
        return _time.time() - t0, sorted(ttfts)

    # warm both paths' compiles out of the measurement; then min-of-3
    # (same convention as the static-mode slope measurement)
    run_cb()
    run_static()
    cb_s, cb_ttft = min((run_cb() for _ in range(3)),
                        key=lambda r: r[0])
    st_s, st_ttft = min((run_static() for _ in range(3)),
                        key=lambda r: r[0])
    pct = lambda xs, q: round(float(np.percentile(xs, q)) * 1e3, 2)
    emit({
        "metric": f"{spec}_serve_cb"
                  + ("_int8kv" if kv_dtype == "int8" else ""),
        "value": round(useful / cb_s, 1),
        "unit": "tokens_per_sec",
        "detail": {
            "requests": n_reqs, "useful_tokens": useful,
            "max_num_seqs": max_seqs, "block_size": bs,
            "cb_tok_s": round(useful / cb_s, 1),
            "static_tok_s": round(useful / st_s, 1),
            "speedup_vs_static": round(st_s / cb_s, 3),
            "cb_ttft_p50_ms": pct(cb_ttft, 50),
            "cb_ttft_p99_ms": pct(cb_ttft, 99),
            "static_ttft_p50_ms": pct(st_ttft, 50),
            "static_ttft_p99_ms": pct(st_ttft, 99),
            "decode_steps_total": int(
                sched.metrics.counters["decode_steps"]),
        },
    }, json_path)


def bench_spec_decoding(model, eng, spec, kv_dtype, on_tpu,
                        json_path=None):
    """Speculative (ngram-proposer) vs plain continuous batching on a
    mixed-length REPETITIVE-SUFFIX workload — prompts built by tiling a
    short motif, the regime prompt-lookup exists for (long prompts the
    output echoes; greedy decoding's own repetition loops).  Columns:
    tokens per weight pass (generated tokens over decode+verify passes —
    the quantity speculation raises above 1.0) and draft acceptance
    rate, plus the mean accepted length per verify pass (ISSUE 5
    acceptance: > 1.3 on this workload)."""
    import time as _time
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)

    n_reqs = int(os.environ.get("SERVE_REQS", 24 if on_tpu else 12))
    max_seqs = int(os.environ.get("SERVE_B", 8 if on_tpu else 4))
    max_draft = int(os.environ.get("SERVE_SPEC_K", 8))
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    # motif-tiled prompts with a small random head: the suffix n-gram
    # always has an earlier occurrence, mixed lengths keep the batch
    # ragged like the cb bench
    m_lo, m_hi = (4, 9)
    reps_lo, reps_hi = ((8, 24) if on_tpu else (3, 8))
    n_lo, n_hi = ((32, 256) if on_tpu else (12, 48))
    workload = []
    for i in range(n_reqs):
        motif = rng.integers(1, V, (int(rng.integers(m_lo, m_hi)),))
        head = rng.integers(1, V, (int(rng.integers(0, 4)),))
        prompt = np.concatenate(
            [head, np.tile(motif, int(rng.integers(reps_lo, reps_hi)))])
        workload.append((prompt.astype(np.int32),
                         int(rng.integers(n_lo, n_hi))))
    useful = sum(nn for _, nn in workload)
    max_len = max(p.size + nn for p, nn in workload)
    bs = 16 if on_tpu else 4
    need = -(-max_len // bs) + 2
    base = dict(block_size=bs, max_num_seqs=max_seqs,
                num_blocks=1 + need * max_seqs,
                max_num_batched_tokens=1 << 30)

    def run(spec_mode):
        cfg = ServingConfig(**base, spec=(
            {"mode": "ngram", "max_draft_tokens": max_draft}
            if spec_mode else {"mode": "off"}))
        sched = ContinuousBatchingScheduler(
            model, eng.params, cfg, kv_cache_dtype=kv_dtype)
        # warm compiles out of the measurement, then measure once (the
        # workload is long enough to swamp dispatch jitter off-TPU too)
        for _ in range(2):
            reqs = [sched.submit(p, SamplingParams(max_new_tokens=nn))
                    for p, nn in workload]
            t0 = _time.time()
            sched.run_until_idle()
            dt = _time.time() - t0
            assert all(len(r.output_ids) == nn
                       for r, (_, nn) in zip(reqs, workload))
        return dt, sched.metrics

    spec_s, spec_m = run(True)
    cb_s, cb_m = run(False)
    c = spec_m.counters
    # weight passes that generated tokens: plain decode scan iterations
    # plus one per spec verify window
    spec_passes = c["decode_steps"] + c["spec_verify_steps"]
    cb_passes = cb_m.counters["decode_steps"]
    h = spec_m.spec_accept_len
    emit({
        "metric": f"{spec}_serve_spec"
                  + ("_int8kv" if kv_dtype == "int8" else ""),
        "value": round(useful / spec_s, 1),
        "unit": "tokens_per_sec",
        "detail": {
            "requests": n_reqs, "useful_tokens": useful,
            "max_num_seqs": max_seqs, "max_draft_tokens": max_draft,
            "spec_tok_s": round(useful / spec_s, 1),
            "cb_tok_s": round(useful / cb_s, 1),
            "speedup_vs_cb": round(cb_s / spec_s, 3),
            "spec_tokens_per_weight_pass": round(
                c["generated_tokens"] / max(spec_passes, 1), 3),
            "cb_tokens_per_weight_pass": round(
                cb_m.counters["generated_tokens"] / max(cb_passes, 1), 3),
            "accept_rate": round(
                c["spec_accepted_tokens"] / max(c["spec_drafted_tokens"],
                                                1), 3),
            "mean_accept_len": round(h.sum / max(h.count, 1), 3),
            "drafted": int(c["spec_drafted_tokens"]),
            "accepted": int(c["spec_accepted_tokens"]),
            "rolled_back": int(c["spec_rolled_back_tokens"]),
            "verify_passes": int(c["spec_verify_steps"]),
        },
    }, json_path)


def bench_prefix_cache(model, eng, spec, kv_dtype, on_tpu,
                       json_path=None):
    """Shared-prefix workload (ISSUE 6): N requests drawn over M shared
    system prompts, each with a distinct random tail — the chat-fleet
    regime where most prefill is redundant.  Runs the cb scheduler with
    the prefix cache ON vs OFF (fresh scheduler each, identical
    workload), asserts token-identical outputs, and reports TTFT
    p50/p99, block-granular hit rate, prefill tokens computed (the >=2x
    acceptance column), and serving_goodput."""
    import time as _time
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)

    n_reqs = int(os.environ.get("SERVE_REQS", 32 if on_tpu else 12))
    max_seqs = int(os.environ.get("SERVE_B", 8 if on_tpu else 4))
    n_sys = int(os.environ.get("SERVE_SYS_PROMPTS", 4 if on_tpu else 2))
    sys_len = int(os.environ.get("SERVE_SYS_LEN", 512))
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    t_lo, t_hi = ((16, 96) if on_tpu else (4, 16))
    n_lo, n_hi = ((32, 128) if on_tpu else (6, 20))
    systems = [rng.integers(1, V, (sys_len,)).astype(np.int32)
               for _ in range(n_sys)]
    workload = []
    for i in range(n_reqs):
        tail = rng.integers(1, V, (int(rng.integers(t_lo, t_hi)),))
        prompt = np.concatenate([systems[i % n_sys], tail])
        workload.append((prompt.astype(np.int32),
                         int(rng.integers(n_lo, n_hi))))
    useful = sum(nn for _, nn in workload)
    max_len = max(p.size + nn for p, nn in workload)
    bs = 16 if on_tpu else 8
    need = -(-max_len // bs) + 1
    # pool sized so the batch fits AND released prefixes can be retained
    # (the steady-state regime the cache serves)
    base = dict(block_size=bs, max_num_seqs=max_seqs,
                num_blocks=1 + need * (max_seqs + n_sys + 1),
                max_num_batched_tokens=1 << 30)

    def run(enabled):
        cfg = ServingConfig(**base,
                            prefix_cache={"enabled": enabled})
        sched = ContinuousBatchingScheduler(
            model, eng.params, cfg, kv_cache_dtype=kv_dtype)
        outs = None
        # warm compiles out of the measurement, then measure (fresh
        # submission wave; the cache persists across waves, as in a
        # long-lived server)
        for _ in range(2):
            reqs = [sched.submit(p, SamplingParams(max_new_tokens=nn))
                    for p, nn in workload]
            t0 = _time.time()
            sched.run_until_idle()
            dt = _time.time() - t0
            assert all(len(r.output_ids) == nn
                       for r, (_, nn) in zip(reqs, workload))
            outs = [list(r.output_ids) for r in reqs]
        ttfts = sorted(r.ttft_s for r in reqs)
        return dt, ttfts, sched.metrics, outs

    on_s, on_ttft, on_m, on_out = run(True)
    off_s, off_ttft, off_m, off_out = run(False)
    assert on_out == off_out, \
        "prefix cache changed greedy output (parity violation)"
    pct = lambda xs, q: round(float(np.percentile(xs, q)) * 1e3, 2)
    c = on_m.counters
    lookups = c["prefix_cache_hit"] + c["prefix_cache_miss"]
    emit({
        "metric": f"{spec}_serve_prefix"
                  + ("_int8kv" if kv_dtype == "int8" else ""),
        "value": round(useful / on_s, 1),
        "unit": "tokens_per_sec",
        "detail": {
            "requests": n_reqs, "system_prompts": n_sys,
            "system_len": sys_len, "useful_tokens": useful,
            "max_num_seqs": max_seqs, "block_size": bs,
            "cache_on_tok_s": round(useful / on_s, 1),
            "cache_off_tok_s": round(useful / off_s, 1),
            "speedup_vs_off": round(off_s / on_s, 3),
            "prefill_tokens_on": int(c["prefill_tokens"]),
            "prefill_tokens_off": int(
                off_m.counters["prefill_tokens"]),
            "prefill_reduction": round(
                off_m.counters["prefill_tokens"]
                / max(c["prefill_tokens"], 1), 2),
            "hit_rate": round(c["prefix_cache_hit"] / max(lookups, 1), 3),
            "cow_forks": int(c["prefix_cache_cow_forks"]),
            "evictions": int(c["prefix_cache_evict"]),
            "ttft_on_p50_ms": pct(on_ttft, 50),
            "ttft_on_p99_ms": pct(on_ttft, 99),
            "ttft_off_p50_ms": pct(off_ttft, 50),
            "ttft_off_p99_ms": pct(off_ttft, 99),
            "goodput_on": on_m.gauges.get("goodput"),
            "goodput_off": off_m.gauges.get("goodput"),
        },
    }, json_path)


def bench_kv_tiering(model, eng, spec, kv_dtype, on_tpu,
                     json_path=None):
    """Tiered-KV on/off A/B (ISSUE 16): the shared-prefix workload runs
    twice under a deliberately SMALL hot cache (``max_cached_blocks``
    sized below the working set, so wave-1 prefixes are pushed off the
    LRU before wave 2 re-requests them).  With tiering ON the push is a
    demotion (HBM→host, spilling host→NVMe under ``host_blocks``
    pressure) and wave 2's cold hits pay an async swap-in; with tiering
    OFF the push is an eviction and wave 2 re-prefills.  Token-identical
    greedy outputs are ASSERTED across the two runs; the record carries
    prefill tokens saved, per-tier hit counts, demote/spill/swap-in
    counters, and — when ``DS_NVME_GBPS`` declares a floor — the
    ``swap/achieved_vs_floor`` bandwidth rows (``bench_compare.py``
    gates on the ``*_tok_s`` / ``prefill_*`` keys)."""
    import time as _time
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)
    from deepspeed_tpu.telemetry.iostat import peek_iostat

    n_reqs = int(os.environ.get("SERVE_REQS", 24 if on_tpu else 8))
    max_seqs = int(os.environ.get("SERVE_B", 8 if on_tpu else 4))
    n_sys = int(os.environ.get("SERVE_SYS_PROMPTS", 4 if on_tpu else 3))
    sys_len = int(os.environ.get("SERVE_SYS_LEN", 512 if on_tpu else 64))
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    t_lo, t_hi = ((16, 96) if on_tpu else (4, 12))
    n_lo, n_hi = ((32, 128) if on_tpu else (4, 10))
    systems = [rng.integers(1, V, (sys_len,)).astype(np.int32)
               for _ in range(n_sys)]
    workload = []
    for i in range(n_reqs):
        tail = rng.integers(1, V, (int(rng.integers(t_lo, t_hi)),))
        prompt = np.concatenate([systems[i % n_sys], tail])
        workload.append((prompt.astype(np.int32),
                         int(rng.integers(n_lo, n_hi))))
    useful = sum(nn for _, nn in workload)
    max_len = max(p.size + nn for p, nn in workload)
    bs = 16 if on_tpu else 8
    need = -(-max_len // bs) + 1
    sys_blocks = sys_len // bs
    # hot cache holds ONE system prompt's chain (plus change): the
    # others demote/evict between waves — the spill regime on purpose
    base = dict(block_size=bs, max_num_seqs=max_seqs,
                num_blocks=1 + need * (max_seqs + n_sys + 1),
                max_num_batched_tokens=1 << 30)

    def run(enabled):
        cfg = ServingConfig(
            **base,
            prefix_cache={"enabled": True,
                          "max_cached_blocks": sys_blocks + 1},
            kv_tiering={"enabled": enabled,
                        # host holds one more system's worth; the rest
                        # spills onward to NVMe
                        "host_blocks": sys_blocks,
                        "nvme_blocks": 0})
        sched = ContinuousBatchingScheduler(
            model, eng.params, cfg, kv_cache_dtype=kv_dtype)
        outs = None
        for _ in range(2):
            reqs = [sched.submit(p, SamplingParams(max_new_tokens=nn))
                    for p, nn in workload]
            t0 = _time.time()
            sched.run_until_idle()
            dt = _time.time() - t0
            assert all(len(r.output_ids) == nn
                       for r, (_, nn) in zip(reqs, workload))
            outs = [list(r.output_ids) for r in reqs]
        return dt, sched.metrics, outs

    on_s, on_m, on_out = run(True)
    off_s, off_m, off_out = run(False)
    assert on_out == off_out, \
        "tiered KV changed greedy output (parity violation)"
    c = on_m.counters
    swapped = int(c["kv_swap_in_blocks"])
    io = peek_iostat()
    io_rows = io.summary() if io is not None else {}
    emit({
        "metric": f"{spec}_serve_tier"
                  + ("_int8kv" if kv_dtype == "int8" else ""),
        "value": round(useful / on_s, 1),
        "unit": "tokens_per_sec",
        "detail": {
            "requests": n_reqs, "system_prompts": n_sys,
            "system_len": sys_len, "useful_tokens": useful,
            "max_num_seqs": max_seqs, "block_size": bs,
            "hot_cache_blocks": sys_blocks + 1,
            "host_tier_blocks": sys_blocks,
            "tier_on_tok_s": round(useful / on_s, 1),
            "tier_off_tok_s": round(useful / off_s, 1),
            "prefill_tokens_on": int(c["prefill_tokens"]),
            "prefill_tokens_off": int(
                off_m.counters["prefill_tokens"]),
            "prefill_tokens_saved": int(
                off_m.counters["prefill_tokens"]
                - c["prefill_tokens"]),
            "swap_in_blocks": swapped,
            "swap_in_tokens": swapped * bs,
            "tier_hits_host": int(c["kv_tier_hit_host"]),
            "tier_hits_nvme": int(c["kv_tier_hit_nvme"]),
            "demotions": int(c["kv_demotions"]),
            "spills": int(c["kv_spills"]),
            "swap_failures": int(c["kv_swap_failures"]),
            "tier_hit_rate": on_m.gauges.get("kv_tier_hit_rate"),
            "evictions_off": int(
                off_m.counters["prefix_cache_evict"]),
            "swap_io": io_rows,
            "swap_read_vs_floor": (io_rows.get("ops", {})
                                   .get("read", {}).get("vs_floor")),
            "swap_write_vs_floor": (io_rows.get("ops", {})
                                    .get("write", {}).get("vs_floor")),
        },
    }, json_path)


def bench_lora_multitenant(model, eng, spec, kv_dtype, on_tpu,
                           json_path=None):
    """Multi-tenant LoRA A/B (ISSUE 20): N tenants' adapters serve from
    the paged AdapterStore with FEWER HBM slots than tenants, so the
    round-robin workload keeps adapters paging between HBM and the host
    tier (mixed hot/cold on purpose).  The paged run batches every
    tenant — plus adapter-less base rows — into ONE unified window via
    batched gather-LoRA; the A/B alternative is the dedicated-weights
    deployment it replaces: one ``merge_lora`` scheduler per tenant,
    serialized (no cross-tenant batching — that is the point).
    Token-identical greedy outputs are ASSERTED between the two.  The
    record carries both throughputs, the store's swap-in / demotion /
    spill / slot-wait counters, the fraction of swap-in-pending steps
    that still produced decode tokens (swap-in hidden behind running
    decode), and per-tenant mean TTFT."""
    import time as _time
    import jax as _jax
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.runtime.lora import init_lora_params, merge_lora
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)

    n_tenants = int(os.environ.get("SERVE_TENANTS", 6 if on_tpu else 4))
    hbm_slots = int(os.environ.get(
        "SERVE_HBM_ADAPTERS", max(2, n_tenants // 2) if on_tpu else 2))
    n_reqs = int(os.environ.get("SERVE_REQS", 24 if on_tpu else 12))
    max_seqs = int(os.environ.get("SERVE_B", 8 if on_tpu else 4))
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    p_lo, p_hi = ((32, 128) if on_tpu else (4, 12))
    n_lo, n_hi = ((32, 96) if on_tpu else (4, 10))

    def mk_lora(seed):
        # init_lora_params zeros B (merged == base) — randomize it so
        # every tenant is distinguishable from the base model
        lora = init_lora_params(eng.params, rank=4,
                                rng=_jax.random.PRNGKey(seed))
        r2 = np.random.default_rng(seed)
        return {p: {"a": np.asarray(ab["a"]),
                    "b": r2.normal(0, 0.05, ab["b"].shape).astype(
                        np.float32)}
                for p, ab in lora.items()}

    tenants = [f"t{i}" for i in range(n_tenants)]
    loras = {t: mk_lora(100 + i) for i, t in enumerate(tenants)}
    # round-robin over base + every tenant: adapter-less rows ride the
    # same unified window and must skip the gather-LoRA pass exactly
    ids = [None] + tenants
    workload = []
    for i in range(n_reqs):
        prompt = rng.integers(
            1, V, (int(rng.integers(p_lo, p_hi)),)).astype(np.int32)
        workload.append((ids[i % len(ids)], prompt,
                         int(rng.integers(n_lo, n_hi))))
    useful = sum(nn for _, _, nn in workload)

    bs = 16 if on_tpu else 8
    max_len = max(p.size + nn for _, p, nn in workload)
    need = -(-max_len // bs) + 1
    base = dict(block_size=bs, max_num_seqs=max_seqs,
                num_blocks=1 + need * (max_seqs + 1),
                max_num_batched_tokens=1 << 30)

    # paged run: adapters register COLD (host tier); fewer HBM slots
    # than tenants keeps the store paging under the round-robin
    cfg = ServingConfig(**base, adapters={"enabled": True,
                                          "max_hbm_adapters": hbm_slots})
    sched = ContinuousBatchingScheduler(model, eng.params, cfg,
                                        kv_cache_dtype=kv_dtype)
    for t in tenants:
        sched.register_adapter(t, lora_tree=loras[t])
    reqs = [sched.submit(p, SamplingParams(max_new_tokens=nn),
                         adapter_id=t)
            for t, p, nn in workload]
    pending_steps = overlap_steps = 0
    decoded_prev = 0
    t0 = _time.time()
    while sched.has_work():
        waiting = bool(sched._adapter_pending)
        sched.step()
        decoded = sum(len(r.output_ids) for r in reqs)
        if waiting:
            pending_steps += 1
            if decoded > decoded_prev:
                overlap_steps += 1   # swap-in hid behind running decode
        decoded_prev = decoded
    paged_s = _time.time() - t0
    paged_out = [list(r.output_ids) for r in reqs]
    assert all(len(o) == nn
               for o, (_, _, nn) in zip(paged_out, workload))

    ttft = {}
    for (t, _, _), r in zip(workload, reqs):
        ttft.setdefault(t or "base", []).append(r.ttft_s * 1e3)
    ttft_ms = {k: round(float(np.mean(v)), 3)
               for k, v in sorted(ttft.items())}

    # merged A/B: the dedicated-weights alternative — one offline
    # merge_lora scheduler per tenant, serialized; the parity oracle
    merged_out = [None] * len(workload)
    t0 = _time.time()
    for t in ids:
        mp = (merge_lora(eng.params, loras[t], 1.0, freeze_base=False)
              if t else eng.params)
        s2 = ContinuousBatchingScheduler(model, mp, ServingConfig(**base),
                                         kv_cache_dtype=kv_dtype)
        mine = [(j, p, nn) for j, (tt, p, nn) in enumerate(workload)
                if tt == t]
        rs = [s2.submit(p, SamplingParams(max_new_tokens=nn))
              for _, p, nn in mine]
        s2.run_until_idle()
        for (j, _, _), r in zip(mine, rs):
            merged_out[j] = list(r.output_ids)
    merged_s = _time.time() - t0
    assert paged_out == merged_out, \
        "paged gather-LoRA drifted from the offline-merged oracle"

    st = sched.adapter_store.summary()
    emit({
        "metric": f"{spec}_serve_lora"
                  + ("_int8kv" if kv_dtype == "int8" else ""),
        "value": round(useful / paged_s, 1),
        "unit": "tokens_per_sec",
        "detail": {
            "tenants": n_tenants, "hbm_adapter_slots": hbm_slots,
            "requests": n_reqs, "useful_tokens": useful,
            "max_num_seqs": max_seqs, "block_size": bs,
            "paged_tok_s": round(useful / paged_s, 1),
            "merged_tok_s": round(useful / merged_s, 1),
            "token_identical": True,
            "swap_ins": int(st["swap_ins"]),
            "demotions": int(st["demotions"]),
            "spills": int(st["spills"]),
            "slot_waits": int(st["slot_waits"]),
            "swapin_pending_steps": pending_steps,
            "swapin_overlap_steps": overlap_steps,
            "swapin_overlap_fraction": (
                round(overlap_steps / pending_steps, 3)
                if pending_steps else None),
            "ttft_ms_by_tenant": ttft_ms,
        },
    }, json_path)


def bench_slo_chunked(model, eng, spec, kv_dtype, on_tpu,
                      json_path=None):
    """Adversarial heavy-prefill overload (ISSUE 9): a steady pool of
    short ``chat``-class streams decodes while a few long ``batch``-class
    prompts arrive mid-flight (at fixed scheduler step counts, identical
    in both runs).  A/B: chunked prefill ON vs OFF, token-identical
    greedy outputs asserted.  The record carries p50/p99 TPOT + TTFT per
    class for both runs — ``bench_compare.py`` gates regressions on the
    ``*_ms`` keys (lower-better inferred).  The acceptance column is
    ``chat_tpot_p99_ms``: bounded with chunking on, spiking with it off
    (each spike = one long prompt's whole prefill inside one scheduler
    iteration, stalling every chat stream)."""
    import time as _time
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)

    n_chat = int(os.environ.get("SERVE_REQS", 16 if on_tpu else 6))
    n_long = int(os.environ.get("SERVE_LONG", 2))
    # off-TPU the long prompts must be long enough that the one-shot
    # prefill's quadratic attention dwarfs a chunk window's cost — the
    # verify-window programs are per-position compute off-chip (the PR 6
    # CPU-crossover caveat); on TPU the regime is the real one
    long_len = int(os.environ.get("SERVE_LONG_LEN",
                                  8192 if on_tpu else 640))
    chunk_tokens = int(os.environ.get("SERVE_CHUNK",
                                      512 if on_tpu else 64))
    max_seqs = int(os.environ.get("SERVE_B", 8 if on_tpu else 4))
    arrival_step = int(os.environ.get("SERVE_ARRIVAL_STEP", 8))
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    p_lo, p_hi = ((32, 128) if on_tpu else (6, 24))
    chat_new = int(os.environ.get("SERVE_TOKENS", 128 if on_tpu else 48))
    chat = [(rng.integers(1, V, (int(pl),)).astype(np.int32), chat_new)
            for pl in rng.integers(p_lo, p_hi, n_chat)]
    longs = [(rng.integers(1, V, (long_len,)).astype(np.int32),
              8 if on_tpu else 4) for _ in range(n_long)]
    bs = 16 if on_tpu else 8
    max_len = max(p.size + nn for p, nn in chat + longs)
    need = -(-max_len // bs) + 1
    base = dict(
        block_size=bs, max_num_seqs=max_seqs,
        num_blocks=1 + need * (max_seqs + n_long),
        # a realistic per-iteration budget (not the other modes' 1<<30):
        # the whole point is that chunking turns it into a REAL cap
        max_num_batched_tokens=max(2048, chunk_tokens * 2),
        # unfused decode: every chat token's timestamp is one scheduler
        # iteration, so the inter-token gap IS the interference signal
        # (a fused window emits k tokens with one timestamp and buries
        # the spike in zero-width gaps)
        max_fused_steps=1,
        slo={"enabled": True,
             "classes": {"chat": {"tpot_ms": 200.0, "priority": 1},
                         "batch": {"priority": 0}}})

    def run(chunked):
        cfg = ServingConfig(**base, chunked_prefill={
            "enabled": chunked, "chunk_tokens": chunk_tokens})
        sched = ContinuousBatchingScheduler(
            model, eng.params, cfg, kv_cache_dtype=kv_dtype)
        outs = None
        max_step_prefill = 0
        for _ in range(2):          # warm compiles, then measure
            creqs = [sched.submit(p, SamplingParams(max_new_tokens=nn),
                                  slo_class="chat") for p, nn in chat]
            lreqs = []
            t0 = _time.time()
            steps = 0
            max_step_prefill = 0
            while sched.has_work() or len(lreqs) < n_long:
                sched.step()
                steps += 1
                # the boundedness witness: the largest prefill spend any
                # single iteration saw — chunked it stays ~chunk_tokens,
                # unchunked it is the whole long prompt in one iteration
                max_step_prefill = max(
                    max_step_prefill,
                    int(sched.metrics.gauges.get("step_prefill_tokens",
                                                 0)))
                # long prompts arrive mid-flight, one per arrival
                # window, while the chat pool is mid-decode — the
                # step-keyed schedule is identical across the A/B
                if steps % arrival_step == 0 and len(lreqs) < n_long:
                    p, nn = longs[len(lreqs)]
                    lreqs.append(sched.submit(
                        p, SamplingParams(max_new_tokens=nn),
                        slo_class="batch"))
            dt = _time.time() - t0
            reqs = creqs + lreqs
            assert all(len(r.output_ids) == nn for r, (_, nn) in
                       zip(reqs, chat + longs))
            outs = [list(r.output_ids) for r in reqs]
        # per-class latency shape: TPOT = every inter-token gap (the
        # spike detector — a one-iteration 32k prefill shows up as one
        # huge gap in EVERY concurrent chat stream), TTFT per request
        gaps = {"chat": [], "batch": []}
        ttfts = {"chat": [], "batch": []}
        for cls, rs in (("chat", creqs), ("batch", lreqs)):
            for r in rs:
                ttfts[cls].append(r.ttft_s)
                ts = r.token_times
                gaps[cls].extend(b - a for a, b in zip(ts, ts[1:]))
        return dt, gaps, ttfts, outs, sched.metrics, max_step_prefill

    on_s, on_gaps, on_ttft, on_out, on_m, on_maxpf = run(True)
    off_s, off_gaps, off_ttft, off_out, off_m, off_maxpf = run(False)
    assert on_out == off_out, \
        "chunked prefill changed greedy output (parity violation)"
    pct = lambda xs, q: (round(float(np.percentile(xs, q)) * 1e3, 2)
                         if xs else None)
    useful = sum(nn for _, nn in chat + longs)
    # the backend-independent boundedness witness: with chunking on, no
    # single iteration may execute (much) more prefill than the chunk
    # allowance (window bucket rounding allows a few tokens of slack);
    # with it off, the long prompt's whole prefill lands in ONE iteration
    assert on_maxpf <= chunk_tokens + 64, \
        (f"chunked max per-iteration prefill {on_maxpf} blew the "
         f"chunk_tokens={chunk_tokens} allowance")
    assert off_maxpf >= long_len, \
        "unchunked run never monopolized an iteration — workload too small"
    detail = {
        "chat_requests": n_chat, "long_requests": n_long,
        "long_len": long_len, "chunk_tokens": chunk_tokens,
        "max_num_seqs": max_seqs, "block_size": bs,
        "chunked_tok_s": round(useful / on_s, 1),
        "unchunked_tok_s": round(useful / off_s, 1),
        "max_step_prefill_tokens_on": on_maxpf,
        "max_step_prefill_tokens_off": off_maxpf,
        "chunks_deferred": int(on_m.counters["chunks_deferred"]),
        "slo_violations_on": int(on_m.counters["slo_violations"]),
        "slo_violations_off": int(off_m.counters["slo_violations"]),
    }
    for cls in ("chat", "batch"):
        detail.update({
            f"{cls}_tpot_p50_ms": pct(on_gaps[cls], 50),
            f"{cls}_tpot_p99_ms": pct(on_gaps[cls], 99),
            f"{cls}_tpot_max_ms": pct(on_gaps[cls], 100),
            f"{cls}_ttft_p50_ms": pct(on_ttft[cls], 50),
            f"{cls}_ttft_p99_ms": pct(on_ttft[cls], 99),
            f"{cls}_tpot_p50_off_ms": pct(off_gaps[cls], 50),
            f"{cls}_tpot_p99_off_ms": pct(off_gaps[cls], 99),
            f"{cls}_tpot_max_off_ms": pct(off_gaps[cls], 100),
            f"{cls}_ttft_p50_off_ms": pct(off_ttft[cls], 50),
            f"{cls}_ttft_p99_off_ms": pct(off_ttft[cls], 99),
        })
    emit({
        "metric": f"{spec}_serve_slo"
                  + ("_int8kv" if kv_dtype == "int8" else ""),
        "value": detail["chat_tpot_p99_ms"],
        "unit": "chat_p99_tpot_ms",
        "detail": detail,
    }, json_path)


def bench_fleet_routing(model, eng, spec, kv_dtype, on_tpu,
                        json_path=None):
    """Shared-prefix workload through the fleet Router (ISSUE 11):
    N requests over M shared system prompts dispatched across
    ``SERVE_REPLICAS`` replica schedulers, submitted in waves (the
    steady-traffic regime — routing decisions see the caches earlier
    waves populated).  A/B: the prefix-aware scored policy vs
    round-robin, token-identical greedy outputs asserted; the record
    carries the aggregate prefix-cache hit rate per policy (the
    acceptance column: scored > round_robin) plus per-replica dispatch
    counts and resubmit/misroute counters."""
    import time as _time
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import SamplingParams
    from deepspeed_tpu.serving.fleet import Replica, Router

    n_replicas = int(os.environ.get("SERVE_REPLICAS", 2))
    n_reqs = int(os.environ.get("SERVE_REQS", 32 if on_tpu else 12))
    max_seqs = int(os.environ.get("SERVE_B", 8 if on_tpu else 4))
    n_sys = int(os.environ.get("SERVE_SYS_PROMPTS", 4 if on_tpu else 3))
    sys_len = int(os.environ.get("SERVE_SYS_LEN", 512))
    wave = int(os.environ.get("SERVE_WAVE", max(n_replicas * 2, 4)))
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    t_lo, t_hi = ((16, 96) if on_tpu else (4, 16))
    n_lo, n_hi = ((32, 128) if on_tpu else (6, 20))
    systems = [rng.integers(1, V, (sys_len,)).astype(np.int32)
               for _ in range(n_sys)]
    workload = []
    for i in range(n_reqs):
        tail = rng.integers(1, V, (int(rng.integers(t_lo, t_hi)),))
        prompt = np.concatenate([systems[int(rng.integers(n_sys))], tail])
        workload.append((prompt.astype(np.int32),
                         int(rng.integers(n_lo, n_hi))))
    useful = sum(nn for _, nn in workload)
    max_len = max(p.size + nn for p, nn in workload)
    bs = 16 if on_tpu else 8
    need = -(-max_len // bs) + 1
    base = dict(block_size=bs, max_num_seqs=max_seqs,
                num_blocks=1 + need * (max_seqs + n_sys + 1),
                max_num_batched_tokens=1 << 30,
                prefix_cache={"enabled": True})

    def run(policy):
        cfg = ServingConfig(**base, fleet={
            "num_replicas": n_replicas, "policy": policy,
            # always-fresh digests: the A/B measures the POLICY, not
            # digest staleness
            "digest_refresh_s": 0})
        replicas = [Replica(i, model, eng.params, cfg,
                            kv_cache_dtype=kv_dtype)
                    for i in range(n_replicas)]
        router = Router(replicas, cfg.fleet)

        def dispatch_counts():
            return {str(r.replica_id): int(router.registry.get_counter(
                "fleet/dispatches", replica=str(r.replica_id)))
                for r in replicas}

        outs, warm = None, {}
        for it in range(2):         # warm compiles, then measure
            handles = []
            t0 = _time.time()
            for i in range(0, n_reqs, wave):
                handles.extend(
                    router.submit(p, SamplingParams(max_new_tokens=nn))
                    for p, nn in workload[i:i + wave])
                router.run_until_idle()
            dt = _time.time() - t0
            assert all(len(h.output_ids) == nn
                       for h, (_, nn) in zip(handles, workload))
            outs = [list(h.output_ids) for h in handles]
            if it == 0:
                warm = dispatch_counts()   # the record reports only the
        counts = {rid: n - warm.get(rid, 0)  # measured pass's spread
                  for rid, n in dispatch_counts().items()}
        return dt, outs, router.aggregate_prefix_hit_rate(), counts

    sc_s, sc_out, sc_hit, sc_counts = run("scored")
    rr_s, rr_out, rr_hit, rr_counts = run("round_robin")
    assert sc_out == rr_out, \
        "routing policy changed greedy output (parity violation)"
    if n_replicas > 1 and n_sys > 1:
        # the acceptance column: concentrating same-prefix traffic can
        # never LOSE to scattering it (strictly above on the default
        # smoke: 0.873 vs 0.831 — see PERF.md PR 11)
        assert sc_hit >= rr_hit, \
            (f"prefix-aware routing hit rate {sc_hit} fell below "
             f"round-robin {rr_hit}")
    emit({
        "metric": f"{spec}_serve_fleet"
                  + ("_int8kv" if kv_dtype == "int8" else ""),
        "value": round(useful / sc_s, 1),
        "unit": "tokens_per_sec",
        "detail": {
            "replicas": n_replicas, "requests": n_reqs,
            "system_prompts": n_sys, "system_len": sys_len,
            "wave": wave, "useful_tokens": useful,
            "max_num_seqs": max_seqs, "block_size": bs,
            "scored_tok_s": round(useful / sc_s, 1),
            "round_robin_tok_s": round(useful / rr_s, 1),
            "prefix_hit_rate_scored": (round(sc_hit, 4)
                                       if sc_hit is not None else None),
            "prefix_hit_rate_round_robin": (
                round(rr_hit, 4) if rr_hit is not None else None),
            "dispatches_scored": sc_counts,
            "dispatches_round_robin": rr_counts,
        },
    }, json_path)


def bench_moe_dispatch(model, eng, spec, kv_dtype, quant, on_tpu,
                       json_path=None):
    """Mixtral expert-dispatch A/B (ISSUE 8): the same mixed-length cb
    workload through the scheduler with grouped (megablocks-style ragged
    grouped GEMM, ops/pallas/grouped_gemm.py) vs einsum (GShard [T,E,C]
    capacity tensors) dispatch — greedy outputs asserted token-identical
    (eval einsum capacity is drop-free by MixtralConfig default, so the
    two formulations compute the same math).  With SERVE_INT8_WEIGHTS=1
    the grouped path consumes the int8 expert stacks in place through
    the fused-dequant grouped kernel and the record carries the
    ``weights_floor_moe`` accounting: dense int8 bytes + top-k-DISTINCT-
    expert bytes per decode step — the floor the grouped path streams
    at, vs all-E-experts for einsum's dense dispatch."""
    import time as _time
    from deepspeed_tpu.moe.layer import dispatch_scope, gg_kernel_real
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)

    moe_cfg = getattr(model.config, "moe", None)
    if moe_cfg is None:
        raise SystemExit(f"SERVE_MODE=moe needs a routed-expert model "
                         f"(got {spec}) — e.g. SERVE_MODEL=mixtral:1b-moe")

    n_reqs = int(os.environ.get("SERVE_REQS", 24 if on_tpu else 8))
    max_seqs = int(os.environ.get("SERVE_B", 8 if on_tpu else 4))
    p_lo, p_hi = ((32, 768) if on_tpu else (4, 24))
    n_lo, n_hi = ((8, 384) if on_tpu else (4, 16))
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    workload = [
        (rng.integers(1, V, (int(pl),)).astype(np.int32), int(nn))
        for pl, nn in zip(rng.integers(p_lo, p_hi, n_reqs),
                          rng.integers(n_lo, n_hi, n_reqs))]
    useful = sum(nn for _, nn in workload)
    max_len = max(p.size + nn for p, nn in workload)
    bs = 16 if on_tpu else 4
    need = -(-max_len // bs) + 1
    base = dict(block_size=bs, max_num_seqs=max_seqs,
                num_blocks=1 + need * max_seqs,
                max_num_batched_tokens=1 << 30)

    def run(mode):
        # fresh scheduler per mode: per-instance jit caches, and the
        # dispatch choice is resolved at trace time inside the scope
        with dispatch_scope(mode):
            cfg = ServingConfig(**base)
            sched = ContinuousBatchingScheduler(
                model, eng.params, cfg, kv_cache_dtype=kv_dtype)
            outs = None
            for _ in range(2):      # warm compiles, then measure
                reqs = [sched.submit(p, SamplingParams(max_new_tokens=nn))
                        for p, nn in workload]
                t0 = _time.time()
                sched.run_until_idle()
                dt = _time.time() - t0
                assert all(len(r.output_ids) == nn
                           for r, (_, nn) in zip(reqs, workload))
                outs = [list(r.output_ids) for r in reqs]
        return dt, outs

    g_s, g_out = run("grouped")
    e_s, e_out = run("einsum")
    assert g_out == e_out, \
        "grouped dispatch changed greedy output (parity violation)"

    detail = {
        "requests": n_reqs, "useful_tokens": useful,
        "max_num_seqs": max_seqs, "block_size": bs,
        "num_experts": moe_cfg.num_experts, "top_k": moe_cfg.top_k,
        "grouped_tok_s": round(useful / g_s, 1),
        "einsum_tok_s": round(useful / e_s, 1),
        "speedup_vs_einsum": round(e_s / g_s, 3),
        "grouped_kernel_real": gg_kernel_real(),
        "int8_weights": bool(quant),
    }
    if quant:
        # weights_floor_moe: per decode step the grouped int8 path
        # streams every DENSE int8 byte once plus, per layer, only the
        # distinct routed experts' bytes (<= min(active_rows * top_k, E)
        # — the slot plan fetches each distinct expert's weight block
        # exactly once); einsum dispatch streams all E experts' bytes
        from deepspeed_tpu.models.serving import split_quantized_bytes
        dense_b, expert_b = split_quantized_bytes(eng.params["blocks"])
        E, k = moe_cfg.num_experts, moe_cfg.top_k
        per_expert = expert_b // max(E, 1)      # all layers, one expert
        distinct = min(max_seqs * k, E)
        detail.update({
            "dense_int8_bytes": dense_b,
            "expert_int8_bytes_total": expert_b,
            "weights_floor_moe_bytes": dense_b + distinct * per_expert,
            "einsum_stream_bytes": dense_b + expert_b,
            "distinct_experts_bound": distinct,
        })
    emit({
        "metric": f"{spec}_serve_moe"
                  + ("_int8kv" if kv_dtype == "int8" else "")
                  + ("_int8w" if quant else ""),
        "value": round(useful / g_s, 1),
        "unit": "tokens_per_sec",
        "detail": detail,
    }, json_path)


if __name__ == "__main__":
    main()
