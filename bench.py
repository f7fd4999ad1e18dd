"""Benchmark entry point (driver-run on real TPU hardware).

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Workload: GPT-2 760M causal-LM training step, ZeRO-2, bf16 compute + fp32 master, on the
available chip(s).  Reports model FLOPs utilisation (MFU) against the chip's
bf16 peak; ``vs_baseline`` is MFU relative to the BASELINE.md acceptance target
of 35% MFU.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np
import jax

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import gpt2_model
from deepspeed_tpu.telemetry.mfu import peak_flops_per_device
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

MODEL_SIZE = os.environ.get("BENCH_MODEL", "760m")
SEQ = int(os.environ.get("BENCH_SEQ", 1024))
MICRO = int(os.environ.get("BENCH_MICRO", 12))
STEPS = int(os.environ.get("BENCH_STEPS", 10))
WARMUP = int(os.environ.get("BENCH_WARMUP", 3))
ZERO_STAGE = int(os.environ.get("BENCH_ZERO", 2))
OFFLOAD = bool(int(os.environ.get("BENCH_OFFLOAD", "0")))
REMAT_POLICY = os.environ.get("BENCH_REMAT_POLICY", "nothing")


def chip_peak_flops() -> float:
    """bf16 peak of the attached chip from the library's table; a device
    the table does not know (or no TPU at all) is an error, not a default."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures on a TPU; jax found "
                         f"platform={dev.platform!r} ({dev.device_kind})")
    peak = peak_flops_per_device(dev, env={})
    if peak is None:
        raise SystemExit(f"bench.py: no peak FLOP/s for device_kind="
                         f"{dev.device_kind!r} in telemetry/mfu.py")
    return peak


def train_flops_per_token(model, seq: int) -> float:
    """MFU accounting: 6N matmul flops/token (N = ACTIVE params for MoE —
    model.flops_per_token) + causal attention (12*L*S*D fwd+bwd, halved
    for causal masking)."""
    cfg = model.config
    return ((model.flops_per_token or 6.0 * model.meta["n_params"])
            + 6.0 * cfg.num_layers * seq * cfg.d_model)


def train_config(micro: int, zero_stage: int, precision: str = "diet",
                 offload: bool = False) -> dict:
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 0,
    }
    # optimizer-phase byte diet (runtime/bf16_optimizer.py): Kahan bf16
    # masters / bf16 moments / bf16 grad accumulation.  DEFAULT since
    # round 5 — the metric name carries "_diet" so rounds stay
    # comparable; BENCH_PRECISION=fp32 restores fp32 optimizer states
    # (the round-4 configuration).  The diet's loss trajectory tracks
    # fp32 masters (PERF.md; tests/test_bf16_optimizer.py).
    if precision == "diet":
        config["bf16"].update(master_weights_dtype="bfloat16",
                              optimizer_states_dtype="bfloat16")
        config["data_types"] = {"grad_accum_dtype": "bf16"}
    if offload:
        # ZeRO-Infinity tier: params+optimizer state in pinned host DRAM,
        # streamed per layer (models beyond one chip's HBM, e.g. 1.3B+ fp32
        # state on a 16 GB v5e)
        config["zero_optimization"]["offload_optimizer"] = {"device": "cpu"}
        config["zero_optimization"]["offload_param"] = {"device": "cpu"}
    return config


def main():
    enable_compile_cache()
    peak_flops = chip_peak_flops()
    n_chips = jax.device_count()
    remat = bool(int(os.environ.get("BENCH_REMAT", "1")))
    if MODEL_SIZE.startswith("bert"):
        # BASELINE row 1 (fastest-BERT): BENCH_MODEL=bert-large BENCH_SEQ=128
        # BENCH_MICRO=128 / BENCH_SEQ=512 BENCH_MICRO=32
        from deepspeed_tpu.models.bert import bert_model
        model = bert_model(MODEL_SIZE.split("-", 1)[1], max_seq_len=SEQ,
                           dtype="bfloat16", remat=remat,
                           remat_policy=REMAT_POLICY)
    elif MODEL_SIZE.startswith("mixtral"):
        # BASELINE config 5's measurable half: BENCH_MODEL=mixtral-1b-moe
        # BENCH_SEQ=1024 BENCH_MICRO=8 (ep=1 single chip)
        from deepspeed_tpu.models.mixtral import mixtral_model
        model = mixtral_model(MODEL_SIZE.split("-", 1)[1], max_seq_len=SEQ,
                              dtype="bfloat16", remat=remat,
                              remat_policy=REMAT_POLICY)
    else:
        model = gpt2_model(MODEL_SIZE, max_seq_len=SEQ, dtype="bfloat16",
                           remat=remat, remat_policy=REMAT_POLICY)
    n_params = model.meta["n_params"]
    cfg = model.config
    flops_per_token = train_flops_per_token(model, SEQ)
    precision = os.environ.get("BENCH_PRECISION", "diet")
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=train_config(MICRO, ZERO_STAGE, precision,
                                         OFFLOAD))

    rng = np.random.default_rng(0)
    global_batch = MICRO * engine.topology.dp_world_size

    def batch():
        ids = rng.integers(0, cfg.vocab_size, size=(1, global_batch, SEQ),
                           dtype=np.int32)
        if MODEL_SIZE.startswith("bert"):     # 15% MLM objective
            labels = np.where(rng.random(ids.shape) < 0.15, ids,
                              -100).astype(np.int32)
            return {"input_ids": ids, "labels": labels}
        return {"input_ids": ids}

    for _ in range(WARMUP):
        loss = engine.train_batch(batch=batch())
    jax.block_until_ready(loss)

    t0 = time.time()
    for _ in range(STEPS):
        loss = engine.train_batch(batch=batch())
    jax.block_until_ready(loss)   # chained data dependence -> all steps done
    dt = (time.time() - t0) / STEPS

    tokens_per_sec = global_batch * SEQ / dt
    tokens_per_sec_chip = tokens_per_sec / n_chips
    mfu = tokens_per_sec_chip * flops_per_token / peak_flops
    dev = jax.devices()[0]

    print(json.dumps({
        "metric": ((MODEL_SIZE if MODEL_SIZE.startswith(("bert", "mixtral"))
                    else f"gpt2_{MODEL_SIZE}")
                   + f"_bf16_zero{ZERO_STAGE}"
                   + ("_diet" if precision == "diet" else "")
                   + ("_offload" if OFFLOAD else "") + "_mfu"),
        "value": round(mfu, 4),
        "unit": "MFU_fraction",
        "vs_baseline": round(mfu / 0.35, 4),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": n_chips},
        "detail": {
            "tokens_per_sec_per_chip": round(tokens_per_sec_chip, 1),
            "step_time_s": round(dt, 4),
            "seq_len": SEQ,
            "micro_batch": MICRO,
            "n_chips": n_chips,
            "n_params": n_params,
            "loss": float(loss),
        },
    }))


if __name__ == "__main__":
    main()
