"""Bring-up proof: the training path on a TPU, through its normal entry
points, at GPT-2 760M width.

    python chip_smoke.py             # one chip: kernel vs reference, trainer
    python chip_smoke.py --chips 4   # four chips: ZeRO-3 over data=4 vs one

One process drives every chip it uses.  Each phase is a function that
raises on failure; nothing here catches, so a failed phase is a non-zero
exit and no result line.  The last line of stdout is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The numbers printed on the earlier lines (compile seconds, step seconds,
tokens/s, MFU) are informational and carry the device they came from.
``main()`` insists on a TPU; the phase functions take sizes and step
counts as arguments so a test or a scratch script can rehearse them at
toy size on the CPU.
"""
import argparse
import importlib.metadata
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np
import jax

import deepspeed_tpu
from bench import train_config, train_flops_per_token
from deepspeed_tpu.models.gpt2 import gpt2_model
from deepspeed_tpu.ops.attention import flash_status
from deepspeed_tpu.telemetry import tracing
from deepspeed_tpu.telemetry.mfu import peak_flops_per_device
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

KERNEL = "tpu_custom_call"          # how a Mosaic kernel reads in HLO text
#: |loss difference| allowed between two bf16 runs of the same step that
#: differ only in reduction order (kernel vs einsum, four shards vs one)
BF16_LOSS_ATOL = 0.03


def say(**fields):
    print(json.dumps(fields), flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def backend_compiles(since=0.0):
    """(count, seconds) of the backend compilations, persistent-cache
    loads included, that ended after ``since`` (``time.perf_counter()``),
    from the program's own account of what it traced, lowered and
    compiled (``telemetry.tracing.setup_account``: jax's monitoring
    events, one listener set in the program).  A folded row of unnamed
    programs counts whole if its last event ended after ``since``."""
    rows = [r for r in tracing.setup_account()["rows"]
            if r["stage"] in ("compile", "cache_load") and r["end"] > since]
    return (sum(r.get("count", 1) for r in rows),
            sum(r["self_s"] for r in rows))


def device_fields():
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def seeded_batch(seed, batch, seq, vocab):
    """One global batch, leading gas dim of 1."""
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(1, batch, seq),
                                      dtype=np.int32)}


def release(engine):
    """Free an engine's device buffers now (the next phase needs the HBM)."""
    for leaf in jax.tree.leaves(engine.state):
        if isinstance(leaf, jax.Array):
            leaf.delete()


def pallas_call_sites():
    """Kernel launch sites in the train step of the engine that stepped
    last, from its cost report, which is made here, for the asking; a
    dead cost model fails here."""
    report = tracing.get_program_cost()
    check(report is not None, "cost model did not analyse the train step")
    return report.pallas_launches


def require_flash():
    """The auto ladder chose a Pallas kernel for every shape class it met
    and the step's jaxpr holds kernel launches — anything else means the
    [S,S] einsum stood in."""
    status = flash_status()
    check(status and all(v is True for v in status.values()),
          f"flash kernel not selected: {status}")
    sites = pallas_call_sites()
    check(sites > 0, "no pallas_call in the train step's jaxpr")
    return sites


def kernel_calls(engine, batch, mosaic):
    """The Mosaic custom calls in the compiled step, one HLO line each.
    ``mosaic=False`` is for a CPU rehearsal only: Pallas interprets
    there and the HLO holds no custom call to look for."""
    text = engine.compile_train_step(batch).as_text()
    calls = [l for l in text.splitlines() if KERNEL in l]
    check(calls or not mosaic, f"no {KERNEL} in the compiled step")
    return text, calls


# ------------------------------------------------------------------ phases
def phase_device(chips, compile_cache):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, jax found platform="
            f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) != chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} but jax sees {len(devices)}")
    say(phase="device", device=device_fields(), jax=jax.__version__,
        jaxlib=importlib.metadata.version("jaxlib"),
        libtpu=importlib.metadata.version("libtpu"),
        compile_cache=compile_cache)


def phase_kernel_vs_reference(size="760m", num_layers=2, seq=1024, micro=12,
                              mosaic=True, **widths):
    """One step with the flash kernel and one with the [S,S] einsum, same
    seed and batch: the losses agree and the kernel is in the program."""
    losses = {}
    for impl in ("auto", "xla"):
        model = gpt2_model(size, num_layers=num_layers, max_seq_len=seq,
                           dtype="bfloat16", remat=True,
                           attention_impl=impl, **widths)
        engine, *_ = deepspeed_tpu.initialize(
            model=model, config=train_config(micro, zero_stage=2))
        batch = seeded_batch(0, micro * engine.topology.dp_world_size, seq,
                             model.config.vocab_size)
        losses[impl] = float(engine.train_batch(batch=batch))
        if impl == "auto":
            sites = require_flash()
            _, calls = kernel_calls(engine, batch, mosaic)
        else:
            check(pallas_call_sites() == 0,
                  "the einsum step holds pallas_call sites")
        release(engine)
    check(all(math.isfinite(v) for v in losses.values()), f"{losses}")
    check(abs(losses["auto"] - losses["xla"]) < BF16_LOSS_ATOL,
          f"flash and einsum losses differ: {losses}")
    cfg = model.config
    say(phase="kernel_vs_reference", device=device_fields(),
        model={"d_model": cfg.d_model, "num_heads": cfg.num_heads,
               "vocab_size": cfg.vocab_size, "seq": seq, "micro": micro},
        reduced={"num_layers": num_layers}, loss_flash=losses["auto"],
        loss_einsum=losses["xla"], pallas_call_sites=sites,
        kernel_calls=len(calls),
        flash_status={str(k): v for k, v in flash_status().items()})


def phase_trainer(size="760m", seq=1024, micro=12, warmup=2,
                  steps=5, mosaic=True, **widths):
    """bench.py's configuration through initialize/train_batch: warm-up,
    then timed steps on a fixed seeded batch."""
    device = jax.devices()[0]
    peak = peak_flops_per_device(device)
    check(peak is not None,
          f"no peak FLOP/s for device_kind={device.device_kind!r}")
    model = gpt2_model(size, max_seq_len=seq, dtype="bfloat16", remat=True,
                       **widths)
    cfg = model.config
    t_start = t0 = time.perf_counter()
    # the engine waits for the device where it prints: at the last timed
    # step, where this loop waits anyway, its rate gauges are written
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config={**train_config(micro, zero_stage=2),
                             "steps_per_print": warmup + steps})
    global_batch = micro * engine.topology.dp_world_size
    batch = seeded_batch(0, global_batch, seq, cfg.vocab_size)
    init_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    losses = [engine.train_batch(batch=batch) for _ in range(warmup)]
    jax.block_until_ready(losses)
    warmup_s = time.perf_counter() - t0
    setup_compiles, compile_s = backend_compiles(since=t_start)
    kernel_sites = require_flash()

    t_timed = t0 = time.perf_counter()
    timed = [engine.train_batch(batch=batch) for _ in range(steps)]
    jax.block_until_ready(timed)
    step_s = (time.perf_counter() - t0) / steps
    timed_compiles, _ = backend_compiles(since=t_timed)
    _, calls = kernel_calls(engine, batch, mosaic)

    losses = [float(x) for x in losses + timed]
    tokens_per_s = global_batch * seq / step_s
    n = len(jax.devices())
    mfu = tokens_per_s / n * train_flops_per_token(model, seq) / peak
    # the engine's own MFU is 6N over a window that began at its first
    # step, compile and all: above the timed steps' it divided by a
    # dispatch time
    gauge = engine.telemetry_registry.get_gauge("train/mfu")
    say(phase="trainer", device=device_fields(),
        model={"name": model.meta["name"], "n_params": model.meta["n_params"],
               "num_layers": cfg.num_layers, "d_model": cfg.d_model,
               "seq": seq, "micro": micro, "zero_stage": 2},
        setup={"init_s": init_s, "warmup_s": warmup_s,
               "compile_s": compile_s, "compiles": setup_compiles},
        step_s=step_s, tokens_per_s_per_chip=tokens_per_s / n, mfu=mfu,
        engine_mfu_gauge=gauge, peak_flops_per_chip=peak, timed_steps=steps,
        timed_compiles=timed_compiles, pallas_call_sites=kernel_sites,
        kernel_calls=len(calls), losses=losses)
    check(all(math.isfinite(v) for v in losses), f"non-finite loss: {losses}")
    check(abs(losses[0] - math.log(cfg.vocab_size)) < 0.5,
          f"first loss {losses[0]} is not near ln(vocab)")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(timed_compiles == 0,
          f"{timed_compiles} compilations inside the timed steps")
    check(engine._peak_flops and gauge is not None,
          "engine published no MFU from the library's peak table")
    check(0 < gauge <= mfu, f"engine's train/mfu {gauge} against {mfu} "
                            f"over the timed steps")
    release(engine)


def _state_bytes_per_device(engine):
    per = {}
    for leaf in jax.tree.leaves({"params": engine.state["params"],
                                 "opt_state": engine.state["opt_state"]}):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return per


def phase_zero3_four_chips(size="760m", num_layers=6, seq=1024, micro=12,
                           steps=4, mosaic=True, **widths):
    """ZeRO-3 over data=N on every device against the same model, seed and
    global batches on one of them (micro-batches accumulated instead of
    spread): same losses, state spread 1/N per device, kernel partitioned."""
    devices = jax.devices()
    n = len(devices)
    model = gpt2_model(size, num_layers=num_layers, max_seq_len=seq,
                       dtype="bfloat16", remat=True, **widths)
    vocab = model.config.vocab_size
    batches = [seeded_batch(100 + i, n * micro, seq, vocab)
               for i in range(steps)]

    def run(mesh_devices, gas):
        # fp32 masters and moments: accumulating gas micro-batches and
        # reducing over gas devices then differ in summation order only
        config = {**train_config(micro, zero_stage=3, precision="fp32"),
                  "gradient_accumulation_steps": gas}
        mesh = jax.sharding.Mesh(np.asarray(mesh_devices), ("data",))
        engine, *_ = deepspeed_tpu.initialize(model=model, config=config,
                                              mesh=mesh)
        losses = []
        for b in batches:
            b = {k: v.reshape(gas, -1, seq) for k, v in b.items()}
            losses.append(float(engine.train_batch(batch=b)))
        return engine, b, losses

    engine, batch, sharded = run(devices, 1)
    kernel_sites = require_flash()
    threshold = engine._config.zero_config.param_persistence_threshold
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            engine.state["params"]):
        shards = leaf.addressable_shards
        check(len({s.device.id for s in shards}) == n,
              f"{path}: not on {n} devices")
        if leaf.size >= threshold:
            check(len({str(s.index) for s in shards}) == n
                  and all(s.data.size * n == leaf.size for s in shards),
                  f"{path} {leaf.shape}: not split {n} ways "
                  f"({leaf.sharding})")
    per_device = _state_bytes_per_device(engine)
    text, calls = kernel_calls(engine, batch, mosaic)
    release(engine)

    engine, _, single = run(devices[:1], n)
    one_device = _state_bytes_per_device(engine)
    release(engine)

    total = sum(one_device.values())
    say(phase="zero3_four_chips", device=device_fields(),
        model={"d_model": model.config.d_model,
               "num_heads": model.config.num_heads, "vocab_size": vocab,
               "seq": seq, "micro": micro, "global_batch": n * micro,
               "zero_stage": 3},
        reduced={"num_layers": num_layers},
        losses_sharded=sharded, losses_one_device=single,
        state_bytes_per_device=per_device, state_bytes_one_device=total,
        pallas_call_sites=kernel_sites, kernel_calls=len(calls),
        all_gathers=text.count(" all-gather("),
        reduce_scatters=text.count(" reduce-scatter("))
    check(all(math.isfinite(v) for v in sharded + single),
          f"non-finite loss: {sharded} {single}")
    check(max(abs(a - b) for a, b in zip(sharded, single)) < BF16_LOSS_ATOL,
          f"sharded and one-device losses differ: {sharded} vs {single}")
    check(len(per_device) == n and len(one_device) == 1,
          f"state lives on {sorted(per_device)} / {sorted(one_device)}")
    check(all(total / n <= b <= total / n + 0.02 * total
              for b in per_device.values()),
          f"per-device state {per_device} is not 1/{n} of {total}")
    check(all(f"[{micro}," in l for l in calls),
          f"a kernel operand is not the per-device batch {micro}")
    check(" all-gather(" in text or " all-gather-start(" in text,
          "no all-gather in the ZeRO-3 step")
    check(" reduce-scatter(" in text or " all-reduce(" in text,
          "no gradient reduction in the ZeRO-3 step")


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: kernel-vs-reference and trainer phases; "
                             "4: the ZeRO-3 phase and its one-device "
                             "reference, nothing else")
    args = parser.parse_args()
    phase_device(args.chips, enable_compile_cache())
    if args.chips == 4:
        phase_zero3_four_chips()
    else:
        phase_kernel_vs_reference()
        phase_trainer()
    print(json.dumps({"ok": True, "device": device_fields()}), flush=True)


if __name__ == "__main__":
    main()
