"""Driver ``train_steps``: a training job through the program's normal
entry points — ``deepspeed_tpu.initialize`` then ``engine.train_batch``
on a fresh batch every optimizer step — measured over a window of
``--seconds`` seconds after warm-up.  ``--seed`` makes the traffic (the
documents' lengths and the token draws) and nothing else: the weights are
the configuration's own draw, ``deployment.init_seed``.

``run_cell`` takes the configuration and the traffic as dictionaries and
the devices as an argument, so a test rehearses it at toy size on the
CPU; the command (``run.py``) hands it a TPU or gives no result.
"""
import importlib
import json
import math
import os
import re
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import datagen, flops, trace as tr
from harness.compile_log import CompileLog
from harness.device import device_fields, peak_bytes
from harness.manifest import init_seed
from harness.spans import Spans

KERNEL = "tpu_custom_call"      # how a Mosaic kernel reads in HLO text
STEP_PROGRAM = "train/step"     # the name the engine registers its step under
IN_FLIGHT_STEPS = 2             # dispatched and not yet waited for, at most
TRACE_STEPS = 4                 # steady optimizer steps under the profiler
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "reduce-scatter",
               "collective-permute")


def say(**fields):
    """An earlier line of standard output: counts worth keeping."""
    print(json.dumps(fields), flush=True)


def check(ok, msg, problems):
    if not ok:
        problems.append(msg)
        print(f"benchmark: CHECK FAILED: {msg}", file=sys.stderr, flush=True)


def build_model(config):
    module, _, function = config["builder"]["function"].partition(":")
    model = getattr(importlib.import_module(module), function)(
        **config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        if have != want:
            raise SystemExit(f"benchmark: configuration {config['name']} "
                             f"says {key}={want}, the model has {have}")
    return model


def build_engine(config, traffic, model, seed, devices):
    """The engine of one cell.  Its weights are the configuration's own
    draw, ``deployment.init_seed``; ``seed``, the run's ``--seed``, makes
    the traffic and is not handed to the engine (the parameter stays for
    the scripts outside the benchmark that call with it)."""
    import deepspeed_tpu
    del seed
    engine_config = {
        **config["deployment"]["engine_config"],
        "train_micro_batch_size_per_gpu": traffic["micro_batch_per_chip"],
        "gradient_accumulation_steps":
            traffic["gradient_accumulation_steps"],
        "seed": init_seed(config),
    }
    spec = config["deployment"]["mesh"]
    mesh = jax.sharding.Mesh(
        np.asarray(devices).reshape(spec["shape"]), tuple(spec["axes"]))
    engine, *_ = deepspeed_tpu.initialize(model=model, config=engine_config,
                                          mesh=mesh)
    return engine, mesh


def check_split(engine, n, problems):
    """ZeRO-3: every parameter above the persistence threshold is split
    ``n`` ways, a different slice on every device."""
    threshold = engine.config.zero_config.param_persistence_threshold
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            engine.state["params"]):
        shards = leaf.addressable_shards
        if leaf.size >= threshold and not (
                len({str(s.index) for s in shards}) == n
                and all(s.data.size * n == leaf.size for s in shards)):
            check(False, f"{jax.tree_util.keystr(path)} {leaf.shape} is "
                         f"not split {n} ways ({leaf.sharding})", problems)


def params_sum(engine):
    """The float32 sum of every parameter, in one program: two runs that
    start from one draw of the weights print the same number."""
    return float(jax.jit(lambda params: sum(
        jnp.sum(leaf.astype(jnp.float32))
        for leaf in jax.tree.leaves(params)))(engine.state["params"]))


def state_bytes_per_device(engine):
    per = {}
    for leaf in jax.tree.leaves({"params": engine.state["params"],
                                 "opt_state": engine.state["opt_state"]}):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return per


def missing_kernels(program_map, wanted):
    """Those of the kernel names ``wanted`` that no instruction of the
    program's map ({instruction: {"kernel": name or None, ...}}) carries."""
    have = {row.get("kernel") for row in (program_map or {}).values()}
    return sorted(set(wanted) - have)


def inspect_program(engine, batch, checks, problems):
    """Counts from the compiled step (a cache load once the step has
    run): Mosaic custom calls and collectives by kind; with
    ``require_kernels``, the kernels the program's own map names."""
    from deepspeed_tpu.ops.attention import flash_status
    from deepspeed_tpu.telemetry.costmodel import get_report
    text = engine.compile_train_step(batch).as_text()
    kernels = sum(KERNEL in line for line in text.splitlines())
    status = {str(k): v for k, v in flash_status().items()}
    if checks["require_kernel"]:
        check(status and all(v is True for v in status.values()),
              f"flash kernel not chosen for every shape: {status}", problems)
        check(kernels > 0, f"no {KERNEL} in the compiled step", problems)
    if checks.get("require_kernels"):
        # one Mosaic call does not show that every kernel was chosen (an
        # expert FFN that fell back beside a flash kernel that did not)
        from deepspeed_tpu.telemetry.tracing import get_program_map
        missing = missing_kernels(get_program_map(STEP_PROGRAM),
                                  checks["require_kernels"])
        check(not missing, f"no instruction of the compiled step is the "
                           f"kernel {missing}", problems)
    report = get_report(STEP_PROGRAM)
    say(line="program", flash_status=status, kernel_calls=kernels,
        collectives={c: len(re.findall(rf" {c}(?:-start)?\(", text))
                     for c in COLLECTIVES},
        pallas_call_sites=report.pallas_launches if report else None,
        cost_model_flops_per_step=report.flops if report else None,
        state_bytes_per_device=state_bytes_per_device(engine))


class Phases(dict):
    """Set-up seconds by phase, for the record on the ``run`` line."""

    def __init__(self, earlier=None):
        super().__init__(earlier or {})
        self._last = time.perf_counter()

    def mark(self, name):
        now = time.perf_counter()
        self[name], self._last = now - self._last, now


def reduce_trace(trace_dir, layer_metrics, context, keep_trace):
    """The traced window's per-layer values, busy and window seconds, and
    breakdown; the raw trace is removed (or first copied to ``keep_trace``)."""
    path = tr.find_xplane(trace_dir)
    context = {**context, "trace": tr.load(path)}
    values = {}
    for name, spec in layer_metrics.items():
        reader = importlib.import_module(
            "layer_metrics.readers." + spec["reader"])
        value = reader.read(context, spec["params"])
        if value is not None:       # nothing to read: left out of the line
            values[name] = value
    busy_s, window_s = tr.busy_and_window(context["trace"])
    breakdown = {"device_ops": tr.top_device_ops(context["trace"]),
                 "idle_gaps": tr.idle_gaps(context["trace"])}
    if keep_trace:
        shutil.copy(path, keep_trace)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return values, busy_s, window_s, breakdown


def run_cell(cell, config, traffic, layer_metrics, seed, seconds, trace,
             devices, peaks, t_origin, work_dir, phases=None,
             keep_trace=None):
    """One run of one cell.  Returns the result object of the last line,
    its ``metrics`` as {name: value} (run.py adds the manifest's units).
    ``seed`` is the traffic's (see ``build_engine`` for the weights').
    ``t_origin`` is where ``setup_s`` counts from (run.py: process start,
    moved on by the TPU runtime's own start-up, which comes first there);
    ``config["checks"]`` is what the cell is held to; ``layer_metrics`` is
    {metric: its layer_metrics/<metric>.json} for a traced run;
    ``keep_trace`` is a path to copy the raw ``.xplane.pb`` to (how
    tests/data's recording was made)."""
    problems = []
    weights_seed = init_seed(config)    # a refusal before anything is built
    checks = config["checks"]
    learn = checks["learn_check"]
    phases = Phases(phases)
    compiles = CompileLog()
    spans = Spans()
    chips = len(devices)

    model = build_model(config)
    # the configuration's own sizes, which build_model has just held the
    # model to, with the parameters as counted: what the FLOPs function,
    # the plain reference and the readers are handed, whatever the family
    sizes = {**config["model"], "n_params": model.meta["n_params"]}
    engine, mesh = build_engine(config, traffic, model, seed, devices)
    global_micro = traffic["micro_batch_per_chip"] * \
        engine.topology.dp_world_size
    gas = traffic["gradient_accumulation_steps"]
    tokens_per_step = gas * global_micro * traffic["seq_len"]
    s_eff = datagen.effective_context(traffic)
    flops_function = config.get("flops", {}).get(
        "train", "train_flops_per_token")
    flops_per_token = flops.resolve(flops_function)(sizes, s_eff)
    stream = datagen.BatchStream(traffic, sizes["vocab_size"], global_micro,
                                 seed)
    initial_params_sum = params_sum(engine)
    phases.mark("initialize_s")
    losses = []                 # device scalars, one per optimizer step

    def step():
        with spans.span("input_wait"):
            batch = stream.next()
        with spans.span("train_batch"):
            losses.append(engine.train_batch(batch=batch))
        if len(losses) > IN_FLIGHT_STEPS:
            with spans.span("in_flight_wait"):
                jax.block_until_ready(losses[-1 - IN_FLIGHT_STEPS])

    try:
        # the plain reference on the first batch, at the initial weights
        first = stream.next()
        reference = importlib.import_module(
            "references." + config["reference"])
        batch_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(mesh.axis_names))
        ref_loss = reference.step_loss(
            engine.state["params"], first, sizes,
            max(1, checks["reference_chunk_tokens_per_chip"]
                // traffic["seq_len"]) * chips,
            lambda x: jax.device_put(x, batch_sharding))
        phases.mark("reference_s")
        mark_before = peak_bytes(devices)

        # warm-up: real optimizer steps, the first on that same batch
        losses.append(engine.train_batch(batch=first))
        for _ in range(checks["warmup_steps"] - 1):
            step()
        jax.block_until_ready(losses)
        phases.mark("warmup_s")
        if chips > 1:
            check_split(engine, chips, problems)
        inspect_program(engine, first, checks, problems)
        del first
        phases.mark("inspect_s")

        spans.clear()
        stream.waits_s.clear()
        compiled_before = compiles.count
        n_before = len(losses)
        setup_s = time.perf_counter() - t_origin
        if trace:
            trace_dir = os.path.join(work_dir, "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            t0 = time.perf_counter()
            for i in range(TRACE_STEPS):
                with jax.profiler.StepTraceAnnotation("train_batch",
                                                      step_num=i):
                    step()
            jax.block_until_ready(losses)
            window_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
        else:
            # dispatch while the steps already dispatched are due to end
            # inside the window, going by those that have (step() waits
            # for the one IN_FLIGHT_STEPS back): at least one more than
            # may be in flight, and a window of about --seconds, not of
            # that plus what was in flight when the clock ran out
            t0 = time.perf_counter()
            while True:
                step()
                dispatched = len(losses) - n_before
                ended = dispatched - IN_FLIGHT_STEPS
                if ended > 0 and (time.perf_counter() - t0) \
                        * dispatched / ended >= seconds:
                    break
            jax.block_until_ready(losses)
            window_s = time.perf_counter() - t0
        window_compiles = compiles.count - compiled_before
        steps = len(losses) - n_before
        mark_after = peak_bytes(devices)
    finally:
        stream.close()

    host_losses = [float(x) for x in losses]
    timed = host_losses[n_before:]
    failed = sum(not math.isfinite(x) for x in timed)
    check(all(math.isfinite(x) for x in host_losses),
          f"non-finite loss: {host_losses}", problems)
    check(window_compiles == 0,
          f"{window_compiles} compilations inside the window", problems)
    check(abs(host_losses[0] - ref_loss) <= reference.LOSS_ATOL,
          f"first-step loss {host_losses[0]} vs plain reference {ref_loss}: "
          f"over {reference.LOSS_ATOL}", problems)
    at = learn["step_index"]
    check(len(host_losses) > at and min(host_losses[1:at + 1])
          <= host_losses[0] - learn["min_drop"],
          f"no loss of steps 1..{at} is {learn['min_drop']} below the first "
          f"step's (or step {at} not reached): {host_losses[:at + 1]}",
          problems)
    if devices[0].platform == "tpu":
        check(mark_after > 0, "the TPU reports no memory statistics, so "
                              "peak_hbm_gib is not a measurement", problems)
    check(mark_after == 0 or mark_before < mark_after,
          f"the reference check set the memory peak ({mark_before} >= "
          f"{mark_after}); lower reference_chunk_tokens_per_chip", problems)
    say(line="run", cell=cell, seed=seed, init_seed=weights_seed,
        initial_params_sum=initial_params_sum, steps=steps, window_s=window_s,
        tokens_per_step=tokens_per_step, s_eff=s_eff,
        flops_per_token=flops_per_token, flops_function=flops_function,
        n_params=sizes["n_params"],
        reference_loss=ref_loss, first_loss=host_losses[0],
        loss_vs_reference=host_losses[0] - ref_loss,
        losses=host_losses, setup_phases=phases,
        compile_s=compiles.seconds, compiles=compiles.count,
        window_compiles=window_compiles,
        input_wait_max_ms=max(stream.waits_s, default=0.0) * 1e3,
        peak_bytes_before_warmup=mark_before,
        memory_stats={str(d.id): d.memory_stats() for d in devices},
        problems=problems)

    device = device_fields(devices)
    result = {"correct": not problems, "attempted": steps, "failed": failed,
              "device": device}
    if trace:
        (result["metrics"], device["busy_s"], device["window_s"],
         result["breakdown"]) = reduce_trace(trace_dir, layer_metrics, {
            "steps": steps, "spans": spans, "peaks": peaks, "s_eff": s_eff,
            "traffic": traffic,
            "tokens_per_step_per_chip": tokens_per_step / chips,
            "model": sizes}, keep_trace)
    else:
        rate = steps * tokens_per_step / window_s / chips
        result["metrics"] = {
            "tokens_per_s_per_chip": rate,
            "mfu_pct": 100.0 * rate * flops_per_token
            / peaks["bf16_flops_per_s"],
            "peak_hbm_gib": device["memory_peak_bytes"] / 2 ** 30,
            "setup_s": setup_s,
        }
    return result
