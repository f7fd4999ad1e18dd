"""How full an expert-parallel share's static plan runs, on the chip at a
cell's own size: the engine trains ``--steps`` optimizer steps as the
benchmark does, and before each the model's own router is asked how many
(token, choice) rows of the step's first micro-batch it sends to each of
ALL experts, block by block (``model.meta["routed_rows"]``).  Summed over
each share of ``experts_held`` consecutive experts and divided by a share's
even number of rows, that is what ``held_rows_factor`` has to be above —
for the share the chip holds (whose partial results the later blocks read)
and, as a wider sample, for the other shares of the same routing.

    chiprun --chips 1 -- python scripts/held_rows_table.py \
        --workload <cell> --seed <n> ... [--steps 16]

One JSON line per seed: per step the held share's fullest block and the
fullest of all shares and blocks, as multiples of the even share; per step
the load of the very step that trained (``engine.step_load()``: sums over
the step's blocks and micro-batches that leave it beside the loss) — the
rows of the held plans' live prefixes (``moe/held_live_rows`` beside
``moe/held_plan_rows``: what dispatch, the grouped kernels and combine
walk of the plan), the rows routed here over their even number and the
fullest held expert's over one expert's even share; the engine's own count
of rows over the bound; a last line with the extremes.

``--sum`` times the way back alone, at the cell's shape and with no engine:
a plan drawn at random whose held experts are sent ``--live-share`` of the
routed rows, its rows summed into their tokens by the library's form on
this device (the kernel ``ds_rowsum`` on one TPU), with and without gates,
beside the two XLA forms it replaced (one scatter-add over the plan; one a
chunk over the live prefix) and the memory's floor — microseconds a kept
row, and the largest difference from the float32 scatter-add rounded once.

    chiprun --chips 1 -- python scripts/held_rows_table.py --sum \
        --workload <cell> --seed <n> [--live-share 0.08] [--blocks 256,512]
"""
import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]

from drivers.train_steps import build_engine, build_model    # noqa: E402
from harness import datagen                                   # noqa: E402
from harness.manifest import Manifest                         # noqa: E402


def _timed(fn, *args, repeats=30, **kwargs):
    """``repeats`` calls back to back, drained once -> milliseconds a call
    (the first call, which compiles, apart)."""
    jax.block_until_ready(fn(*args, **kwargs))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def sum_table(args, config, traffic):
    """``--sum``: one JSON line per seed (see the module's docstring)."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    cfg = build_model(config).config.moe
    T = traffic["micro_batch_per_chip"] * traffic["seq_len"]
    k, D, E, held = cfg.top_k, cfg.d_model, cfg.num_experts, cfg.held
    if args.rehearse:
        os.environ["DS_GGEMM_INTERPRET"] = "1"
    bound = gg.held_rows_bound(T * k, held, E, factor=cfg.held_rows_factor)
    share = args.live_share or held / E
    for seed in args.seed:
        rng = np.random.default_rng(seed)
        # each token's top_k distinct experts; the held ones drawn with
        # ``share`` of the probability
        p = np.full(E, (1 - share) / (E - held))
        p[cfg.expert_offset:cfg.expert_offset + held] = share / held
        eids = np.stack([rng.choice(E, k, replace=False, p=p)
                         for _ in range(T)]).reshape(-1)
        plan, over = gg.make_held_group_plan(
            jnp.asarray(eids, jnp.int32), cfg.expert_offset, held, bound)
        Mp = plan.padded_rows
        kept = int(jnp.sum(plan.padded_to_row < T * k))
        y = jnp.asarray(rng.standard_normal((Mp, D)), jnp.bfloat16)
        gates = jnp.asarray(rng.uniform(0.1, 1, T * k), jnp.float32)
        back = gg._way_back(plan)
        chunk = gg._live_chunk_rows(plan, D * 2)
        live = gg.live_rows(plan)
        token_of_row = plan.padded_to_row // k

        def ours(y, gates, back):
            return gg._sum_live_into_tokens(y, gates, back, T, k, live,
                                            chunk)

        def one_pass(y, gate_of_row):
            rows = y if gate_of_row is None else \
                gate_of_row.astype(y.dtype)[:, None] * y
            return jnp.zeros((T, D), jnp.float32).at[token_of_row].add(
                rows.astype(jnp.float32), mode="drop").astype(y.dtype)

        def by_chunks(y):
            # the chunked form of PRs 39-42, kept here as the oracle is
            def add(start, first, acc):
                at = gg._chunk_of(token_of_row, start, chunk)
                return acc.at[jnp.where(gg._seen(start, first, chunk), T,
                                        at)].add(
                    gg._chunk_of(y, start, chunk).astype(jnp.float32),
                    mode="drop")
            return gg._over_live_chunks(
                Mp, chunk, live, add,
                jnp.zeros((T, D), jnp.float32)).astype(y.dtype)

        gate_of_row = jnp.take(gates, plan.padded_to_row, mode="fill",
                               fill_value=0)
        gated = jax.jit(ours)
        plain = jax.jit(functools.partial(ours, gates=None))
        the_librarys = gg._ROWSUM_BLOCKS
        want = jax.jit(one_pass)
        from deepspeed_tpu.telemetry import tracing
        with tracing.step_account("sum"):
            got_gated = gated(y, gates, back)
        path = tracing.held_row_sums("sum")
        got_plain = plain(y, back=back)

        def diff(a, b):
            return float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32))))

        line = {
            "workload": args.workload, "seed": seed,
            "device": jax.devices()[0].device_kind, "tokens": T, "width": D,
            "top_k": k, "plan_rows": Mp, "live_rows": int(live),
            "kept_rows": kept, "rows_over_bound": int(over),
            "sums": path,
            "max_diff_gated": diff(got_gated, want(y, gate_of_row)),
            "max_diff_plain": diff(got_plain, want(y, None)),
            "floor_ms": (kept + T) * D * 2 / 819e9 * 1e3}
        # (a rehearsal makes one call each: its numbers mean nothing)
        timed = functools.partial(_timed, repeats=1 if args.rehearse else 30)
        ms = {"kernel_gated": timed(gated, y, gates, back),
              "kernel_plain": timed(plain, y, back=back),
              "xla_one_pass": timed(want, y, None),
              "xla_by_chunks": timed(jax.jit(by_chunks), y)}
        for blocks in args.blocks:
            gg._ROWSUM_BLOCKS = (("", blocks),)
            at = "@%dx%d" % blocks
            ms["kernel_gated" + at] = timed(jax.jit(ours), y, gates,
                                            back)
            ms["kernel_plain" + at] = timed(jax.jit(functools.partial(
                ours, gates=None)), y, back=back)
        gg._ROWSUM_BLOCKS = the_librarys
        line["ms"] = {n: round(v, 4) for n, v in ms.items()}
        line["us_per_kept_row"] = {
            n: round(v * 1e3 / max(kept, 1), 4) for n, v in ms.items()}
        print(json.dumps(line), flush=True)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--sum", action="store_true",
                        help="time the sum into tokens alone")
    parser.add_argument("--live-share", type=float,
                        help="--sum: the share of the routed rows the held "
                             "experts are sent (default: their even share)")
    parser.add_argument("--blocks", nargs="+", default=[],
                        type=lambda v: tuple(int(n) for n in v.split(",")),
                        help="--sum: also time the kernel at these (tokens "
                             "a block, rows a stage), e.g. 512,256 256,512")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--steps", type=int, default=16)
    parser.add_argument("--held-rows-factor", type=int,
                        help="train with this factor in the place of the "
                             "configuration's (a bound too low to count "
                             "under drops rows and bends the run)")
    parser.add_argument("--rehearse", action="store_true",
                        help="the cell's toy sizes, for a run on the CPU")
    args = parser.parse_args()
    if args.rehearse:
        sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
        from rehearse import toy
        _, config, traffic = toy(Manifest(ROOT), args.workload)
    else:
        _, config, traffic = Manifest(ROOT).cell(args.workload)
    if args.held_rows_factor:
        config["model"]["held_rows_factor"] = args.held_rows_factor
        config["builder"]["kwargs"]["held_rows_factor"] = \
            args.held_rows_factor
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.sum:
        return sum_table(args, config, traffic)
    held_worst, any_worst = [], []
    for seed in args.seed:
        model = build_model(config)
        cfg = model.config.moe
        engine, _ = build_engine(config, traffic, model, seed, jax.devices())
        # the all-experts sample is a second program: the step knows the
        # share it holds only
        rows = jax.jit(model.meta["routed_rows"])
        stream = datagen.BatchStream(
            traffic, model.config.vocab_size,
            traffic["micro_batch_per_chip"], seed)
        even = traffic["micro_batch_per_chip"] * traffic["seq_len"] \
            * cfg.top_k * cfg.held / cfg.num_experts
        mine = cfg.expert_offset // cfg.held
        held, fullest = [], []
        try:
            for _ in range(args.steps):
                batch = stream.next()
                micro = {k: jnp.asarray(np.asarray(v)[0])
                         for k, v in batch.items()}
                routed = np.asarray(rows(engine.state["params"], micro))
                shares = routed \
                    .reshape(-1, cfg.num_experts // cfg.held, cfg.held) \
                    .sum(-1) / even               # [blocks, shares]
                held.append(round(float(shares[:, mine].max()), 3))
                fullest.append(round(float(shares.max()), 3))
                engine.train_batch(batch=batch)
        finally:
            stream.close()
        held_worst.append(max(held))
        any_worst.append(max(fullest))
        from deepspeed_tpu.moe import layer as moe
        steps = engine.step_load()["last"]      # the newest 64 at most
        ratio = lambda above, below: [          # noqa: E731
            round(step[above] / step[below], 4) for step in steps]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "device": jax.devices()[0].device_kind,
            "even_share_rows": even, "held_rows_factor": cfg.held_rows_factor,
            "held_share_fullest_block": held,
            "any_share_fullest_block": fullest,
            "held_plan_rows": sorted({s[moe.HELD_PLAN_ROWS] for s in steps}),
            "held_live_rows": [s[moe.HELD_LIVE_ROWS] for s in steps],
            "routed_over_even_rows": ratio(moe.ROUTED_ROWS, moe.EVEN_ROWS),
            "fullest_expert_over_even": ratio(moe.FULLEST_EXPERT_ROWS,
                                              moe.EVEN_EXPERT_ROWS),
            "step_counts": engine.step_counts()}), flush=True)
        del engine
    print(json.dumps({"seeds": len(args.seed), "steps": args.steps,
                      "held_share_max": max(held_worst),
                      "any_share_max": max(any_worst)}), flush=True)


if __name__ == "__main__":
    main()
