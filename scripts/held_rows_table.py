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
and block the rows of the held plan's live prefix as the expert layer
itself reports them (``moe/held_live_rows`` beside ``moe/held_plan_rows``,
gauges of the registry tap: the blocks in the order the device ran them)
— what dispatch, the grouped kernels and combine walk of the plan; the
engine's own count of rows over the bound; a last line with the extremes.
"""
import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]

from drivers.train_steps import build_engine, build_model    # noqa: E402
from harness import datagen                                   # noqa: E402
from harness.manifest import Manifest                         # noqa: E402


class _HeldPlanTap:
    """The registry tap of ``moe/layer.py`` as a list: every value a held
    expert layer sets its two gauges to, in the order they arrive."""

    def __init__(self):
        self.live, self.plan = [], []

    def set_gauge(self, name, value, **labels):
        from deepspeed_tpu.moe.layer import HELD_LIVE_ROWS, HELD_PLAN_ROWS
        if name == HELD_LIVE_ROWS:
            self.live.append(int(value))
        elif name == HELD_PLAN_ROWS:
            self.plan.append(int(value))

    def inc(self, name, value=1.0, **labels):
        pass

    def taken(self):
        jax.effects_barrier()
        live, self.live = self.live, []
        return live


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
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
    held_worst, any_worst = [], []
    for seed in args.seed:
        model = build_model(config)
        cfg = model.config.moe
        engine, _ = build_engine(config, traffic, model, seed, jax.devices())
        # the tap is in place only while the diagnostic is traced: the
        # engine's step, traced at its first call below, has no callback
        from deepspeed_tpu.moe.layer import set_moe_metrics_registry
        tap = _HeldPlanTap()
        rows = jax.jit(model.meta["routed_rows"])
        stream = datagen.BatchStream(
            traffic, model.config.vocab_size,
            traffic["micro_batch_per_chip"], seed)
        even = traffic["micro_batch_per_chip"] * traffic["seq_len"] \
            * cfg.top_k * cfg.held / cfg.num_experts
        mine = cfg.expert_offset // cfg.held
        held, fullest, live = [], [], []
        try:
            for _ in range(args.steps):
                batch = stream.next()
                micro = {k: jnp.asarray(np.asarray(v)[0])
                         for k, v in batch.items()}
                set_moe_metrics_registry(tap)
                try:
                    routed = np.asarray(rows(engine.state["params"], micro))
                finally:
                    set_moe_metrics_registry(None)
                live.append(tap.taken())
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
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "device": jax.devices()[0].device_kind,
            "even_share_rows": even, "held_rows_factor": cfg.held_rows_factor,
            "held_share_fullest_block": held,
            "any_share_fullest_block": fullest,
            "held_plan_rows": sorted(set(tap.plan)),
            "held_live_rows": live,
            "step_counts": engine.step_counts()}), flush=True)
        del engine
    print(json.dumps({"seeds": len(args.seed), "steps": args.steps,
                      "held_share_max": max(held_worst),
                      "any_share_max": max(any_worst)}), flush=True)


if __name__ == "__main__":
    main()
