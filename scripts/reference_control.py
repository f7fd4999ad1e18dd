"""The control of a cell's ``LOSS_ATOL``, on the chip at the cell's own
size: the plain reference's loss on a seed's first batch at freshly
initialised parameters (bf16, as the engine holds them), in float32 and
with every matrix product's operands first rounded to each of ``--dtypes``
(the reference's ``matmul_dtype``).  The precision the configuration states
has to read inside the limit in every seed, the nearest one below it
outside in every seed.

    chiprun --chips 1 -- python scripts/reference_control.py \
        --workload <cell> --seed <n> ...

One JSON line per seed: the float32 loss and each dtype's distance from
it — and, where the reference has ``token_losses``, the root of the mean
squared difference of the first micro-batch's scored positions' losses
(what ``TOKEN_NLL_RMS_ATOL`` limits) — and, where it also has
``mtp_token_losses`` (a prediction module's, which the benchmark's driver
does not read), the same of those under ``mtp_*``, with the program's own
forward pass (its kernels and precision, at these parameters) held to the
float32 reference beside the controls (``program_*``); a last line with the
extremes over the seeds and the limits.
"""
import argparse
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]

from drivers.train_steps import build_model                   # noqa: E402
from harness import datagen                                   # noqa: E402
from harness.manifest import Manifest                         # noqa: E402


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--dtypes", nargs="+",
                        default=["bfloat16", "float8_e4m3fn"])
    parser.add_argument("--rehearse", action="store_true",
                        help="the cell's toy sizes, for a run on the CPU")
    args = parser.parse_args()
    if args.rehearse:
        sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
        from rehearse import toy
        cell, config, traffic = toy(Manifest(ROOT), args.workload)
    else:
        cell, config, traffic = Manifest(ROOT).cell(args.workload)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    reference = importlib.import_module("references." + config["reference"])
    model = build_model(config)
    sizes = {**config["model"], "n_params": model.meta["n_params"]}
    init = jax.jit(lambda key: jax.tree.map(
        lambda p: p.astype(jnp.bfloat16), model.init(key)))
    chunk = max(1, config["checks"]["reference_chunk_tokens_per_chip"]
                // traffic["seq_len"])
    distances = {name: [] for name in args.dtypes}
    per_token = getattr(reference, "token_losses", None)
    token_rms = {name: [] for name in args.dtypes if per_token}
    heads = {"mtp_": (reference.mtp_token_losses,
                      jax.jit(model.meta["mtp_token_losses"]))} \
        if hasattr(reference, "mtp_token_losses") else {}
    if heads:
        from drivers.train_steps_counted import token_nll
        heads[""] = (per_token, lambda p, b: (token_nll(model, p, b), None))
    head_rms = {}
    for seed in args.seed:
        params = init(jax.random.PRNGKey(seed))
        stream = datagen.BatchStream(traffic, sizes["vocab_size"],
                                     traffic["micro_batch_per_chip"], seed)
        first = stream.next()
        stream.close()
        exact = reference.step_loss(params, first, sizes, chunk)
        line = {"workload": args.workload, "seed": seed,
                "device": jax.devices()[0].device_kind, "float32": exact}
        for name in args.dtypes:
            line[name] = reference.step_loss(
                params, first, sizes, chunk,
                matmul_dtype=getattr(jnp, name)) - exact
            distances[name].append(line[name])
        if per_token:
            micro = {k: np.asarray(v)[0] for k, v in first.items()}
            want, scored = per_token(params, micro, sizes, chunk)
            for name in args.dtypes:
                got, _ = per_token(params, micro, sizes, chunk,
                                   matmul_dtype=getattr(jnp, name))
                token_rms[name].append(float(np.sqrt(np.mean(
                    np.square(got - want)[scored]))))
                line[name + "_token_nll_rms"] = token_rms[name][-1]
            rms = lambda a, b: float(np.sqrt(np.mean(
                np.square(np.asarray(a) - b)[scored])))
            for head, (plain, program) in heads.items():
                want, scored = plain(params, micro, sizes, chunk)
                readings = {"program_" + head: program(
                    params, {k: jnp.asarray(v) for k, v in micro.items()})[0]}
                if head:
                    readings.update({
                        name + "_" + head: plain(
                            params, micro, sizes, chunk,
                            matmul_dtype=getattr(jnp, name))[0]
                        for name in args.dtypes})
                for key, got in readings.items():
                    line[key + "token_nll_rms"] = rms(got, want)
                    head_rms.setdefault(key + "token_nll_rms", []).append(
                        line[key + "token_nll_rms"])
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "LOSS_ATOL": reference.LOSS_ATOL, "seeds": len(args.seed),
        **{name: {"min_abs": min(map(abs, d)), "max_abs": max(map(abs, d))}
           for name, d in distances.items()},
        "TOKEN_NLL_RMS_ATOL": getattr(reference, "TOKEN_NLL_RMS_ATOL", None),
        **{name + "_token_nll_rms": {"min": min(d), "max": max(d)}
           for name, d in token_rms.items()},
        **{key: {"min": min(d), "max": max(d)}
           for key, d in head_rms.items()}}), flush=True)


if __name__ == "__main__":
    main()
