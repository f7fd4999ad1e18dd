"""The table behind a configuration's ``deployment.init_seed``
(benchmarks/README.md, "Adding things"): one untraced run of a cell a draw
of the weights, each with a traffic seed of its own, and the draw whose
``tokens_per_s_per_chip`` is nearest the median.

    chiprun --chips 1 --timeout 3600 -- python scripts/draw_table.py \
        --workload <cell> --draws <n> <n> ... --seed <first traffic seed>

Every run is the driver's own command in a process of its own (this one
never touches JAX: a chip belongs to one process), with the draw laid over
the configuration's file for that run and the file put back after it.  One
JSON line a draw — the rate, ``correct``, the first loss's distance from
the reference and the token check's reading, which are also the engine's
readings behind ``LOSS_ATOL`` and ``TOKEN_NLL_RMS_ATOL`` — and a last line
with the median and the draw nearest it.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one_run(workload, seed, seconds):
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", "0"], capture_output=True, text=True)
    lines = [json.loads(line) for line in run.stdout.splitlines()
             if line.startswith("{")]
    if run.returncode or not lines:
        return {"exit": run.returncode, "stderr": run.stderr[-2000:]}
    by = {line.get("line"): line for line in lines[:-1]}
    result, ran = lines[-1], by.get("run", {})
    return {"correct": result["correct"],
            **{k: v["value"] for k, v in result["metrics"].items()},
            "loss_vs_reference": ran.get("loss_vs_reference"),
            "fall_by_step_11": (ran["losses"][0] - min(ran["losses"][1:12]))
            if len(ran.get("losses", [])) > 11 else None,
            "token_nll_rms": by.get("token_check", {}).get("token_nll_rms"),
            "step_counts": by.get("step_counts", {}).get("counts"),
            "problems": ran.get("problems")}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--draws", type=int, nargs="+", required=True)
    parser.add_argument("--seed", type=int, required=True,
                        help="the first run's traffic seed; run i takes "
                             "this plus i")
    parser.add_argument("--seconds", type=float, default=10.0)
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    name = next(w["config"] for w in manifest["workloads"]
                if w["name"] == args.workload)
    path = os.path.join(ROOT, next(c["file"] for c in manifest["configs"]
                                   if c["name"] == name))
    with open(path) as f:
        original = f.read()
    rates = {}
    try:
        for i, draw in enumerate(args.draws):
            config = json.loads(original)
            config["deployment"]["init_seed"] = draw
            with open(path, "w") as f:
                json.dump(config, f)
            row = one_run(args.workload, args.seed + i, args.seconds)
            print(json.dumps({"draw": draw, "seed": args.seed + i, **row}),
                  flush=True)
            if row.get("tokens_per_s_per_chip"):
                rates[draw] = row["tokens_per_s_per_chip"]
    finally:
        with open(path, "w") as f:
            f.write(original)
    if rates:
        median = statistics.median(rates.values())
        print(json.dumps({
            "workload": args.workload, "draws": len(rates),
            "median_tokens_per_s_per_chip": median,
            "min": min(rates.values()), "max": max(rates.values()),
            "nearest_draw": min(rates, key=lambda d: abs(rates[d] - median))}),
            flush=True)


if __name__ == "__main__":
    main()
