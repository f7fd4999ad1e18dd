"""One run of one cell of BENCHMARK.json:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--seed`` is the traffic's seed: the program receives only the batches it
makes, and the weights are the configuration's own draw (its
``deployment.init_seed``).  The last line of standard output is the result
object; earlier lines are counts worth keeping.  Any failure to run — no
TPU, fewer chips than the cell asks for, a device_kind that peaks.json
does not know, a checkout without the program — is a non-zero exit and no
result line.  A run that finishes with a failed check prints
``"correct": false``.

Nothing here names a cell, a configuration, a traffic mix or a metric:
the workload entry names its ``config`` and ``traffic`` (and, by its own
name, ``cells/<workload>.json`` where the pair has checks of its own);
the traffic file names its ``driver`` (drivers/<driver>.py); a per-layer
metric's file (layer_metrics/<metric>.json) names its reader
(layer_metrics/readers/<reader>.py).  See README.md.
"""
import time

T_PROCESS = time.perf_counter()

import argparse
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()

    # the TPU runtime comes up first, before the program is imported, so
    # that its seconds (5.9-16.9 from run to run on one machine, PERF.md
    # PR 23) are the same span in every PR and no import can move them
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise SystemExit("benchmark: the jax backend was initialised before "
                         "run.py asked for the devices")
    from harness.manifest import Manifest
    manifest = Manifest(ROOT)
    cell, config, traffic = manifest.cell(args.workload)
    from harness.device import require_device
    t_before = time.perf_counter()
    devices, peaks = require_device(cell["chips"])
    device_init_s = time.perf_counter() - t_before

    # the program under test and its compile cache (JAX_COMPILATION_CACHE_DIR
    # where set, else <checkout>/.jax_cache: a fixed path inside the checkout)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    program_import_s = time.perf_counter() - t_before - device_init_s

    wanted = manifest.metrics("per_layer" if args.trace else "end_to_end",
                              cell["name"])
    driver = importlib.import_module("drivers." + traffic["driver"])
    result = driver.run_cell(
        cell["name"], config, traffic,
        {m["name"]: manifest.layer_metric(m["name"]) for m in wanted}
        if args.trace else {},
        args.seed, args.seconds, bool(args.trace), devices, peaks,
        # setup_s is everything from process start to the first timed
        # dispatch but the runtime's own start-up, which the run line
        # carries as device_init_s
        t_origin=T_PROCESS + device_init_s,
        work_dir=os.path.join(ROOT, ".bench_work", cell["name"]),
        phases={"before_devices_s": t_before - T_PROCESS,
                "device_init_s": device_init_s,
                "program_import_s": program_import_s})
    # the driver's values under the manifest's names and units; a per-layer
    # metric whose reader found nothing is left out
    result["metrics"] = {
        m["name"]: {"value": result["metrics"][m["name"]], "unit": m["unit"]}
        for m in wanted if m["name"] in result["metrics"]}
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
