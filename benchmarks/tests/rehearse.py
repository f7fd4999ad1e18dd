"""A cell at toy size on the CPU through the driver's own functions: the
same files, with the sizes handed in (as chip_smoke's phases take them)."""
import copy
import importlib
import os
import time

import jax

from harness.manifest import Manifest

#: the sizes a rehearsal takes where the configuration's file gives no
#: ``rehearsal`` of its own (``model``: sizes laid over its ``model`` block;
#: ``builder_kwargs``: laid over the builder's kwargs): a small GPT-2
_SMALL_GPT2 = {"num_layers": 2, "d_model": 64, "num_heads": 2,
               "head_dim": 32, "d_mlp": 256, "vocab_size": 512,
               "max_seq_len": 128}
TOY = {"model": _SMALL_GPT2,
       "builder_kwargs": {k: v for k, v in _SMALL_GPT2.items()
                          if k not in ("head_dim", "d_mlp")}}
#: peaks for arithmetic only: nothing a rehearsal computes is a device number
TOY_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "ici_bits_per_s": 1e11}


def toy(manifest, cell_name, seq_len=64, micro=2):
    cell, config, traffic = copy.deepcopy(manifest.cell(cell_name))
    small = config.get("rehearsal", TOY)
    config["model"] = {**config["model"], **small["model"]}
    config["model"].pop("n_params", None)   # the driver counts the toy's
    config["builder"]["kwargs"].update(small["builder_kwargs"])
    traffic.update(seq_len=seq_len, micro_batch_per_chip=micro)
    traffic["documents"].update(median=20, min=4)
    # the CPU runs no Mosaic kernel; a toy model in the first steps of the
    # lr warm-up moves less than one batch differs from the next, so the
    # rehearsal holds it only to reaching the step the check reads
    config["checks"].update(
        require_kernel=False, require_kernels=[], learn_check={
            "step_index": config["checks"]["learn_check"]["step_index"],
            "min_drop": -1.0})
    return cell, config, traffic


def rehearse(cell_name, root=None, seconds=1.0, trace=False, tmp=".",
             checks=None, seed=1):
    """``checks``: keys of the configuration's ``checks`` to hold the
    rehearsal to after all (``toy`` switches the kernel checks off);
    ``seed``: the run's ``--seed``, the traffic's."""
    manifest = Manifest(root) if root else Manifest()
    cell, config, traffic = toy(manifest, cell_name)
    config["checks"].update(checks or {})
    driver = importlib.import_module("drivers." + traffic["driver"])
    metrics = {m["name"]: manifest.layer_metric(m["name"])
               for m in manifest.metrics("per_layer", cell_name)} \
        if trace else {}
    return driver.run_cell(
        cell_name, config, traffic, metrics, seed=seed, seconds=seconds,
        trace=trace, devices=jax.devices()[:cell["chips"]], peaks=TOY_PEAKS,
        t_origin=time.perf_counter(), work_dir=os.path.join(tmp, "work"))
