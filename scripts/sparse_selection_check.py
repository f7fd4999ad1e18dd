"""A top-k is discrete, so a tolerance on the loss says little about it: the
sparse layers' selection and their attend stage held to the plain reference
one by one, on the chip at a cell's own size.

    chiprun --chips 1 -- python scripts/sparse_selection_check.py \
        --workload minicpm-sala.packed-s16384-longdocs --seed <n> ...

For each seed, at freshly initialised parameters (bf16, as the engine holds
them) and that seed's first micro-batch, one JSON line:

* ``agreement``: the share of (token, key/value head) rows whose kept
  blocks — ``ops/sparse_attention.py select_blocks`` on the program's own
  bfloat16 q and k, float32 scores — are exactly the reference's
  (``references/<family>.py selection``: float32 throughout), over the rows
  that have a choice to make (``choosing``: a document of ``dense_len`` or
  more and more causal blocks than ``topk``) and over all rows
  (``agreement_all``); ``blocks_off``: of the rows that differ, the mean
  number of blocks that do.  Held to the reference's
  ``SELECTION_AGREEMENT_MIN``.
* ``same_inputs_agreement``: the same share with the reference's steps 1-4
  (``references/<family>.py select``) run on the PROGRAM's own bfloat16 q
  and k, so that the projections' rounding is out of it and what is left
  is the selection itself — slots and columns against a per-query gather,
  ``lax.top_k`` against a stable sort — and, on the chip, float32 products
  of two shapes, each made of bfloat16 passes.  Held to the reference's
  ``SELECTION_SAME_INPUTS_MIN``; ``--same-inputs-only`` reads it alone,
  which a CPU can do at the cell's size (there it reads 0.99996 where the
  chip reads 0.96: what is left on the chip is its arithmetic).
* the same of the reference's own controls, its matrix products' operands
  rounded to each of ``--dtypes`` (``<dtype>_agreement``): the precision
  the configuration states reads near the program, the next one below far
  under the floor.
* ``attend_rel``: ``selected_attention`` given the REFERENCE's selection
  against the reference's step 5 on the same bfloat16 q, k and v — the
  largest difference over the largest value; no block left out can hide in
  it.  Held to ``--attend-tol``.  The stage runs through the lowering the
  engine takes here (``ops/sparse_attention.py``'s rule: the Mosaic kernels
  on one TPU, the XLA form elsewhere), and ``attend_lowering`` says which.
* the data-dependent counts no static account can hold
  (``ops.sparse_attention.selection_counts``).
* with ``--plant``: what the benchmark's token-by-token check
  (``drivers/train_steps_counted.py``: ``token_nll_rms`` against
  ``TOKEN_NLL_RMS_ATOL``) and its first loss would read of a WRONG
  selection — the program's forward pass with ``select_blocks`` replaced
  by each of :data:`PLANTS`, against the reference's ``token_losses`` at
  the same parameters (``plant_<name>_token_nll_rms``, ``_loss``, and the
  share of choosing rows that then differ from the float32 reference's,
  ``_rows_wrong``), beside the program as it is (``plant_none_*``).  A reading, held to nothing: it says
  whether ``correct`` would notice.

``--rehearse`` runs the cell's toy sizes on the CPU.  Exit 1 if a limit is
passed.
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


def program_qkv(params, micro, config):
    """The first sparse layer's q, k, v as the program's forward pass makes
    them (its dtype, its products): layer 0 of the cell's stack."""
    from deepspeed_tpu.models import minicpm_sala as m
    assert config.kinds[0] == m.SPARSE, config.layer_kinds
    return m.sparse_qkv(m.embedded(params, micro, config),
                        params["layers"][m.layer_name(0)], config)[1:]


def _forced_only(select_blocks, q, k, seg, sel):
    """Keeps the first blocks and the nearest and chooses nothing."""
    from deepspeed_tpu.ops.sparse_attention import _geometry
    blocks, _ = select_blocks(q, k, seg, sel)
    own = (_geometry(seg, seg.shape[1], sel)["pos"]
           // sel.block_size)[:, None, :, None]
    forced = (blocks >= 0) & ((blocks < sel.init_blocks)
                              | (blocks > own - sel.local_blocks))
    blocks = jnp.sort(jnp.where(forced, blocks, jnp.iinfo(jnp.int32).max),
                      axis=-1)
    blocks = jnp.where(blocks == jnp.iinfo(jnp.int32).max, -1, blocks)
    return blocks, jnp.sum(blocks >= 0, axis=-1, dtype=jnp.int32)


def _some_rows(share):
    """The lowest-scoring blocks in one row of every ``share``."""
    def plant(select_blocks, q, k, seg, sel):
        right, count = select_blocks(q, k, seg, sel)
        wrong, _ = select_blocks(-q, k, seg, sel)
        rows = (jnp.arange(q.shape[1]) % share == 0)[None, None, :, None]
        return jnp.where(rows, wrong, right), count
    return plant


#: wrong selections to plant, each ``(select_blocks, q, k, seg, sel) ->
#: (blocks, count)``: the sign of the scores turned (every row that
#: chooses keeps its LOWEST blocks beside the forced ones), the same in
#: one row of five, and nothing chosen at all (the forced blocks alone)
PLANTS = {"negated": _some_rows(1), "negated_fifth": _some_rows(5),
          "forced_only": _forced_only}


def planted_readings(model, reference, params, micro, sizes, chunk, want,
                     choosing):
    """``{plant_<name>_<reading>: value}`` for the program as it is
    (``none``) and under each of :data:`PLANTS`."""
    from deepspeed_tpu.models import minicpm_sala as m
    from drivers.train_steps_counted import rms, token_nll
    ref_nll, scored = reference.token_losses(params, micro, sizes, chunk)
    real, out = m.select_blocks, {}
    for name, plant in {"none": None, **PLANTS}.items():
        seen = []

        def planted(q, k, seg, sel, plant=plant):
            blocks, count = real(q, k, seg, sel) if plant is None \
                else plant(real, q, k, seg, sel)
            jax.debug.callback(lambda b: seen.append(np.asarray(b)), blocks)
            return blocks, count

        m.select_blocks = planted
        jax.clear_caches()     # no layer body traced with another selection
        try:
            nll = token_nll(model, params, micro)
            jax.effects_barrier()
        finally:
            m.select_blocks = real
        wrong = ~(seen[0] == want).all(-1)
        out.update({
            f"plant_{name}_token_nll_rms": rms(nll, ref_nll, scored),
            f"plant_{name}_loss": float(nll[scored].mean()
                                        - ref_nll[scored].mean()),
            f"plant_{name}_rows_wrong": float(wrong[choosing].mean())
            if choosing.any() else 0.0})
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--dtypes", nargs="*",
                        default=["bfloat16", "float8_e4m3fn"])
    parser.add_argument("--attend-tol", type=float, default=2e-2)
    parser.add_argument("--plant", action="store_true",
                        help="also read what the token-by-token check "
                             "would make of a wrong selection")
    parser.add_argument("--same-inputs-only", action="store_true",
                        help="that share alone: cheap enough for a CPU at "
                             "a cell's own size (JAX_PLATFORMS=cpu), whose "
                             "float32 products are float32")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    if args.rehearse:
        sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
        from rehearse import toy
        cell, config, traffic = toy(Manifest(ROOT), args.workload)
    else:
        cell, config, traffic = Manifest(ROOT).cell(args.workload)
    from deepspeed_tpu.ops.sparse_attention import (select_blocks,
                                                    selected_attention,
                                                    selection_counts)
    from deepspeed_tpu.telemetry import tracing
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    reference = importlib.import_module("references." + config["reference"])
    model = build_model(config)
    cfg, sel = model.config, model.config.selection
    sizes = {**config["model"], "n_params": model.meta["n_params"]}
    init = jax.jit(lambda key: jax.tree.map(
        lambda p: p.astype(jnp.bfloat16), model.init(key)))

    @jax.jit
    def program(params, micro):
        q, k, v = program_qkv(params, micro, cfg)
        blocks, count = select_blocks(q, k, micro["segment_ids"], sel)
        lengths = jax.vmap(lambda seg: reference.documents(seg)[1])(
            micro["segment_ids"])
        return q, k, v, blocks, selection_counts(
            blocks, count, micro["segment_ids"], sel), count, lengths

    @jax.jit
    def attend_both(q, k, v, blocks, seg):
        got = selected_attention(
            q, k, v, blocks, seg, sel, query_chunk=cfg.attend_query_chunk,
            key_spans=cfg.attend_key_spans).astype(jnp.float32)
        f32 = lambda t: t.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = jax.lax.map(lambda a: reference.attend(
                f32(a[0]), f32(a[1]), f32(a[2]), a[3], a[4], sizes),
                (q, k, v, blocks, seg))
        return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))

    @jax.jit
    def reference_on_the_programs(q, k, seg):
        f32 = lambda t: t.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(lambda a: reference.select(
                f32(a[0]), f32(a[1]), a[2], sizes)[0], (q, k, seg))

    chunk = max(1, config["checks"]["reference_chunk_tokens_per_chip"]
                // traffic["seq_len"])
    ok, lowering = True, None
    for seed in args.seed:
        params = init(jax.random.PRNGKey(seed))
        stream = datagen.BatchStream(traffic, sizes["vocab_size"],
                                     traffic["micro_batch_per_chip"], seed)
        first = stream.next()
        stream.close()
        micro = {k: np.asarray(v)[0] for k, v in first.items()}
        q, k, v, got, counts, count, lengths = program(
            params, {k: jnp.asarray(v) for k, v in micro.items()})
        got = np.asarray(got)
        # rows that choose: more causal blocks than topk, in a document that
        # selects
        choosing = (np.asarray(count) >= sel.topk) \
            & (np.asarray(lengths) >= sel.dense_len)[:, None]

        def shares(blocks, want):
            same = (blocks == want).all(-1)
            off = (~(blocks[..., :, None] == want[..., None, :]).any(-1)
                   & (blocks >= 0)).sum(-1)
            return {"agreement": float(same[choosing].mean())
                    if choosing.any() else 1.0,
                    "agreement_all": float(same.mean()),
                    "blocks_off": float(off[~same].mean())
                    if (~same).any() else 0.0}

        same_inputs = shares(got, np.asarray(reference_on_the_programs(
            q, k, jnp.asarray(micro["segment_ids"]))))
        line = {"workload": args.workload, "seed": seed,
                "device": jax.devices()[0].device_kind,
                "choosing": float(choosing.mean()),
                "same_inputs_agreement": same_inputs["agreement"],
                "same_inputs_blocks_off": same_inputs["blocks_off"],
                "SELECTION_SAME_INPUTS_MIN":
                    reference.SELECTION_SAME_INPUTS_MIN}
        line["ok"] = line["same_inputs_agreement"] \
            >= reference.SELECTION_SAME_INPUTS_MIN
        if not args.same_inputs_only:
            want = reference.selection(params, micro, sizes)
            line.update(shares(got, want))
            line.update({name: float(value)
                         for name, value in counts.items()})
            for name in args.dtypes:
                control = reference.selection(
                    params, micro, sizes, matmul_dtype=getattr(jnp, name))
                line.update({f"{name}_{key}": value for key, value
                             in shares(control, want).items()})
            with tracing.step_account("check/attend"):
                line["attend_rel"] = float(attend_both(
                    q, k, v, jnp.asarray(want),
                    jnp.asarray(micro["segment_ids"])))
            # the row is written where the call is traced: the first seed
            rows = tracing.sparse_attention_calls("check/attend")
            if rows:
                lowering = {key: rows[0][key] for key in (
                    "lowering", "blocks", "tiles",
                    "sparse/visited_keys_per_query") if key in rows[0]}
            line["attend_lowering"] = lowering
            line["SELECTION_AGREEMENT_MIN"] = \
                reference.SELECTION_AGREEMENT_MIN
            line["ok"] = (line["ok"] and line["agreement"]
                          >= reference.SELECTION_AGREEMENT_MIN
                          and line["attend_rel"] <= args.attend_tol)
            if args.plant:
                line.update(planted_readings(
                    model, reference, params, micro, sizes, chunk, want,
                    choosing))
        ok = ok and line["ok"]
        print(json.dumps(line), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
