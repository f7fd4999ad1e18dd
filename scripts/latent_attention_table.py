"""What latent attention costs on the chip around its kernels, instruction
by instruction: one traced run of a benchmark cell (``benchmarks/run.py
--trace 1``, unchanged), then the self time of every instruction of the
compiled step under ``ds.block/attn/``, joined through
``get_program_map("train/step")`` and summed by phase, scope and result
shape (an instruction has another name in every layer-call; its scope and
shape are what a parent and a change share).

    chiprun --chips 1 -- python scripts/latent_attention_table.py --seed <n> \
        [--workload xing4.0-29b-a4b.packed-s4096-pretrain] \
        [--root <checkout>] [--out chiprun_out/<file>.json]

``--root`` as in ``scripts/moe_movement_table.py``, whose run and join
this shares.  Standard output: the cell's own lines, every row of half a
millisecond a step or more outside the kernels, then one JSON line: ms per
optimizer step by scope (``q_latent``, ``kv_latent``, ``rope``, ``scores``,
``out_proj``, the rest), the kernels' sum and the whole.

``--bits`` runs no cell: at the JoyAI cell's widths, ``[2, 8192, 2048]``
packed, bfloat16, it hands ``models/joyai.py latent_attention`` and the
assembled form it had before PR 57 (``tests/test_joyai.py``'s oracle) the
same layer and counts the elements of ``k`` and of ``v`` that differ, then
compares the layer's loss and gradients.  One JSON line.

Refuses the CPU as ``benchmarks/run.py`` does.
"""
import json
import os
import re
import sys
from collections import defaultdict

from moe_movement_table import cell_arguments, scope_rows, traced_cell

SCOPE = re.compile(r"/attn/")
#: ``--bits``: the JoyAI cell's micro-batch
BATCH, SEQ, DOCUMENT = 2, 8192, 1024


def table(args):
    dev, program_map, tr, step_phase, _ = traced_cell(args)
    steps, rows = scope_rows(dev, program_map, tr, step_phase, SCOPE,
                             "/attn/")
    summed = defaultdict(lambda: [0.0, 0.0])
    for r in rows:
        key = (r["phase"], r["op"], r["shape"].split("{")[0],
               r["kernel"] or "")
        summed[key][0] += r["ms_per_step"]
        summed[key][1] += r["calls_per_step"]
    listed = [{"phase": k[0], "scope": k[1], "shape": k[2], "kernel": k[3],
               "ms_per_step": ms, "calls_per_step": calls}
              for k, (ms, calls) in sorted(summed.items(),
                                           key=lambda kv: -kv[1][0])]
    by_scope = defaultdict(float)
    for r in listed:
        by_scope[r["scope"].split("/", 1)[0]] += r["ms_per_step"]
        if not r["kernel"] and r["ms_per_step"] >= 0.5:
            print(f'{r["phase"]:9s} {r["ms_per_step"]:8.2f} ms '
                  f'{r["calls_per_step"]:5.0f}x  {r["shape"]:30s} '
                  f'{r["scope"][-80:]}')
    out = {"steps_traced": steps,
           "by_scope": {k: round(v, 3) for k, v in sorted(by_scope.items())},
           "kernels": round(sum(r["ms_per_step"] for r in listed
                                if r["kernel"]), 3),
           "all": round(sum(r["ms_per_step"] for r in listed), 3)}
    print(json.dumps(out))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({**out, "rows": listed}, f, indent=1)


def bits():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.join(root, "benchmarks"), root]
    from harness.device import require_device
    require_device(1)
    from functools import partial
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import joyai
    from tests.test_joyai import (_assembled_latent_attention,
                                  handed_to_the_kernels)
    config = joyai.JoyAIConfig(num_layers=5)
    dt = jnp.bfloat16
    layer = {name: w.astype(dt) if w.ndim == 2 else
             w + 0.1 * jax.random.normal(jax.random.PRNGKey(i), w.shape)
             for i, (name, w) in enumerate(joyai._attn_params(
                 config, jax.random.PRNGKey(7)).items())}
    x = jax.random.normal(jax.random.PRNGKey(8),
                          (BATCH, SEQ, config.d_model)).astype(dt)
    seg = jnp.broadcast_to(jnp.arange(SEQ, dtype=jnp.int32) // DOCUMENT,
                           (BATCH, SEQ))
    forms = (joyai.latent_attention, _assembled_latent_attention)

    def loss(fn, layer, x):
        return jnp.sum(fn(x, layer, config, seg).astype(jnp.float32) ** 2)

    (_, k, v), (_, want_k, want_v) = (
        jax.jit(partial(handed_to_the_kernels, fn, config=config,
                        segment_ids=seg))(x, layer) for fn in forms)
    got, want = (jax.jit(jax.value_and_grad(partial(loss, fn), (0, 1)))(
        layer, x) for fn in forms)
    f32 = lambda a: a.astype(jnp.float32)
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "k": list(k.shape),
        "k_elements_that_differ": int(jnp.sum(k != want_k)),
        "v_elements_that_differ": int(jnp.sum(v != want_v)),
        "loss": [float(got[0]), float(want[0])],
        "gradients_max_abs_difference_over_max_abs": jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(f32(a) - f32(b)))
                               / jnp.max(jnp.abs(f32(b)))),
            list(got[1]), list(want[1]))}))


if __name__ == "__main__":
    if "--bits" in sys.argv[1:]:
        bits()
    else:
        table(cell_arguments(__doc__, "joyai-llm-flash.packed-s8192-gas2"))
