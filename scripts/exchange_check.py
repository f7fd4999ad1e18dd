"""The expert-parallel exchange alone, on the chips of one host: one expert
layer at a cell's widths through ``moe/layer.py _exchanged_grouped_moe``
(on a TPU: ``lax.ragged_all_to_all`` out and back, the grouped kernels and
``ds_rowsum`` inside the manual region — the path no CPU test runs, whose
all-to-alls there are ``lax.all_to_all`` of whole segments) against the
layer written plainly in float32 on the same devices: every token through
every expert, weight 0 where it was not chosen.  Output, ``dx``, every
``dw``, the router's gradient and the gates' own (``gates``: the gradient
by a ``[tokens, experts]`` array of zeros added to the chosen weights —
in the exchanged layer what the experts' chips form from ``dh`` and the
activation and send home through the narrow exchange's transpose) as
``|a - b|_2 / |b|_2``, beside the plain layer with its products' operands
rounded to bf16 against itself (the noise a bf16 program cannot be under).
A cotangent that came back to the wrong place, or an expert's gradient
summed over chips, reads near 1.

    chiprun --chips 4 -- python scripts/exchange_check.py --seed <n>

One JSON line per seed; exit 1 where a leaf reads more than ``--limit``
times its control (and more than 2%).  ``layer_ms`` is the host clock's
median over ``--timed`` calls of the compiled layer, forward and backward
(a layer alone, its input on the chips: no step's number).
"""
import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.comm.mesh import MeshTopology, set_topology   # noqa: E402
from deepspeed_tpu.moe import layer as moe_layer_module          # noqa: E402
from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,  # noqa: E402
                                     moe_layer, moe_logical_specs)


def plain_layer(params, x, nudge, config, matmul_dtype=None):
    """[B, S, D] -> [B, S, D], float32: softmax over all experts, the
    ``top_k`` largest renormalised (plus ``nudge`` [B, S, E] where
    chosen), every token through every expert."""
    f32 = lambda a: a.astype(jnp.float32)
    mm = jnp.matmul if matmul_dtype is None else (
        lambda a, b: jnp.matmul(f32(a.astype(matmul_dtype)),
                                f32(b.astype(matmul_dtype))))
    m = f32(x).reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(mm(m, f32(params["router"])), axis=-1)
    _, chosen = jax.lax.top_k(probs, config.top_k)
    sent = jax.nn.one_hot(chosen, config.num_experts).sum(1)
    picked = probs * sent
    weights = picked / picked.sum(-1, keepdims=True) \
        + sent * f32(nudge).reshape(sent.shape)

    def one_expert(out, expert):
        w_gate, w_in, w_out, weight = expert
        y = mm(jax.nn.silu(mm(m, f32(w_gate))) * mm(m, f32(w_in)),
               f32(w_out))
        return out + weight[:, None] * y, None

    out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(m), (
        params["w_gate"], params["w_in"], params["w_out"], weights.T))
    return out.reshape(x.shape)


def exchanged_layer(params, x, nudge, config):
    """The library's layer with ``nudge`` added to the gates its router
    chose, between the routing and the dispatch."""
    route = moe_layer_module._route

    def nudged(*args, **kwargs):
        routing = route(*args, **kwargs)
        return routing._replace(gate_weights=routing.gate_weights
                                + jnp.take_along_axis(
                                    nudge.reshape(-1, nudge.shape[-1]),
                                    routing.expert_idx, axis=1))

    moe_layer_module._route = nudged
    try:
        return moe_layer(params, x, config, train=True)[0]
    finally:
        moe_layer_module._route = route


def weighted(layer):
    def loss(params, x, nudge):
        out = layer(params, x, nudge)
        w = jnp.cos(jnp.arange(out.shape[-1], dtype=jnp.float32))
        return jnp.sum(out.astype(jnp.float32) * w) / out.shape[0], out
    return loss


def l2(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--tokens", type=int, default=2048,
                        help="tokens a chip")
    parser.add_argument("--sizes", type=int, nargs=4,
                        default=[2304, 896, 64, 8],
                        metavar=("D", "F", "E", "K"))
    parser.add_argument("--factor", type=int, default=4)
    parser.add_argument("--limit", type=float, default=3.0)
    parser.add_argument("--timed", type=int, default=5)
    args = parser.parse_args()
    D, F, E, K = args.sizes
    devices = jax.devices()[:4]
    topo = MeshTopology(devices=devices, expert_parallel_size=len(devices))
    set_topology(topo)
    config = MoEConfig(d_model=D, d_ff=F, num_experts=E, top_k=K,
                       router="softmax", activation="silu_glu",
                       norm_topk_prob=True, dispatch_mode="grouped",
                       aux_loss_coef=0.0, held_rows_factor=args.factor)
    rows = NamedSharding(topo.mesh, P(tuple(topo.data_parallel_axes)))
    specs = moe_logical_specs(config)
    bad = False
    for seed in args.seed:
        keys = jax.random.split(jax.random.PRNGKey(seed))
        params = jax.tree.map(
            lambda a, s: jax.device_put(a.astype(jnp.bfloat16),
                                        NamedSharding(topo.mesh, s)),
            init_moe_params(config, keys[0]), specs)
        x = jax.device_put(jax.random.normal(
            keys[1], (len(devices), args.tokens, D), jnp.bfloat16), rows)

        nudge = jax.device_put(jnp.zeros(x.shape[:2] + (E,), jnp.float32),
                               rows)
        grad = lambda layer: jax.jit(jax.value_and_grad(
            weighted(layer), argnums=(0, 1, 2), has_aux=True))
        with jax.default_matmul_precision("highest"):
            (_, want), (dw_want, dx_want, dg_want) = grad(
                lambda p, x, n: plain_layer(p, x, n, config))(
                    params, x, nudge)
            (_, low), (dw_low, dx_low, dg_low) = grad(
                lambda p, x, n: plain_layer(p, x, n, config, jnp.bfloat16))(
                    params, x, nudge)
        compiled = grad(lambda p, x, n: exchanged_layer(
            p, x, n, config)).lower(params, x, nudge).compile()
        (_, got), (dw_got, dx_got, dg_got) = compiled(params, x, nudge)
        text = compiled.as_text()
        took = []
        for _ in range(args.timed):
            start = time.perf_counter()
            jax.block_until_ready(compiled(params, x, nudge))
            took.append(1e3 * (time.perf_counter() - start))
        line = {"seed": seed, "device": devices[0].device_kind,
                "chips": len(devices), "tokens_per_chip": args.tokens,
                "sizes": [D, F, E, K],
                "ragged_all_to_all": text.count(" ragged-all-to-all("),
                "all_to_all": text.count(" all-to-all("),
                "mosaic_calls": text.count("tpu_custom_call"),
                "layer_ms": statistics.median(took) if took else None,
                "leaves": {}}
        pairs = {"out": (got, low, want), "dx": (dx_got, dx_low, dx_want),
                 "gates": (dg_got, dg_low, dg_want),
                 **{name: (dw_got[name], dw_low[name], dw_want[name])
                    for name in sorted(dw_want)}}
        for name, (a, c, b) in pairs.items():
            mine, control = l2(a, b), l2(c, b)
            line["leaves"][name] = {"exchange": mine, "bf16_control": control}
            bad |= mine > max(args.limit * control, 0.02)
        print(json.dumps(line), flush=True)
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
