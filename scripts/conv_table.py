"""What one call of the short causal convolution costs on the chip, by
lowering: ``ops/linear_attention.py causal_conv`` with its document reset,
bias and ``silu`` at the two hybrid cells' shapes — Qwen3-Next's
``[2, 8192, 8192]`` with positions down sublanes, Nemotron-H's
``[2, 8192, 6144]`` with positions along lanes (the array handed over as
``[2, 6144, 8192]``, as XLA lays that layer out by itself, so that the
relabelings around the call cancel as they do in the model), bfloat16,
documents packed — as XLA's shifted copies with autodiff's backward and as
the Mosaic kernels ``ds_conv_fwd`` / ``ds_conv_bwd`` of
``ops/pallas/causal_conv.py``: the forward alone and the forward with the
backward alone (the gradients of ``x``, ``w`` and the bias from ``x`` and
``dy``; what of the forward it needs it computes again, as under full
remat), slope-timed (``scripts/bench_util.py timed_unrolled``: a call's
result is the next call's ``x`` in one straight program, so nothing is
read, written or copied beside the calls), ms a call
and GB/s of the rows a call has to move (forward: ``x`` in, ``y`` out;
backward: ``x`` and ``dy`` in, ``dx`` out), and how far the two lowerings'
values and gradients are apart.

    chiprun --chips 1 -- python scripts/conv_table.py \
        [--slabs 128,256] [--seed 0] [--out chiprun_out/<file>.json]

``--slabs``: channels a grid step takes, each timed beside the library's
own choice (``slab_width``).  One JSON line per row, then one line with
the largest differences as max |a - b| / max |b|.  Refuses the CPU as
``benchmarks/run.py`` does.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

B, S, K = 2, 8192, 4
SHAPES = {"qwen3-next": (8192, "sublanes", False),       # C, positions, bias
          "nemotron-h": (6144, "lanes", True)}
MEAN_DOCUMENT = 1128            # OpenWebText's, as the cells' traffic


def _inputs(seed, C, positions):
    import jax
    import jax.numpy as jnp
    import numpy as np
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    rng = np.random.default_rng(seed)
    starts = rng.random((B, S)) < 1.0 / MEAN_DOCUMENT
    shape = (B, S, C) if positions == "sublanes" else (B, C, S)
    return dict(
        x=jax.random.normal(k[0], shape).astype(jnp.bfloat16),
        dy=jax.random.normal(k[1], shape).astype(jnp.bfloat16),
        w=(jax.random.normal(k[2], (K, C)) / 2).astype(jnp.bfloat16),
        b=(jax.random.normal(k[3], (C,)) / 2).astype(jnp.bfloat16),
        seg=jnp.asarray(np.cumsum(starts, axis=1).astype(np.int32)))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--slabs", default="")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--out")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"conv_table: needs a TPU, jax found platform="
                 f"{jax.devices()[0].platform}")
    from deepspeed_tpu.ops.linear_attention import causal_conv
    from deepspeed_tpu.ops.pallas import causal_conv as kernels
    from scripts.bench_util import timed_unrolled

    rows, apart = [], {}
    for name, (C, positions, with_bias) in SHAPES.items():
        t = _inputs(args.seed, C, positions)
        turn = (lambda a: a) if positions == "sublanes" \
            else (lambda a: jnp.swapaxes(a, 1, 2))

        def conv(interpret):
            return lambda x, w, b: turn(causal_conv(
                turn(x), w, t["seg"], b if with_bias else None, "silu",
                positions, interpret=interpret))

        def backward(fn):
            def step(state):
                x, dy = state
                dx, dw, db = jax.vjp(fn, x, t["w"], t["b"])[1](dy)
                # the small gradients are read; dx is the next call's x
                small = jnp.sum(dw.astype(jnp.float32)) + jnp.sum(
                    db.astype(jnp.float32))
                return dx, dy.at[:1, :1, :1].add(
                    (1e-9 * jnp.tanh(small)).astype(dy.dtype))
            return step

        def time_of(fn):
            fwd = timed_unrolled(lambda s: (fn(s[0], t["w"], t["b"]),),
                                 (t["x"],), args.steps)
            bwd = timed_unrolled(backward(fn), (t["x"], t["dy"]), args.steps)
            return fwd * 1e3, bwd * 1e3

        moved = B * S * C * 2                       # bytes of one array

        def row(lowering, slab, fn):
            fwd, bwd = time_of(fn)
            rows.append({
                "shape": name, "positions": positions, "lowering": lowering,
                "slab": slab, "fwd_ms": round(fwd, 3), "bwd_ms": round(bwd, 3),
                "fwd_gbps": round(2 * moved / fwd / 1e6, 1),
                "bwd_gbps": round(3 * moved / bwd / 1e6, 1)})
            print(json.dumps(rows[-1]), flush=True)

        own = kernels.slab_width(S, C, 2, positions)
        row("xla", None, conv(False))
        row("kernel", own.slab, conv(None))
        rule = kernels.slab_width
        for slab in (int(c) for c in args.slabs.split(",") if c):
            kernels.slab_width = lambda S_, C_, size, pos, first=0, \
                slab=slab: own._replace(
                    slab=slab, vmem_bytes=kernels.working_set(
                        S_, slab, size, pos))
            try:
                row("kernel", slab, conv(None))
            except Exception as e:                 # a slab Mosaic refuses
                print(json.dumps({"shape": name, "lowering": "kernel",
                                  "slab": slab, "error": str(e)[-300:]}),
                      flush=True)
            finally:
                kernels.slab_width = rule

        close = lambda u, v: float(
            jnp.max(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)))
            / jnp.max(jnp.abs(v.astype(jnp.float32))))
        a = (t["x"], t["w"], t["b"])
        vjp_of = lambda fn: jax.jit(
            lambda *p: (fn(*p),) + jax.vjp(fn, *p)[1](t["dy"]))(*a)
        names = ("y", "dx", "dw", "db")[:4 if with_bias else 3]
        apart[name] = {n: close(u, v) for n, u, v in zip(
            names, vjp_of(conv(None)), vjp_of(conv(False)))}
    out = {"device": jax.devices()[0].device_kind,
           "shape": {"batch": B, "positions": S, "taps": K}, "rows": rows,
           "kernel_against_xla": apart}
    print(json.dumps(out))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
