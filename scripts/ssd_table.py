"""What one call of the state-space scan costs on the chip, by lowering:
``ops/state_space.py ssd_scan`` at the Nemotron-H cell's shape (``b`` 2,
``S`` 8192, 64 heads x 64 in 8 groups of state 128, chunk 128, bfloat16,
documents packed) as XLA's chunked form and as the Mosaic kernels
``ds_ssd_fwd`` / ``ds_ssd_bwd`` of ``ops/pallas/state_space.py`` — the
forward alone and the forward with the gradient of all six arguments,
slope-timed (``scripts/bench_util.py timed_chain``; every output is read
whole, so XLA prunes nothing), and how far the two lowerings' values and
gradients are apart.

    chiprun --chips 1 -- python scripts/ssd_table.py \
        [--chunks 8,4,2] [--seed 0] [--out chiprun_out/<file>.json]

``--chunks``: chunks a grid step walks, each timed beside the library's own
choice (``chunks_per_step``).  One JSON line per row — lowering, blocking,
ms a call forward and forward + backward — then one line with the largest
difference of ``y`` and of each gradient between the lowerings, as
max |a - b| / max |b|.  Refuses the CPU as ``benchmarks/run.py`` does.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

B, S, H, P, G, N, CHUNK = 2, 8192, 64, 64, 8, 128, 128
MEAN_DOCUMENT = 1128            # OpenWebText's, as the cell's traffic


def _inputs(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf = jnp.bfloat16
    rng = np.random.default_rng(seed)
    starts = rng.random((B, S)) < 1.0 / MEAN_DOCUMENT
    return dict(
        x=jax.random.normal(k[0], (B, S, H, P)).astype(bf),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, S, H)) - 2.0),
        A=-jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0,
                                      maxval=jnp.log(16.0))),
        Bm=jax.random.normal(k[3], (B, S, G, N)).astype(bf),
        Cm=jax.random.normal(k[4], (B, S, G, N)).astype(bf),
        D=jnp.ones((H,), jnp.float32),
        seg=jnp.asarray(np.cumsum(starts, axis=1).astype(np.int32)),
        w=jax.random.normal(k[5], (B, S, H, P)).astype(bf))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chunks", default="")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--out")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"ssd_table: needs a TPU, jax found platform="
                 f"{jax.devices()[0].platform}")
    from deepspeed_tpu.ops import state_space as ss
    from deepspeed_tpu.ops.pallas import state_space as kernels
    from scripts.bench_util import timed_chain

    t = _inputs(args.seed)
    order = ("x", "dt", "A", "Bm", "Cm", "D")
    own = kernels.chunks_per_step(S // CHUNK, CHUNK, H // G, P, N, 2)

    def scan(interpret):
        # x, B, C and dt with positions along lanes, as XLA lays the
        # layer's arrays out by itself, and y read the same way: for the
        # kernels the transposes cancel, as they do in the model
        to_tokens = lambda a, *shape: jnp.swapaxes(a, 1, 2).reshape(
            (B, S) + shape)
        return lambda x, dt, A, Bm, Cm, D, seg: jnp.swapaxes(ss.ssd_scan(
            to_tokens(x, H, P), to_tokens(dt, H), A, to_tokens(Bm, G, N),
            to_tokens(Cm, G, N), D, seg, chunk=CHUNK,
            interpret=interpret).reshape(B, S, H * P), 1, 2)

    def grads(fn):
        return lambda w, *a: jax.grad(lambda *d: jnp.sum(
            (fn(*d, a[6]) * w).astype(jnp.float32)), argnums=range(6))(*a[:6])

    def nudged(x, *outs):
        # chains call n + 1 to call n, and reads every output whole (XLA
        # prunes what nothing reads; a Mosaic call it cannot)
        total = sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
        return x.at[:1, :8, :128].add((1e-6 * jnp.tanh(total)).astype(x.dtype))

    along_lanes = lambda a: jnp.swapaxes(a.reshape(B, S, -1), 1, 2)
    flat = {k: along_lanes(t[k]) for k in ("x", "w", "Bm", "Cm", "dt")}
    a = tuple(flat.get(k, t[k]) for k in order) + (t["seg"],)

    def time_of(fn):
        fwd = timed_chain(lambda s: (nudged(s[0], fn(*s)),) + s[1:], a,
                          args.steps)
        g = grads(fn)
        both = timed_chain(
            lambda s: (nudged(s[0], *g(s[-1], *s[:-1])),) + s[1:],
            a + (flat["w"],), args.steps)
        return round(fwd * 1e3, 3), round(both * 1e3, 3)

    rows = []

    def row(name, blocking, fn):
        fwd, both = time_of(fn)
        rows.append({"lowering": name, "chunks_per_step": blocking,
                     "fwd_ms": fwd, "fwd_bwd_ms": both,
                     "bwd_ms": round(both - fwd, 3)})
        print(json.dumps(rows[-1]), flush=True)

    row("xla", None, scan(False))
    row("kernel", own.chunks, scan(None))
    rule = kernels.chunks_per_step
    for nc in (int(c) for c in args.chunks.split(",") if c):
        kernels.chunks_per_step = lambda n, C, *r, nc=nc: kernels.Blocking(
            C, nc, H // G, kernels.working_set(nc, C, *r))
        try:
            row("kernel", nc, scan(None))
        except Exception as e:                     # a block Mosaic refuses
            print(json.dumps({"lowering": "kernel", "chunks_per_step": nc,
                              "error": str(e)[-300:]}), flush=True)
        finally:
            kernels.chunks_per_step = rule

    # the two lowerings, value and every gradient
    close = lambda u, v: float(
        jnp.max(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)))
        / jnp.max(jnp.abs(v.astype(jnp.float32))))
    apart = {"y": close(jax.jit(scan(None))(*a), jax.jit(scan(False))(*a))}
    for name, u, v in zip(order, jax.jit(grads(scan(None)))(flat["w"], *a),
                          jax.jit(grads(scan(False)))(flat["w"], *a)):
        apart["d" + name] = close(u, v)
    out = {"device": jax.devices()[0].device_kind, "shape": {
        "b": B, "S": S, "heads": H, "head_dim": P, "groups": G, "state": N,
        "chunk": CHUNK}, "rows": rows, "kernel_against_xla": apart}
    print(json.dumps(out))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
