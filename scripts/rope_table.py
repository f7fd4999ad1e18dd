"""What one call of the interleaved rotary costs on the chip, by form:
``q`` ``[2, 8192, 32, 192]`` bfloat16 with lanes 128-191 turning (latent
attention's head in ``joyai-llm-flash.packed-s8192-gas2``), ms a call,
forward alone and forward with the backward, slope-timed
(``scripts/bench_util.py timed_unrolled``: a call's result is the next
call's ``x`` in one straight program; with the backward, the value is its
own cotangent and what comes back is the next ``x``), beside the traffic
floor of one pass over ``q`` (read once, written once, at the chip's
published bytes/s) —

- ``old``: the form ``models/llama.py rope(..., interleaved=True)`` had
  before PR 45, with the caller's slice and join around it (strided slices
  of every other lane, a stack; ``tests/test_rope_forms.py old_form``, the
  tests' plain reference);
- ``product``: the library's — the pair swap as a product with a signed
  permutation, the multiply-add its epilogue (ISSUE 45's form (i));
- ``rolled``: two rotations of the head by one lane and a select on lane
  parity in float32, written here (form (ii)) —

and whether each new form equals the old one to the bit on the chip, value
and gradient.  The table decides which form the library keeps (PERF.md
section 6, PR 45).

    chiprun --chips 1 -- python scripts/rope_table.py \
        [--seed 0] [--steps 4] [--out chiprun_out/<file>.json]

One JSON line per row, then one line with everything.  Refuses the CPU as
``benchmarks/run.py`` does.
"""
import argparse
import json
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

B, S, H, HD, FIRST = 2, 8192, 32, 192, 128
THETA = 32000000.0


def rolled(x, theta, first):
    """Form (ii).  All float32, so autodiff's transpose is the same pass
    turned back, exact; XLA lowers a rotation to two slices and a join."""
    import jax.numpy as jnp
    from jax import lax
    from deepspeed_tpu.models.llama import interleaved_tables
    hd = x.shape[-1]
    c, s = interleaved_tables(jnp.arange(x.shape[1]), theta, hd - first,
                              first)
    xf = x.astype(jnp.float32)
    even = lax.broadcasted_iota(jnp.int32, (hd,), 0) % 2 == 0
    swapped = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * c + swapped * s).astype(x.dtype)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--out")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"rope_table: needs a TPU, jax found platform="
                 f"{device.platform}")
    from deepspeed_tpu.models.llama import rope
    from deepspeed_tpu.telemetry.roofline import hbm_bytes_per_s
    from scripts.bench_util import timed_unrolled
    from tests.test_rope_forms import old_form

    forms = {
        "old": old_form(FIRST, None, THETA),
        "product": lambda x: rope(x, THETA, interleaved=True, first=FIRST),
        "rolled": lambda x: rolled(x, THETA, FIRST),
    }
    k = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 2)
    x = jax.random.normal(k[0], (B, S, H, HD)).astype(jnp.bfloat16)
    g = jax.random.normal(k[1], (B, S, H, HD)).astype(jnp.bfloat16)
    one_pass = 2 * x.size * 2                       # q read, q written
    floor_ms = one_pass / hbm_bytes_per_s(device) * 1e3

    def there_and_back(fn):
        # the value is its own cotangent: one chain, a forward and a
        # backward a step, nothing of either for the compiler to drop
        def step(state):
            out, pull = jax.vjp(fn, state[0])
            return pull(out)
        return step

    def value_and_pullback(fn):
        out, pull = jax.vjp(fn, x)
        return out, pull(g)[0]

    rows, results = [], {}
    for name, fn in forms.items():
        fwd = timed_unrolled(lambda s: (fn(s[0]),), (x,), args.steps) * 1e3
        fwd_bwd = timed_unrolled(there_and_back(fn), (x,), args.steps) * 1e3
        results[name] = jax.jit(partial(value_and_pullback, fn))()
        rows.append({"form": name, "fwd_ms": round(fwd, 3),
                     "fwd_bwd_ms": round(fwd_bwd, 3),
                     "fwd_over_floor": round(fwd / floor_ms, 2),
                     "fwd_bwd_over_floor": round(fwd_bwd / (2 * floor_ms),
                                                 2)})
        print(json.dumps(rows[-1]), flush=True)

    def apart(a, b):
        a, b = (t.astype(jnp.float32) for t in (a, b))
        return {"max_diff": float(jnp.max(jnp.abs(a - b))),
                "share_differing": float(jnp.mean(a != b))}

    out = {"device": device.device_kind,
           "shape": [B, S, H, HD], "lanes_turning": [FIRST, HD],
           "one_pass_bytes": one_pass, "one_pass_floor_ms": round(floor_ms, 3),
           "rows": rows,
           "against_old": {name: {"value": apart(results[name][0],
                                                 results["old"][0]),
                                  "vjp": apart(results[name][1],
                                               results["old"][1])}
                           for name in ("product", "rolled")}}
    print(json.dumps(out))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
