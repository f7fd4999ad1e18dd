"""The interleaved rotary (``models/llama.py rope(..., interleaved=True)``:
the pair swap as a product with a signed permutation, fused with the
multiply-add, on whole heads) against the form it replaced, written out
here with its strided slices and its stack: the value and the ``vjp``.

**Bit for bit, operation by operation.**  ``x1 cos - x2 sin`` and ``x1 cos +
(-x2) sin`` are the same float32 numbers, as are ``x1 sin + x2 cos`` and
``x2 cos + x1 sin``, and a product with one non-zero a column is exact: run
one primitive at a time (``jax.disable_jit``) the two forms agree to the
bit, value and gradient, in both dtypes, which is the arithmetic the chip
does (``scripts/rope_table.py`` compares them there under ``jit``).
**Under ``jit`` on the CPU, to one float32 ulp**, and the reason is not the
form: LLVM contracts one product of each sum into a fused multiply-add
(XLA's CPU compiler always allows it), ``fma(x1, cos, -(x2 sin))`` and
``fma(x1, sin, x2 cos)`` in the old form, ``fma(x, C, swap(x) Sn)`` in the
new one — so on the odd lanes the other product is the one left unrounded.
The old form under ``jit`` differs from *itself* run by primitive in a
fifth of its elements (rebuilt in float64, each form under ``jit`` is its
own contraction to the bit: PERF.md section 6, PR 45); which products the
compiler takes follows its flags, so the test below asks only whether it
contracts at all.

The split-half branch (every cell but JoyAI's) is held to the jaxpr it had
at the parent commit (tests/data/rope_split_half_jaxpr.json)."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import rope

THETA = 10000.0
HERE = os.path.dirname(os.path.abspath(__file__))
#: name -> (shape, first lane that turns)
SHAPES = {"toy_2x64x4x8": ((2, 64, 4, 8), 0),
          "heads_1x256x32x64": ((1, 256, 32, 64), 0),
          "lanes_128_191_of_192": ((1, 256, 32, 192), 128),
          "decode_3x1x4x8": ((3, 1, 4, 8), 0)}
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def _positions(kind, B, S):
    if kind == "none":
        return None
    if kind == "s":
        return jnp.arange(S) + 3
    return (jnp.arange(B * S).reshape(B, S) * 7) % 50       # "b_s"


def old_rope(x, theta, positions=None):
    """``rope(..., interleaved=True)`` as it stood before PR 45."""
    B, S, H, hd = x.shape
    if positions is None:
        positions = jnp.arange(S)
    freqs = theta ** (-jnp.arange(0, hd // 2) / (hd // 2))
    if positions.ndim == 1:
        angles = positions[:, None] * freqs[None, :]
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
    else:
        angles = positions[:, :, None] * freqs[None, None, :]
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    r1, r2 = x1 * cos - x2 * sin, x1 * sin + x2 * cos
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


def old_form(first, positions, theta=THETA):
    """The caller's slice and join around it (``models/joyai.py``);
    ``scripts/rope_table.py`` times this on the chip."""
    def fn(x):
        turned = old_rope(x[..., first:], theta, positions)
        return jnp.concatenate([x[..., :first], turned], axis=-1) \
            if first else turned
    return fn


def new_form(first, positions):
    return lambda x: rope(x, THETA, positions, interleaved=True, first=first)


def _case(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(2), shape).astype(dtype)
    return x, g


def _value_and_pullback(fn, x, g):
    out, pull = jax.vjp(fn, x)
    return out, pull(g)[0]


def _bits(a):
    return np.asarray(a).view(np.uint16 if a.dtype == jnp.bfloat16
                              else np.uint32)


@pytest.mark.parametrize("positions", ["none", "s", "b_s"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_value_and_vjp_equal_the_old_forms_to_the_bit(shape, dtype,
                                                      positions):
    (B, S, H, hd), first = SHAPES[shape]
    x, g = _case((B, S, H, hd), DTYPES[dtype])
    pos = _positions(positions, B, S)
    with jax.disable_jit():
        want, dwant = _value_and_pullback(old_form(first, pos), x, g)
        got, dgot = _value_and_pullback(new_form(first, pos), x, g)
    assert got.dtype == x.dtype and dgot.dtype == x.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(_bits(dgot), _bits(dwant))
    # the lanes that pass come back, and their cotangent goes back, untouched
    np.testing.assert_array_equal(_bits(got[..., :first]),
                                  _bits(x[..., :first]))
    np.testing.assert_array_equal(_bits(dgot[..., :first]),
                                  _bits(g[..., :first]))
    # and something turned
    assert float(jnp.abs(got[..., first:].astype(jnp.float32)
                         - x[..., first:].astype(jnp.float32)).max()) \
        > (0.1 if S > 1 or positions != "none" else -1)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_under_jit_on_the_cpu_one_float32_ulp(shape, dtype):
    """See the module's text: the CPU's fused multiply-add, not the form.
    Where this process's compiler contracts nothing (the old form under
    ``jit`` is itself run by primitive), the forms agree to the bit under
    ``jit`` too."""
    (B, S, H, hd), first = SHAPES[shape]
    x, g = _case((B, S, H, hd), DTYPES[dtype])
    pos = _positions("s", B, S)
    old, new = old_form(first, pos), new_form(first, pos)
    want, dwant = jax.jit(lambda x, g: _value_and_pullback(old, x, g))(x, g)
    got, dgot = jax.jit(lambda x, g: _value_and_pullback(new, x, g))(x, g)
    with jax.disable_jit():
        plain, dplain = _value_and_pullback(old, x, g)
    contracted = (_bits(want) != _bits(plain)).any() \
        or (_bits(dwant) != _bits(dplain)).any()
    # |x| < 8 here, so a float32 ulp of a sum is < 2**-21; a bfloat16 that
    # the ulp tips over a rounding boundary moves by one of its own (2**-6)
    ulp = 0.0 if not contracted else 2.0 ** -21 if dtype == "f32" \
        else 2.0 ** -6
    for a, b in ((got, want), (dgot, dwant)):
        a, b = (np.asarray(t.astype(jnp.float32)) for t in (a, b))
        assert np.abs(a - b).max() <= ulp
        if dtype == "bf16":
            assert np.mean(a != b) < 0.01
    np.testing.assert_array_equal(_bits(got[..., :first]),
                                  _bits(x[..., :first]))


@pytest.mark.parametrize("positions", ["positions_none", "positions_b_s"])
def test_the_split_half_branch_is_the_parents_jaxpr(positions):
    with open(os.path.join(HERE, "data", "rope_split_half_jaxpr.json")) as f:
        want = json.load(f)[positions]
    x = jax.ShapeDtypeStruct((2, 16, 4, 8), jnp.bfloat16)
    if positions == "positions_none":
        got = jax.make_jaxpr(lambda x: rope(x, THETA))(x)
    else:
        got = jax.make_jaxpr(lambda x, p: rope(x, THETA, p))(
            x, jax.ShapeDtypeStruct((2, 16), jnp.int32))
    assert str(got) == want


def test_first_is_the_interleaved_layouts_alone():
    x = jnp.zeros((1, 4, 2, 8), jnp.bfloat16)
    with pytest.raises(AssertionError):
        rope(x, THETA, first=4)
    with pytest.raises(AssertionError):
        rope(x, THETA, interleaved=True, first=3)    # an odd count turns


def test_no_stride_and_no_gather_in_the_interleaved_text():
    """The lowered text, value and gradient, at the JoyAI head: no slice
    with a stride, no gather or scatter (what XLA makes of one on the
    chip), no concatenate of ``x``-sized parts."""
    x = jax.ShapeDtypeStruct((2, 64, 4, 192), jnp.bfloat16)
    text = jax.jit(lambda x, g: _value_and_pullback(
        new_form(128, None), x, g)).lower(x, x).as_text()
    assert "stablehlo.dot_general" in text
    for op in ("stablehlo.gather", "stablehlo.scatter", "stablehlo.slice",
               "stablehlo.concatenate"):
        assert op not in text, op
    # the old form's, for the contrast
    old = jax.jit(lambda x, g: _value_and_pullback(
        old_form(128, None), x, g)).lower(x, x).as_text()
    assert "stablehlo.slice" in old and "stablehlo.concatenate" in old
