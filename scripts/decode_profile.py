"""Decode-step variant profiling: where does the per-token millisecond go?

VERDICT round-4 item 1: per-family decode rates sit 4-5x above the
weight-streaming floor.  This script times the gpt2 decode step in
structural variants to attribute the residue:

  scan_scatter   — the shipped round-4 path: lax.scan over layers with the
                   cache in xs/ys (full cache copy per token) and scatter
                   cache writes
  unroll_scatter — python-unrolled layers, cache updated in place on the
                   carried stacked array (static layer index + scatter)
  unroll_mask    — unrolled, cache row written via an iota==length mask
                   select instead of scatter
  weights_floor  — one dummy matmul chain streaming the same weight bytes
                   (the floor decode can never beat)

Timing uses the on-device fori_loop slope discipline from flash_ab.py
(a blocking round trip is a fixed cost; only slopes between step counts
are trustworthy).

    python scripts/decode_profile.py            # gpt2 125m, B=4, S=384
    DEC_B=8 DEC_S=512 python scripts/decode_profile.py
    DEC_MOE=1 python scripts/decode_profile.py  # mixtral expert floors

DEC_MOE=1 (ISSUE 8) switches to the Mixtral expert-floor accounting:
``weights_floor_moe`` streams the dense int8 bytes plus only the top-k-
DISTINCT-expert bytes per step (what the grouped int8 kernel's slot
plan fetches), vs ``weights_floor_moe_all`` streaming all E experts
(what einsum dispatch — or any capacity-padded formulation — pays).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


# shared slope-timing helper (scripts/bench_util.py): value-fetch sync
from scripts.bench_util import emit_ledger
from scripts.bench_util import timed_chain_ms as timed_chain


def _variant_record(model: str, name: str, step_ms: float) -> dict:
    """Ledger form of one variant row (DS_BENCH_LEDGER=1, ISSUE 13):
    step_ms is the gated value; the model shape rides detail.model so
    bench_compare's cross-model guard engages.  ``mem_peak_*`` fields
    (ISSUE 14) and ``comm_*`` fields (ISSUE 19) ride detail too, so
    the history can gate memory and interconnect regressions beside
    latency ones."""
    from scripts.bench_util import comm_fields, mem_peak_fields
    return {"metric": f"decode_profile_{name}", "value": step_ms,
            "unit": "ms_per_step", "direction": "lower_better",
            "detail": {"model": model, **mem_peak_fields(),
                       **comm_fields()}}


def moe_floor_main():
    """Mixtral expert-floor accounting + dummy-stream timing (ISSUE 8):
    how much of the decode step's weight traffic is experts, and what
    the grouped int8 path's distinct-expert floor buys over streaming
    every expert.  Per layer a decode step with A active rows and top-k
    routing touches at most min(A*k, E) distinct experts — the grouped
    slot kernel fetches exactly the distinct set once; the einsum
    formulation's dense [T,E,C] dispatch computes (and streams) all E."""
    on_tpu = "tpu" in str(jax.devices()[0]).lower()
    B = int(os.environ.get("DEC_B", 4))
    size = os.environ.get("DEC_MODEL", "1b-moe" if on_tpu else "tiny")
    steps = int(os.environ.get("DEC_STEPS", 20 if on_tpu else 2))

    from deepspeed_tpu.models.mixtral import mixtral_model
    from deepspeed_tpu.models.model import QuantizedTensor
    from deepspeed_tpu.ops.pallas.quantization import block_quantize_int8
    model = mixtral_model(size, dtype="bfloat16" if on_tpu else "float32",
                          attention_impl="xla")
    cfg = model.config
    dtype = jnp.dtype(cfg.dtype)
    params = jax.jit(model.init_fn)(jax.random.PRNGKey(0))

    def _pack(x):
        if x.ndim >= 3 and jnp.issubdtype(x.dtype, jnp.floating):
            qq, ss = block_quantize_int8(x.astype(dtype))
            return QuantizedTensor(qq, ss, str(dtype))
        return x

    qblocks = jax.tree.map(_pack, params["blocks"])
    is_q = lambda x: isinstance(x, QuantizedTensor)
    expert_mats, dense_mats = [], []
    for leaf in jax.tree_util.tree_leaves(qblocks, is_leaf=is_q):
        if not is_q(leaf):
            continue
        if leaf.q.ndim >= 4:        # [L, E, in, out] stacked experts
            expert_mats.append(leaf)
        else:
            dense_mats.append(leaf)
    E, k, L = cfg.num_experts, cfg.top_k, cfg.num_layers
    # byte accounting shared with serve_bench's weights_floor_moe record
    from deepspeed_tpu.models.serving import split_quantized_bytes
    dense_b, expert_b = split_quantized_bytes(qblocks)
    per_expert = expert_b // E          # all layers, one expert
    distinct = min(B * k, E)
    floor_moe = dense_b + distinct * per_expert
    floor_all = dense_b + expert_b
    print(json.dumps({
        "model": f"mixtral:{size}", "batch": B, "num_experts": E,
        "top_k": k, "layers": L,
        "dense_int8_bytes_mb": round(dense_b / 1e6, 2),
        "expert_int8_bytes_mb": round(expert_b / 1e6, 2),
        "distinct_experts_per_step_bound": distinct,
        "weights_floor_moe_mb": round(floor_moe / 1e6, 2),
        "weights_floor_moe_all_mb": round(floor_all / 1e6, 2),
        "floor_ratio_all_over_distinct": round(floor_all / floor_moe, 3),
        "floor_moe_ms_at_819GBs": round(floor_moe / 819e9 * 1e3, 3),
        "floor_moe_all_ms_at_819GBs": round(floor_all / 819e9 * 1e3, 3),
    }))

    # dummy-stream variants: one int8 matvec chain per streamed matrix —
    # the same idiom as weights_floor_int8, restricted to the bytes each
    # formulation actually touches per step
    def chain(mats_2d):
        def step(state):
            tok, a, b = state
            acc = jnp.zeros((B, 1), jnp.int32)
            for m in mats_2d:
                r, _ = m.shape
                y = jnp.broadcast_to(tok[:, None].astype(jnp.int8), (B, r))
                acc = acc + jnp.sum(lax.dot(
                    y, m, preferred_element_type=jnp.int32),
                    axis=-1, keepdims=True)
            return ((tok + jnp.sum(acc) * 0) % 127, a, b)
        return step

    def flat_dense(leaves):
        return [m.q.reshape(-1, m.q.shape[-1]) for m in leaves]

    def flat_experts(n):
        # first n experts of every layer stand in for the distinct set —
        # same byte count, same access pattern class
        return [m.q[:, :n].reshape(-1, m.q.shape[-1])
                for m in expert_mats]

    tok0 = jnp.zeros((B,), jnp.int32)
    state0 = (tok0, tok0, tok0)
    for name, mats_2d in (
            ("weights_floor_moe", flat_dense(dense_mats)
             + flat_experts(distinct)),
            ("weights_floor_moe_all", flat_dense(dense_mats)
             + flat_experts(E))):
        try:
            ms = timed_chain(chain(mats_2d), state0, steps)
            print(json.dumps({"variant": name, "step_ms": round(ms, 4),
                              "tok_per_s_B": (round(B / (ms * 1e-3))
                                              if ms > 0 else None)}))
            emit_ledger(_variant_record(f"mixtral:{size}:B{B}", name,
                                        round(ms, 4)))
        except Exception as e:
            print(json.dumps({"variant": name, "error": str(e)[:300]}))


def main():
    if os.environ.get("DEC_MOE"):
        return moe_floor_main()
    on_tpu = "tpu" in str(jax.devices()[0]).lower()
    B = int(os.environ.get("DEC_B", 4))
    S = int(os.environ.get("DEC_S", 384))
    size = os.environ.get("DEC_MODEL", "125m" if on_tpu else "custom")
    steps = int(os.environ.get("DEC_STEPS", 20 if on_tpu else 2))

    from deepspeed_tpu.models import gpt2 as G
    kwargs = {} if on_tpu else dict(vocab_size=256, num_layers=2,
                                    num_heads=4, d_model=32)
    model = G.gpt2_model(size, dtype="bfloat16" if on_tpu else "float32",
                         max_seq_len=max(1024, S), **kwargs)
    cfg = model.config
    params = jax.jit(model.init_fn)(jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: x.astype(cfg.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    L = cfg.num_layers
    dtype = jnp.dtype(cfg.dtype)

    cache = G.init_cache(cfg, B, S)
    # warm cache with realistic fill
    rng = np.random.default_rng(0)
    cache = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype)
             for k, v in cache.items()}
    lengths0 = jnp.full((B,), S // 2, jnp.int32)
    tok0 = jnp.zeros((B,), jnp.int32)

    from deepspeed_tpu.models.model import maybe_stream
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
    rows = jnp.arange(B)

    def embed(tokens, lengths):
        return (params["wte"].astype(dtype)[tokens] +
                params["wpe"].astype(dtype)[lengths])

    def logits_of(x):
        return G.head(params, x[:, None, :], cfg)[:, 0]

    def next_state(logits, cache, lengths):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # stay in bounds over long chains while keeping the data dependency
        lengths = jnp.minimum(lengths + 1, S - 1)
        return (tok, cache, lengths)

    # ---------------------------------------------------------- variants
    def scan_scatter(state):
        tok, cache, lengths = state
        logits, cache = G.decode_step(params, tok, cache, lengths, cfg)
        return next_state(logits, cache, lengths)

    def unroll_common(state, write):
        tok, cache, lengths = state
        x = embed(tok, lengths)
        kc, vc = cache["k"], cache["v"]
        for l in range(L):
            layer = maybe_stream(jax.tree.map(lambda a: a[l],
                                              params["blocks"]))
            q, kk, v = G._block_qkv(x[:, None, :], layer, cfg)
            kc = write(kc, l, kk[:, 0], lengths)
            vc = write(vc, l, v[:, 0], lengths)
            attn = decode_attention(q[:, 0], kc[l], vc[l], lengths + 1)
            x = G._block_finish(x[:, None, :],
                                attn.reshape(B, 1, cfg.d_model), layer,
                                cfg)[:, 0]
        return next_state(logits_of(x), {"k": kc, "v": vc}, lengths)

    def scatter_write(c, l, new, lengths):
        return c.at[l, rows, lengths].set(new.astype(c.dtype))

    def mask_write(c, l, new, lengths):
        # [B, S] one-hot row mask -> select; dense-bandwidth on ONE layer
        m = (jnp.arange(c.shape[2])[None, :] ==
             lengths[:, None])[..., None, None]           # [B, S, 1, 1]
        upd = jnp.where(m, new[:, None].astype(c.dtype), c[l])
        return lax.dynamic_update_slice(
            c, upd[None], (l, 0, 0, 0, 0))

    def rowdus_write(c, l, new, lengths):
        # B tiny in-place dynamic_update_slices (one per row)
        new = new.astype(c.dtype)
        for b in range(B):
            c = lax.dynamic_update_slice(
                c, new[b][None, None, None],
                (l, b, lengths[b], 0, 0))
        return c

    def unroll_uniform(state):
        # all rows share one position (the engine's common case: equal
        # right-padded prompts) -> ONE dus writes every row's new vector
        tok, cache, lengths = state
        pos = lengths[0]
        x = embed(tok, lengths)
        kc, vc = cache["k"], cache["v"]
        for l in range(L):
            layer = maybe_stream(jax.tree.map(lambda a: a[l],
                                              params["blocks"]))
            q, kk, v = G._block_qkv(x[:, None, :], layer, cfg)
            kc = lax.dynamic_update_slice(
                kc, kk.astype(kc.dtype)[None], (l, 0, pos, 0, 0))
            vc = lax.dynamic_update_slice(
                vc, v.astype(vc.dtype)[None], (l, 0, pos, 0, 0))
            attn = decode_attention(q[:, 0], kc[l], vc[l], lengths + 1)
            x = G._block_finish(x[:, None, :],
                                attn.reshape(B, 1, cfg.d_model), layer,
                                cfg)[:, 0]
        return next_state(logits_of(x), {"k": kc, "v": vc}, lengths)

    variants = {
        "scan_scatter": scan_scatter,
        "unroll_scatter": lambda s: unroll_common(s, scatter_write),
        "unroll_mask": lambda s: unroll_common(s, mask_write),
        "unroll_rowdus": lambda s: unroll_common(s, rowdus_write),
        "unroll_uniform": unroll_uniform,
    }

    # ------------------------------------------------- component ablations
    def ablate(state, *, attn=True, write=True, mlp=True, layers=True):
        tok, cache, lengths = state
        x = embed(tok, lengths)
        kc, vc = cache["k"], cache["v"]
        if layers:
            for l in range(L):
                layer = maybe_stream(jax.tree.map(lambda a: a[l],
                                                  params["blocks"]))
                q, kk, v = G._block_qkv(x[:, None, :], layer, cfg)
                if write:
                    kc = mask_write(kc, l, kk[:, 0], lengths)
                    vc = mask_write(vc, l, v[:, 0], lengths)
                if attn:
                    a = decode_attention(q[:, 0], kc[l], vc[l], lengths + 1)
                else:
                    a = q[:, 0]
                a = a.reshape(B, 1, cfg.d_model)
                if mlp:
                    x = G._block_finish(x[:, None, :], a, layer, cfg)[:, 0]
                else:
                    x = (x[:, None, :] + a @ layer["proj_w"].astype(x.dtype)
                         )[:, 0]
        return next_state(logits_of(x), {"k": kc, "v": vc}, lengths)

    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention_pallas, decode_attention_xla)

    def ablate_attn_impl(state, attn_fn):
        tok, cache, lengths = state
        x = embed(tok, lengths)
        kc, vc = cache["k"], cache["v"]
        for l in range(L):
            layer = maybe_stream(jax.tree.map(lambda a: a[l],
                                              params["blocks"]))
            q, kk, v = G._block_qkv(x[:, None, :], layer, cfg)
            kc = mask_write(kc, l, kk[:, 0], lengths)
            vc = mask_write(vc, l, v[:, 0], lengths)
            a = attn_fn(q[:, 0], kc[l], vc[l], lengths + 1)
            x = G._block_finish(x[:, None, :],
                                a.reshape(B, 1, cfg.d_model), layer,
                                cfg)[:, 0]
        return next_state(logits_of(x), {"k": kc, "v": vc}, lengths)

    variants.update({
        "ab_attn_block384": lambda s: ablate_attn_impl(
            s, lambda q, k, v, cl: decode_attention_pallas(
                q, k, v, cl, block_s=S)),
        "ab_attn_xla": lambda s: ablate_attn_impl(
            s, decode_attention_xla),
        "ab_full": lambda s: ablate(s),
        "ab_no_attn": lambda s: ablate(s, attn=False),
        "ab_no_write": lambda s: ablate(s, write=False),
        "ab_no_mlp": lambda s: ablate(s, mlp=False),
        "ab_embed_head": lambda s: ablate(s, layers=False),
    })

    # mimic the engine's _build_cached_generate scan exactly (decode_fn is
    # the NEW unrolled path): measures what the generate-loop scaffolding
    # (scan ys, done flags, argmax placement) adds per token
    def engine_scan(state):
        tok, cache, lengths = state

        def body(carry, _):
            cache, tok, lens, done = carry
            logits, cache = G.decode_step(params, tok, cache, lens, cfg)
            new = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, new, jnp.minimum(lens + 1, S - 1), done), new

        done = jnp.zeros((B,), bool)
        (cache, tok, lengths, _), ys = lax.scan(
            body, (cache, tok, lengths, done), None, length=8)
        return (tok + jnp.sum(ys) * 0, cache, lengths)

    def engine_fori(state):
        # the REJECTED generate-loop alternative (the engine ships the
        # scan form): fori_loop with an in-place token buffer — measured
        # ~0.1 ms/token slower than scan's ys emission.  Carries the
        # same done flag as engine_scan so the A/B isolates the
        # token-emission mechanism alone.
        tok, cache, lengths = state
        out0 = jnp.zeros((B, 8), jnp.int32)
        done0 = jnp.zeros((B,), bool)

        def body(i, carry):
            cache, tok, lens, done, out = carry
            logits, cache = G.decode_step(params, tok, cache, lens, cfg)
            new = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = lax.dynamic_update_slice(out, new[:, None], (0, i))
            return (cache, new, jnp.minimum(lens + 1, S - 1), done, out)

        cache, tok, lengths, _, out = lax.fori_loop(
            0, 8, body, (cache, tok, lengths, done0, out0))
        return (tok + out[:, -1] * 0, cache, lengths)

    def engine_scan_steps(n, fn=None):
        # per-token cost inside the mimic loop, from the fori slope over
        # chains of 8-token inner loops
        ms = timed_chain(fn or engine_scan, state0, max(2, n // 8))
        return ms / 8

    variants = dict(variants)

    # ------------------------------------------- int8-weight variants
    # the ISSUE-2 A/B: fused-dequant qgemm unrolled decode vs the
    # maybe_stream dequant form, plus the int8 weight-stream floor the
    # qgemm path is chasing (PERF.md round 5: 1.3B int8 238 tok/s on the
    # scan-dequant path vs an int8 floor several× higher)
    from deepspeed_tpu.models.model import QuantizedTensor
    from deepspeed_tpu.ops.pallas.quantization import block_quantize_int8

    def _pack(x):
        if x.ndim >= 3 and jnp.issubdtype(x.dtype, jnp.floating):
            qq, ss = block_quantize_int8(x.astype(dtype))
            return QuantizedTensor(qq, ss, str(dtype))
        return x

    qblocks = jax.tree.map(_pack, params["blocks"])

    def unroll_int8(state, keep_quantized):
        tok, cache, lengths = state
        x = embed(tok, lengths)
        kc, vc = cache["k"], cache["v"]
        for l in range(L):
            layer = maybe_stream(jax.tree.map(lambda a: a[l], qblocks),
                                 keep_quantized=keep_quantized)
            q, kk, v = G._block_qkv(x[:, None, :], layer, cfg)
            kc = mask_write(kc, l, kk[:, 0], lengths)
            vc = mask_write(vc, l, v[:, 0], lengths)
            attn = decode_attention(q[:, 0], kc[l], vc[l], lengths + 1)
            x = G._block_finish(x[:, None, :],
                                attn.reshape(B, 1, cfg.d_model), layer,
                                cfg)[:, 0]
        return next_state(logits_of(x), {"k": kc, "v": vc}, lengths)

    variants["unroll_int8_qgemm"] = lambda s: unroll_int8(s, True)
    variants["unroll_int8_dequant"] = lambda s: unroll_int8(s, False)

    qmats = [leaf.q.reshape(-1, leaf.q.shape[-1])
             for leaf in jax.tree.leaves(
                 qblocks, is_leaf=lambda x: isinstance(x, QuantizedTensor))
             if isinstance(leaf, QuantizedTensor)]
    qbytes = sum(int(m.size) for m in qmats)

    def weights_floor_int8(state):
        # one int8 [B, r] x [r, c] matmul per quantized matrix: streams
        # every int8 byte once per step with a tok data dependency (the
        # bf16 weights_floor idiom at 1 byte/param)
        tok, cache, lengths = state
        acc = jnp.zeros((B, 1), jnp.int32)
        for m in qmats:
            r, c = m.shape
            y = jnp.broadcast_to(tok[:, None].astype(jnp.int8), (B, r))
            d = lax.dot(y, m, preferred_element_type=jnp.int32)
            acc = acc + jnp.sum(d, axis=-1, keepdims=True)
        tok = (tok + jnp.sum(acc) * 0) % cfg.vocab_size
        return (tok, cache, lengths)

    variants["weights_floor_int8"] = weights_floor_int8

    # weights floor: one [B, r] @ [r, c] matmul per large weight matrix —
    # streams every weight byte once per step with zero overhead ops
    flat = [x for x in jax.tree.leaves(params)
            if jnp.issubdtype(x.dtype, jnp.floating)]
    mats = [x.reshape(-1, x.shape[-1]) for x in flat if x.size >= 1 << 16]
    wbytes = sum(int(x.size) * x.dtype.itemsize for x in flat)

    def weights_floor2(state):
        tok, cache, lengths = state
        acc = jnp.zeros((B, 1), jnp.float32)
        for m in mats:
            r, c = m.shape
            y = jnp.broadcast_to(tok[:, None].astype(dtype), (B, r))
            acc = acc + jnp.sum(y @ m, axis=-1, keepdims=True)
        tok = (tok + jnp.sum(acc).astype(jnp.int32) * 0) % cfg.vocab_size
        return (tok, cache, lengths)

    variants["weights_floor"] = weights_floor2

    # ------------------------------------------- fused megakernel A/B
    # ISSUE 12: the same decode step through the fused per-layer path
    # (ONE Pallas call per layer on chip; the jnp reference composition
    # off-chip — a structural A/B only there).  Token identity between
    # the two paths is asserted up front so the timing rows compare
    # equal programs.
    from deepspeed_tpu.ops.pallas.fused_decode import fused_decode_scope

    def fused_decode(state):
        # scope is a trace-time choice; timed_chain traces step_fn
        # inside this call, so the scope covers the trace
        with fused_decode_scope(True):
            tok, cache, lengths = state
            logits, cache = G.decode_step(params, tok, cache, lengths,
                                          cfg)
            return next_state(logits, cache, lengths)

    def fused_int8w(state):
        with fused_decode_scope(True):
            tok, cache, lengths = state
            qp = dict(params)
            qp["blocks"] = qblocks
            logits, cache = G.decode_step(qp, tok, cache, lengths, cfg)
            return next_state(logits, cache, lengths)

    variants["fused_decode"] = fused_decode
    variants["fused_int8w_decode"] = fused_int8w

    def _argmax_chain(fused, n=4):
        tok, cache, lengths = state0
        with fused_decode_scope(fused):
            f = jax.jit(lambda t, c, l: G.decode_step(params, t, c, l,
                                                      cfg))
            out = []
            for _ in range(n):
                logits, cache = f(tok, cache, lengths)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                lengths = lengths + 1
                out.append(np.asarray(tok))
        return np.stack(out)

    state0 = (tok0, cache, lengths0)
    try:
        fused_same = bool((_argmax_chain(False)
                           == _argmax_chain(True)).all())
    except Exception as e:
        fused_same = f"error: {str(e)[:200]}"
    print(json.dumps({"variant": "fused_parity",
                      "token_identical": fused_same}))

    cal = jnp.asarray(rng.standard_normal((2048, 2048)), jnp.bfloat16)
    mm = lambda s: (jnp.tanh(s[0] @ cal), s[1], s[2])
    mm_ms = timed_chain(mm, (cal, 0, 0), steps)
    mm_tf = 2 * 2048 ** 3 / (mm_ms * 1e-3) / 1e12 if mm_ms > 0 else None
    print(json.dumps({"calibration": "matmul2048", "ms": round(mm_ms, 4),
                      "apparent_tflops": round(mm_tf, 1) if mm_tf else None,
                      "weight_bytes_mb": round(wbytes / 1e6, 1),
                      "floor_ms_at_819GBs": round(wbytes / 819e9 * 1e3, 3),
                      "int8_weight_bytes_mb": round(qbytes / 1e6, 1),
                      "int8_floor_ms_at_819GBs": round(
                          qbytes / 819e9 * 1e3, 3)}))

    only = [s for s in os.environ.get("DEC_ONLY", "").split(",") if s]
    if only:
        variants = {k: v for k, v in variants.items() if k in only}

    state0 = (tok0, cache, lengths0)
    for mimic_name, mimic_fn in (("engine_scan_mimic", engine_scan),
                                 ("engine_fori_mimic", engine_fori)):
        try:
            if only and mimic_name not in only:
                continue
            ms8 = engine_scan_steps(steps, mimic_fn)
            print(json.dumps({"variant": mimic_name,
                              "step_ms": round(ms8, 4),
                              "tok_per_s_B": (round(B / (ms8 * 1e-3))
                                              if ms8 > 0 else None)}))
        except Exception as e:
            print(json.dumps({"variant": mimic_name,
                              "error": str(e)[:300]}))
    for name, fn in variants.items():
        try:
            ms = timed_chain(fn, state0, steps)
            print(json.dumps({"variant": name, "step_ms": round(ms, 4),
                              "tok_per_s_B": (round(B / (ms * 1e-3))
                                              if ms > 0 else None)}))
            emit_ledger(_variant_record(f"gpt2:{size}:B{B}:S{S}", name,
                                        round(ms, 4)))
        except Exception as e:  # keep profiling the rest
            print(json.dumps({"variant": name,
                              "error": str(e)[:300]}))


if __name__ == "__main__":
    main()
