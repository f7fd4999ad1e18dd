"""Shared test fixtures (reference: tests/unit/simple_model.py — SimpleModel
and random_dataloader equivalents)."""
import os
import re

import numpy as np

from deepspeed_tpu.models.gpt2 import gpt2_model


def child_env(**extra):
    """The environment of a child process that compiles: this process's
    (the conftest's ``XLA_FLAGS`` are in it) with the compile cache's
    directory, so that a child beside five busy workers does not compile
    cold what the last run compiled.  Not for a child that restores a
    checkpoint and trains on: under a warm cache that path corrupts the
    heap on this jaxlib."""
    import jax
    return dict(os.environ, JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=jax.config.jax_compilation_cache_dir,
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **extra)


def tiny_gpt2(**overrides):
    kwargs = dict(vocab_size=128, max_seq_len=64, num_layers=2, num_heads=4,
                  d_model=32, dtype="float32", attention_impl="xla")
    kwargs.update(overrides)
    return gpt2_model(size="custom", **kwargs)


def random_batch(batch_size=8, seq_len=16, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(batch_size, seq_len),
                                      dtype=np.int32)}


def random_batches(n, batch_size=8, seq_len=16, vocab=128, seed=0):
    return [random_batch(batch_size, seq_len, vocab, seed + i)
            for i in range(n)]


def base_config(**overrides):
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
    }
    cfg.update(overrides)
    return cfg


def scope_parts(scopes):
    """Every name between the slashes and the parentheses of scope paths
    (the ``scope`` column of ``tracing.get_program_map``, ``op_name``s):
    ``transpose(jvp(ds.block))/mlp/experts`` -> ds.block, mlp, experts."""
    return {part for scope in scopes if scope
            for part in re.split(r"[/()]+", scope) if part}


def kernel_names(fn, *args):
    """The ``ds_*`` names in the jaxpr of ``fn`` and of its gradient by
    its first argument: the ``name=`` of each ``pl.pallas_call`` they
    trace to, which is what an instruction's ``op_name`` carries."""
    import jax
    import jax.numpy as jnp
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32))))(*args))
    return set(re.findall(r"\bds_[a-z_]+", text))
