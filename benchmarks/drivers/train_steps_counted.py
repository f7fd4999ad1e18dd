"""Driver ``train_steps_counted``: ``train_steps``, and two more things a
run is held to, for a model of which ``train_steps``' own checks see too
little.  The run is ``train_steps.run_cell``'s, unchanged; afterwards, on
the engine it built:

* **step counts.**  Where the model's loss comes with counts of what it
  left out of it (``Model.loss_with_counts_fn``: the routed rows past an
  expert layer's static bound), the engine's sums over every step it ran —
  reference step, warm-up and window — go on a line of their own, and a run
  in which one is not zero is not ``correct``: the step computed another
  sum than the reference's equations.
* **token by token.**  Where the configuration's reference has
  ``token_losses`` and ``TOKEN_NLL_RMS_ATOL``: at the parameters the run
  ends with, on the first micro-batch of the seed's first batch, every
  scored position's negative log likelihood from the program's own forward
  pass (``model.apply``: its kernels and precision) against the plain
  reference's, as the root of the mean squared difference.  A mean over
  32,768 tokens averages rounding away — the first loss of a reference
  computed in fp8 lands within 1e-3 of the float32 one in most seeds
  (PERF.md section 2, PR 32) — and this does not.

A program without either (a commit from before them) has nothing to read
here: the lines say so and the result stands as ``train_steps`` left it.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from drivers import train_steps
from harness import datagen


def token_nll(model, params, micro_batch):
    """[b, S] float32: the program's negative log likelihood of each
    position's next token (the last position's is of the first: unscored)."""
    def nll(params, batch):
        logits = model.apply(params, batch).astype(jnp.float32)
        targets = jnp.roll(batch["input_ids"], -1, axis=1)
        return jax.scipy.special.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return np.asarray(jax.jit(nll)(
        params, {k: jnp.asarray(v) for k, v in micro_batch.items()}))


def rms(a, b, scored):
    return float(np.sqrt(np.mean(np.square(a - b)[scored])))


def token_check(engine, config, traffic, seed, chips):
    """(root mean squared difference of the scored positions' losses,
    program against reference; the limit) or (None, None)."""
    reference = importlib.import_module("references." + config["reference"])
    limit = getattr(reference, "TOKEN_NLL_RMS_ATOL", None)
    if limit is None:
        return None, None
    sizes = {**config["model"], "n_params": engine.model.meta["n_params"]}
    stream = datagen.BatchStream(
        traffic, sizes["vocab_size"],
        traffic["micro_batch_per_chip"] * engine.topology.dp_world_size, seed)
    try:
        first = stream.next()
    finally:
        stream.close()
    micro = {k: np.asarray(v)[0] for k, v in first.items()}
    params = engine.state["params"]
    want, scored = reference.token_losses(
        params, micro, sizes,
        max(1, config["checks"]["reference_chunk_tokens_per_chip"]
            // traffic["seq_len"]) * chips)
    return rms(token_nll(engine.model, params, micro), want, scored), limit


def run_cell(cell, config, traffic, layer_metrics, seed, *args, **kwargs):
    engines = []
    build_engine = train_steps.build_engine

    def keeping_the_engine(*a, **k):
        built = build_engine(*a, **k)
        engines.append(built[0])
        return built

    train_steps.build_engine = keeping_the_engine
    try:
        result = train_steps.run_cell(cell, config, traffic, layer_metrics,
                                      seed, *args, **kwargs)
    finally:
        train_steps.build_engine = build_engine
    engine, problems = engines[0], []
    read = getattr(engine, "step_counts", None)
    counts = read() if read else None
    train_steps.say(line="step_counts", counts=counts,
                    optimizer_steps=engine.global_steps)
    for name, n in (counts or {}).items():
        train_steps.check(
            n == 0, f"{name} = {n} over the run's {engine.global_steps} "
                    f"steps: the model left that much out of its loss",
            problems)
    distance, limit = token_check(engine, config, traffic, seed,
                                  len(engine.mesh.devices.flat))
    train_steps.say(line="token_check", token_nll_rms=distance, limit=limit,
                    at_step=engine.global_steps)
    if limit is not None:
        train_steps.check(
            distance <= limit, f"the scored positions' losses are {distance} "
            f"(rms) from the plain reference's at step "
            f"{engine.global_steps}: over {limit}", problems)
    if problems:
        result["correct"] = False
    return result
