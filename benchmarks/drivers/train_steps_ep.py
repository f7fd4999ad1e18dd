"""Driver ``train_steps_ep``: ``train_steps_counted`` (and so
``train_steps``) for a cell whose deployment is **expert parallel under
ZeRO-2** on the chips of one host — the experts of every layer spread over
the ``expert`` mesh axis, the rows exchanged between the chips, everything
else data parallel over the same chips with its optimizer state split.

``train_steps.run_cell`` holds any cell of more than one chip to ZeRO-3's
statement (``check_split``: every large parameter split over all chips).
Under this deployment the dense parameters are whole on every chip and only
their optimizer state is split, so that check would fail a right program.
The run is ``train_steps_counted.run_cell``'s, unchanged but for that one
check, in whose place the run is held to *this* deployment:

* every expert leaf of the parameters (``w_gate`` / ``w_in`` / ``w_out``)
  split ``chips`` ways along its expert axis, a different slice on every
  device;
* every large leaf of the optimizer state split ``chips`` ways, and the
  layout of every large accumulated gradient too;
* the step's own account says its expert layers exchanged
  (``tracing.exchange_calls``) and by which collective (``path``), and the
  compiled step holds that collective by its opcode — on a TPU
  ``ragged-all-to-all`` and no other will do; off it (a rehearsal on the
  CPU, whose backend has no ragged one) the stand-in's ``all-to-all``;
* no ``ragged-dot`` and no array of the capacity formulation (``[tokens,
  experts, capacity]``) in it; the kernels of ``checks.require_kernels``
  are ``train_steps``' own check.

``moe/rows_over_bound`` summed over every step and chip, and the
token-by-token comparison, are ``train_steps_counted``'s.
"""
import re

import jax

from drivers import train_steps, train_steps_counted

EXPERT_LEAVES = ("w_gate", "w_in", "w_out")
#: a leaf of fewer elements may stay whole (norm weights, the router)
LARGE = 1 << 20


def _leaf_name(path):
    return getattr(path[-1], "key", None)


def _split(leaf, n, axis=None):
    """Whether ``leaf`` is cut into ``n`` different slices, one a device
    (along ``axis`` alone, where one is given)."""
    shards = leaf.addressable_shards
    if len({str(s.index) for s in shards}) != n \
            or any(s.data.size * n != leaf.size for s in shards):
        return False
    return axis is None or all(
        s.data.shape[axis] * n == leaf.shape[axis] for s in shards)


def _spec_splits(shape, sharding, n):
    """The same of a layout: ``n`` devices, each its own slice."""
    slices = sharding.devices_indices_map(tuple(shape))
    return len(slices) == n and len({str(i) for i in slices.values()}) == n


def check_deployment(engine, n, problems):
    """The three statements about where state lives (the module's first
    two points)."""
    check = train_steps.check
    key = jax.tree_util.keystr
    experts = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            engine.state["params"]):
        if _leaf_name(path) in EXPERT_LEAVES:
            experts += 1
            check(_split(leaf, n, axis=leaf.ndim - 3),
                  f"expert leaf {key(path)} {leaf.shape} is not split {n} "
                  f"ways by expert ({leaf.sharding})", problems)
    check(experts > 0, "the model has no expert leaf (w_gate / w_in / "
                       "w_out)", problems)
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            engine.state["opt_state"]):
        if getattr(leaf, "size", 0) >= LARGE:
            check(_split(leaf, n),
                  f"optimizer state {key(path)} {leaf.shape} is not split "
                  f"{n} ways ({leaf.sharding})", problems)
    for (path, leaf), sharding in zip(
            jax.tree_util.tree_leaves_with_path(engine.state["params"]),
            jax.tree.leaves(engine.grad_shardings)):
        if leaf.size >= LARGE:
            check(_spec_splits(leaf.shape, sharding, n),
                  f"the accumulated gradient of {key(path)} {leaf.shape} "
                  f"is not split {n} ways ({sharding.spec})", problems)


def capacity_arrays(text, tokens, experts):
    """Arrays ``[tokens, experts, anything]`` in an executable's text: the
    capacity formulation's dispatch and combine tensors."""
    return sorted(set(re.findall(
        rf"\b(?:pred|bf16|f32|s32)\[(?:{'|'.join(map(str, tokens))}),"
        rf"{experts},\d+\]", text)))


#: ``path`` of the step's account -> the opcode the executable holds for it
OPCODES = {"ragged_all_to_all": "ragged-all-to-all",
           "all_to_all": "all-to-all"}


def check_program(text, exchanges, tokens, experts, problems, on_chip):
    """The statements about the compiled step (the module's last two
    points): ``text`` the executable's, ``exchanges`` the step's own
    account (``tracing.exchange_calls``), ``tokens`` the token counts an
    array of the capacity formulation could lead with, ``on_chip`` whether
    the step runs on a TPU."""
    check = train_steps.check
    paths = {row.get("path") for row in exchanges or ()}
    check(bool(exchanges) and paths <= set(OPCODES)
          and all(row["pairs"] > 1 for row in exchanges),
          f"the step's own account has no exchange over more than one "
          f"chip: {exchanges}", problems)
    if on_chip:
        check(paths == {"ragged_all_to_all"},
              f"on the chip the rows travel by lax.ragged_all_to_all; the "
              f"step's account says {sorted(map(str, paths))}", problems)
    for path in sorted(paths & set(OPCODES)):
        check(re.search(rf" {OPCODES[path]}(?:-start)?\(", text)
              is not None,
              f"no {OPCODES[path]} in the compiled step, which the step's "
              f"account says its rows travel by: the experts' rows are not "
              f"exchanged", problems)
    check("ragged-dot" not in text and "ragged_dot" not in text,
          "ragged-dot in the compiled step: the grouped kernels gave way "
          "to it", problems)
    found = capacity_arrays(text, tokens, experts)
    check(not found, f"arrays of the capacity formulation in the compiled "
                     f"step: {found[:4]}", problems)


def run_cell(cell, config, traffic, layer_metrics, seed, *args, **kwargs):
    held = {}
    check_split = train_steps.check_split
    inspect_program = train_steps.inspect_program

    def and_the_exchange(engine, batch, checks, problems):
        from deepspeed_tpu.telemetry import tracing
        # one look at the executable for both sets of checks
        compiled = engine.compile_train_step(batch)
        engine.compile_train_step = lambda _: compiled
        try:
            inspect_program(engine, batch, checks, problems)
        finally:
            del engine.compile_train_step
        exchanges = getattr(tracing, "exchange_calls", lambda _: None)(
            train_steps.STEP_PROGRAM)
        per_chip = traffic["micro_batch_per_chip"] * traffic["seq_len"]
        chips = len(engine.mesh.devices.flat)
        check_program(compiled.as_text(), exchanges,
                      (per_chip, per_chip * chips),
                      config["model"]["num_experts"], problems,
                      on_chip=jax.default_backend() == "tpu")
        held["exchanges"] = exchanges

    train_steps.check_split = check_deployment
    train_steps.inspect_program = and_the_exchange
    try:
        result = train_steps_counted.run_cell(
            cell, config, traffic, layer_metrics, seed, *args, **kwargs)
    finally:
        train_steps.check_split = check_split
        train_steps.inspect_program = inspect_program
        # a process that goes on (a rehearsal among other tests) does not
        # keep the four-wide expert axis as its topology: model code traced
        # outside an engine reads the global one
        from deepspeed_tpu.comm import reset_topology
        reset_topology()
    train_steps.say(line="exchange", calls=held.get("exchanges"))
    return result
