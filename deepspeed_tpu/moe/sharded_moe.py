"""Top-k gating with capacity — functional (reference: deepspeed/moe/
sharded_moe.py:184 ``top1gating``, :282 ``top2gating``, :348 ``TopKGate``).

Produces dense dispatch/combine tensors (GShard formulation) so the expert
dispatch is two einsums whose resharding XLA lowers to the all-to-alls the
reference issues explicitly (sharded_moe.py:425 ``MOELayer`` a2a).  Capacity is
enforced by position-in-expert cumsum (deterministic, compile-friendly) — the
reference's random-token-priority option trades determinism for load spread and
is exposed via gumbel jitter on the logits instead.
"""
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class GateOutput(NamedTuple):
    l_aux: jnp.ndarray            # load-balancing loss (scalar)
    combine_weights: jnp.ndarray  # [T, E, C] float
    dispatch_mask: jnp.ndarray    # [T, E, C] bool
    router_z_loss: jnp.ndarray    # scalar (0 when disabled)


class TopKRouting(NamedTuple):
    """Capacity-free routing decision (ISSUE 8): the top-k selection and
    normalized gate values WITHOUT the dense [T, E, C] tensors — the
    grouped (megablocks-style) dispatch consumes this directly, and
    :func:`topkgating` builds its capacity tensors from the same values
    so the two dispatch modes share bitwise-identical router math."""
    l_aux: jnp.ndarray            # load-balancing loss (scalar)
    router_z_loss: jnp.ndarray    # scalar (0 when disabled)
    expert_idx: jnp.ndarray       # [T, k] int32 chosen expert per choice
    gate_weights: jnp.ndarray     # [T, k] fp32 normalized gate values


#: forms of the load-balance term ``E * sum_e f_e * P_e`` (P_e = mean
#: router probability of expert e): ``first_choice`` takes f_e from each
#: token's first choice alone (the reference's top-1 form, sum_e f_e = 1);
#: ``all_choices`` counts every one of the k (token, choice) pairs sent to
#: e, over T (Hugging Face ``load_balancing_loss_func``, sum_e f_e = k)
LOAD_BALANCE_FORMS = ("first_choice", "all_choices")

#: what a router makes of its logits: ``softmax`` over the experts (the
#: reference; the ``top_k`` largest are chosen and their probabilities are
#: the weights), or ``sigmoid`` scores, one expert at a time
#: (DeepSeek-V3, arXiv:2412.19437 section 2.1.2; Nemotron-H): the choice
#: is the ``top_k`` largest of ``score + selection_bias``, a bias the loss
#: does not train and that is NOT in the weights, which are the chosen
#: scores themselves
ROUTER_FORMS = ("softmax", "sigmoid")


def topk_routing(logits: jnp.ndarray, k: int,
                 noise_rng: Optional[jax.Array] = None,
                 z_loss_coef: float = 0.0, normalize: bool = True,
                 load_balance: str = "first_choice",
                 router: str = "softmax", selection_bias=None,
                 scale: float = 1.0) -> TopKRouting:
    """The selection/aux half of :func:`topkgating`, verbatim (iterative
    argmax with -1e9 suppression, top-1 aux loss, per-token gate
    normalization) — extracted so capacity enforcement is a property of
    the DISPATCH, not of the routing decision.  ``normalize=False`` keeps
    the chosen softmax probabilities as they are (OLMoE's
    ``norm_topk_prob: false``); ``load_balance`` picks the form of
    ``l_aux`` (:data:`LOAD_BALANCE_FORMS`).  ``router`` picks what the
    logits become (:data:`ROUTER_FORMS`); under ``sigmoid`` the choice is
    by ``score + selection_bias`` ([E], no gradient reaches it), the
    weights are the chosen scores, and ``P_e`` of the load-balance term is
    the mean of ``score_e / sum_e' score_e'``.  ``scale`` multiplies the
    weights last (after ``normalize``)."""
    if load_balance not in LOAD_BALANCE_FORMS:
        raise ValueError(f"load_balance {load_balance!r}: choose one of "
                         f"{LOAD_BALANCE_FORMS}")
    if router not in ROUTER_FORMS:
        raise ValueError(f"router {router!r}: choose one of {ROUTER_FORMS}")
    T, E = logits.shape
    if router == "sigmoid":
        gates = jax.nn.sigmoid(logits.astype(jnp.float32))
        select_logits = gates if selection_bias is None \
            else gates + selection_bias.astype(jnp.float32)
        balance_probs = gates / jnp.sum(gates, axis=-1, keepdims=True)
    else:
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        select_logits = logits.astype(jnp.float32)
        balance_probs = gates
    if noise_rng is not None:
        select_logits = select_logits + jax.random.gumbel(
            noise_rng, select_logits.shape)

    top1 = jnp.argmax(select_logits, axis=-1)
    me = jnp.mean(balance_probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0)
    l_aux = jnp.sum(me * ce) * E

    z_loss = jnp.float32(0.0)
    if z_loss_coef > 0:
        z = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
        z_loss = z_loss_coef * jnp.mean(z ** 2)

    remaining = select_logits
    chosen_gates = []
    chosen_idx = []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        chosen_idx.append(idx)
        picked = jax.nn.one_hot(idx, E)
        # the chosen gate as a masked sum (one term and zeros: exact), so
        # that its transpose is a product and not a scatter-add
        chosen_gates.append(jnp.sum(gates * picked, axis=1))
        remaining = remaining - picked * 1e9

    expert_idx = jnp.stack(chosen_idx, axis=1).astype(jnp.int32)
    if load_balance == "all_choices":
        fe = jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32),
                     axis=(0, 1)) / T
        l_aux = jnp.sum(me * fe) * E
    if normalize:
        denom = sum(chosen_gates)
        denom = jnp.maximum(denom, jnp.finfo(jnp.float32).eps)
        chosen_gates = [g / denom for g in chosen_gates]
    if scale != 1.0:
        chosen_gates = [g * scale for g in chosen_gates]
    gate_weights = jnp.stack(chosen_gates, axis=1)
    return TopKRouting(l_aux, z_loss, expert_idx, gate_weights)


def router_health(logits: jnp.ndarray, routing: TopKRouting,
                  num_experts: int):
    """Router-health scalars shared BITWISE by both dispatch modes
    (ISSUE 15 satellite): computed from the same ``topk_routing``
    decision the einsum and grouped formulations consume, so the two
    paths can never disagree about the numbers.

    Returns ``(entropy, load_fractions [E], max_load_fraction,
    dead_experts)``:

    - **entropy** — mean per-token softmax entropy in nats (ln E =
      uniform router; ~0 = collapsed router);
    - **load_fractions** — fraction of the T*k routed choices landing
      on each expert (capacity-free: what the router *asked for*, not
      what capacity kept);
    - **max_load_fraction** — the hottest expert's share (1/E =
      balanced; 1.0 = total collapse);
    - **dead_experts** — experts that received ZERO choices this step.
    """
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # [T, E]
    plogp = jnp.where(gates > 0, gates * jnp.log(gates), 0.0)
    entropy = -jnp.mean(jnp.sum(plogp, axis=-1))
    flat = routing.expert_idx.reshape(-1)                          # [T*k]
    counts = jnp.sum(jax.nn.one_hot(flat, num_experts,
                                    dtype=jnp.float32), axis=0)    # [E]
    total = jnp.maximum(jnp.sum(counts), 1.0)
    load = counts / total
    return (entropy, load, jnp.max(load),
            jnp.sum((counts == 0).astype(jnp.int32)))


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int, top_k: int = 1) -> int:
    cap = int(num_tokens * top_k / num_experts * capacity_factor)
    return max(cap, min_capacity)


def _one_hot_dispatch(indices, gates_for_choice, num_experts, capacity,
                      occupancy=None):
    """indices: [T] chosen expert per token; gates_for_choice: [T] weight.

    ``occupancy`` [E] is the number of capacity slots already consumed by
    earlier choice rounds; positions for this round start after it and the
    capacity drop is applied to the offset position (reference
    sharded_moe.py:304-318 ``locations2 += sum(mask1)``), so a token's top-1
    and another token's top-2 for the same expert can never share a slot.
    Returns ([T,E,C] combine, [T,E,C] mask, per-expert kept counts [E]).
    """
    T = indices.shape[0]
    mask = jax.nn.one_hot(indices, num_experts, dtype=jnp.int32)     # [T, E]
    pos_in_expert = jnp.cumsum(mask, axis=0) * mask - mask           # [T, E]
    if occupancy is not None:
        pos_in_expert = pos_in_expert + occupancy[None, :] * mask
    within = pos_in_expert < capacity
    mask = mask * within.astype(jnp.int32)
    pos = jnp.sum(pos_in_expert * mask, axis=1)                      # [T]
    kept = jnp.sum(mask, axis=1) > 0                                 # [T]
    loc = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)           # [T, C]
    combine = (gates_for_choice * kept)[:, None, None] * \
        mask.astype(jnp.float32)[:, :, None] * loc[:, None, :]
    return combine, combine > 0, jnp.sum(mask, axis=0)


def topkgating(logits: jnp.ndarray, k: int, capacity_factor: float = 1.0,
               min_capacity: int = 4, noise_rng: Optional[jax.Array] = None,
               z_loss_coef: float = 0.0,
               routing: Optional[TopKRouting] = None) -> GateOutput:
    """logits: [T, E].  Generalises top1/top2 (reference keeps them separate).

    Load-balancing aux loss follows the reference: E * Σ_e mean_tokens(me) ·
    fraction_dispatched(ce), computed on the top-1 assignment.  A caller
    that already holds the :func:`topk_routing` decision (moe_layer's
    router-health tap) passes it in so the selection runs once.
    """
    T, E = logits.shape
    capacity = _capacity(T, E, capacity_factor, min_capacity, top_k=k)
    if routing is None:
        routing = topk_routing(logits, k, noise_rng, z_loss_coef)

    combine_total = jnp.zeros((T, E, capacity), jnp.float32)
    occupancy = jnp.zeros((E,), jnp.int32)
    for i in range(k):
        combine, _, counts = _one_hot_dispatch(
            routing.expert_idx[:, i], routing.gate_weights[:, i], E,
            capacity, occupancy=occupancy)
        combine_total = combine_total + combine
        occupancy = occupancy + counts

    return GateOutput(routing.l_aux, combine_total, combine_total > 0,
                      routing.router_z_loss)


def top1gating(logits, capacity_factor: float = 1.0, min_capacity: int = 4,
               noise_rng=None) -> GateOutput:
    """reference sharded_moe.py:184 (gate value not normalised for k=1)."""
    T, E = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    capacity = _capacity(T, E, capacity_factor, min_capacity, 1)
    select = logits.astype(jnp.float32)
    if noise_rng is not None:
        select = select + jax.random.gumbel(noise_rng, select.shape)
    idx = jnp.argmax(select, axis=-1)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=0)
    l_aux = jnp.sum(me * ce) * E
    gate_val = jnp.take_along_axis(gates, idx[:, None], axis=1)[:, 0]
    combine, mask, _ = _one_hot_dispatch(idx, gate_val, E, capacity)
    return GateOutput(l_aux, combine, mask, jnp.float32(0.0))


def top2gating(logits, capacity_factor: float = 1.0,
               min_capacity: int = 4, noise_rng=None) -> GateOutput:
    """reference sharded_moe.py:282."""
    return topkgating(logits, 2, capacity_factor, min_capacity, noise_rng)
