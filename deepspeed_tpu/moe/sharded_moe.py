"""MoE gating + dispatch math.

Analogue of reference ``deepspeed/moe/sharded_moe.py`` (``TopKGate`` :343,
``top1gating`` :179, ``top2gating`` :277, ``_capacity`` :157, ``MOELayer``
:420 einsum dispatch, ``_AllToAll`` :90). The einsum dispatch/combine
formulation ports naturally to XLA; the explicit ``_AllToAll`` autograd shim
disappears — expert-sharding constraints make the SPMD partitioner insert
(differentiable) all-to-alls over the ``expert`` mesh axis.

All shapes are static (capacity-factor padding identical to ``_capacity``),
as required for XLA compilation (SURVEY §7 hard-parts).
"""

import jax
import jax.numpy as jnp


def capacity(num_tokens, num_experts, capacity_factor, min_capacity=4):
    """Tokens per expert (reference ``_capacity``, sharded_moe.py:157)."""
    cap = int(num_tokens * capacity_factor / num_experts)
    return max(cap, min_capacity)


def top_k_gating(logits, k, capacity_factor, min_capacity=4, rng=None, noise_std=0.0):
    """Top-k gating with per-expert capacity.

    Args:
      logits: (N, E) router logits (fp32).
    Returns:
      dispatch: (N, E, C) one-hot dispatch mask.
      combine: (N, E, C) combine weights.
      aux_loss: load-balancing loss (reference l_aux, sharded_moe.py:217).
      drop_frac: fraction of routed slots dropped by capacity.
    """
    N, E = logits.shape
    C = capacity(N * k, E, capacity_factor, min_capacity)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    if rng is not None and noise_std > 0:
        logits = logits + noise_std * jax.random.normal(rng, logits.shape)

    # iterative top-k selection
    masked = logits.astype(jnp.float32)
    sel_masks = []
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (N, E)
        sel_masks.append(m)
        masked = jnp.where(m > 0, -jnp.inf, masked)

    # aux loss from the top-1 assignment (reference top1gating l_aux)
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.mean(sel_masks[0], axis=0)  # (E,)
    aux_loss = jnp.sum(me * ce) * E

    # positions within expert buffers, k rounds share the capacity
    dispatch = jnp.zeros((N, E, C), dtype=jnp.float32)
    combine = jnp.zeros((N, E, C), dtype=jnp.float32)
    prior_count = jnp.zeros((E, ), dtype=jnp.int32)
    kept = jnp.zeros((), dtype=jnp.float32)
    for m in sel_masks:
        pos = jnp.cumsum(m, axis=0) - 1 + prior_count[None, :]  # (N, E)
        keep = (pos < C) & (m > 0)
        kept = kept + jnp.sum(keep)
        loc = jnp.where(keep, pos, 0).astype(jnp.int32)
        oh = jax.nn.one_hot(jnp.sum(loc * m.astype(jnp.int32), axis=-1), C,
                            dtype=jnp.float32)  # (N, C) position one-hot
        d = (m * keep)[:, :, None] * oh[:, None, :]  # (N, E, C)
        gate_p = jnp.sum(probs * m, axis=-1, keepdims=True)  # (N, 1)
        dispatch = dispatch + d
        combine = combine + d * gate_p[:, :, None]
        prior_count = prior_count + jnp.sum(m, axis=0).astype(jnp.int32)

    # renormalize combine weights over selected experts (top-2 norm, ref :303)
    if k > 1:
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)

    drop_frac = 1.0 - kept / (N * k)
    return dispatch, combine, aux_loss, drop_frac


def top_k_serving_weights(logits, k):
    """Per-token combine weights for SERVING: deterministic, capacity-free
    top-k routing.

    The training path (:func:`top_k_gating`) buffers tokens into per-expert
    capacity slots, so a token's position — and whether it is DROPPED — is a
    ``cumsum`` over every other token in the batch. That is fine for a loss
    but poison for a slot-pool decode step: a request's logits would depend
    on which other requests (and which garbage padding rows) share the
    dispatch. Serving instead computes, per token independently:

    - softmax probabilities over the router logits (fp32),
    - the same iterative-argmax top-k selection the training gate uses
      (deterministic, ties resolve to the lowest expert index),
    - combine weight = the selected expert's probability, renormalized over
      the selected k (the Mixtral/top-2 normalization, reference
      sharded_moe.py:303) — no capacity, nothing ever dropped.

    Returns ``(N, E)`` fp32 weights that are zero outside each token's
    top-k. Every token's row is a pure function of its own logits, which is
    what makes scheduler results slot/batch-independent and lets dead
    (span-0) pool rows carry garbage without perturbing live rows.
    """
    return _serving_top_k(logits, k)[0]


def _serving_top_k(logits, k):
    """(weights (N, E), chosen expert ids (N, k) in the order chosen)."""
    N, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    masked = logits.astype(jnp.float32)
    weights = jnp.zeros((N, E), jnp.float32)
    ids = []
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        ids.append(idx.astype(jnp.int32))
        m = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        weights = weights + m * probs
        masked = jnp.where(m > 0, -jnp.inf, masked)
    if k > 1:
        denom = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / jnp.maximum(denom, 1e-9)
    return weights, jnp.stack(ids, axis=-1)


def top_k_serving_choice(logits, k):
    """:func:`top_k_serving_weights` as ``(expert ids (N, k) int32, weights
    (N, k) fp32)``: the same numbers, bit for bit, as the k non-zero entries
    of each row. What a sparse dispatch sorts by; each row is a pure
    function of its logits."""
    weights, ids = _serving_top_k(logits, k)
    return ids, jnp.take_along_axis(weights, ids, axis=-1)


def _top_ids(masked, k):
    """The ``k`` largest of each row of ``masked`` (N, E) by iterative argmax
    (ties resolve to the lowest id): ``(ids (N, k) int32, masked with the
    chosen at -inf)``."""
    ids = []
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        ids.append(idx)
        masked = jnp.where(jnp.arange(masked.shape[-1])[None, :] == idx[:, None], -jnp.inf,
                           masked)
    return jnp.stack(ids, axis=-1), masked


def sigmoid_serving_choice(logits, bias, k, eps=1e-20, n_group=1, topk_group=1):
    """The serving choice of a sigmoid router with a selection bias
    (DeepSeek-V3's rule): ``s = sigmoid(logits)`` in fp32 over ALL
    experts; the k experts are the top k of ``s + bias`` (the same iterative
    argmax as :func:`_serving_top_k`: ties resolve to the lowest expert id);
    their weights are ``s`` itself, WITHOUT the bias, renormalised over the k
    (``w / (sum w + eps)``: DeepSeek-V3's code adds 1e-20, ``lfm2_moe``
    publishes 1e-6; ``TransformerConfig.moe_renorm_eps``). With ``n_group``
    > 1 the choice is GROUP-LIMITED (``n_group`` / ``topk_group``): group
    ``j`` is experts ``[j E / n_group, (j + 1) E / n_group)``, its score the
    sum of its two largest ``s + bias``; a row keeps its ``topk_group`` best
    groups (ties to the lowest group) and chooses its k among their experts.
    One group is the rule without a limit, bit for bit. Returns ``(expert
    ids (N, k) int32, weights (N, k) fp32)``; each row is a pure function of
    its logits."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    masked = s + bias.astype(jnp.float32)
    if n_group > 1:
        N, E = masked.shape
        grouped = masked.reshape(N, n_group, E // n_group)
        _, rest = _top_ids(grouped.reshape(N * n_group, -1), 2)
        # a group's two largest: what the iterative argmax took out of it
        two = jnp.where(jnp.isneginf(rest).reshape(grouped.shape), grouped, 0.0)
        kept, _ = _top_ids(jnp.sum(two, axis=-1), topk_group)  # (N, topk_group)
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        masked = jnp.where(jnp.repeat(keep, E // n_group, axis=-1), masked, -jnp.inf)
    ids, _ = _top_ids(masked, k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
