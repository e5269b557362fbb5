"""Batched token sampling for the serving engine (DESIGN.md §7.4).

One fused sampler covers greedy, temperature, top-k and nucleus (top-p)
sampling: every slot selects its own behaviour from per-slot parameter
vectors, so a batch mixing greedy and sampled requests still decodes in a
single compiled program. The sorted domain, values and permutation both,
comes from one stable key-value sort; nothing gathers over the vocabulary.

Determinism contract: the PRNG key for request ``rid``'s ``n``-th
generated token is ``fold_in(fold_in(base_key, rid), n)`` — a function of
the request and token index ONLY. Sampling is therefore independent of
batch composition, slot assignment, and prefill chunking, which is what
makes the slot-recycling test (and replay debugging) possible: a request
produces the same tokens under any schedule.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs (0 / 1.0 = disabled)."""

    temperature: float = 0.0  # <= 0 -> greedy (argmax)
    top_k: int = 0            # 0 -> no top-k cut
    top_p: float = 1.0        # 1.0 -> no nucleus cut


GREEDY = SamplingParams()


def request_keys(base_key, rids, n_generated):
    """Per-slot PRNG keys: fold_in(fold_in(base, rid), n). [B] -> [B] keys."""
    def one(rid, n):
        return jax.random.fold_in(jax.random.fold_in(base_key, rid), n)
    return jax.vmap(one)(rids, n_generated)


def sample_tokens(logits, keys, temperature, top_k, top_p):
    """Sample one token per slot. All modes in one jit-able function.

    logits: [B, V] (any float dtype); keys: [B] PRNG keys (request_keys);
    temperature/top_p: [B] f32; top_k: [B] i32. Returns [B] int32.

    Filtering runs in the sorted domain (descending logits), which one
    stable key-value sort of ``(-scaled, iota)`` yields whole: the sorted
    values and the permutation, ties in index order as ``jnp.argsort``
    breaks them, with no gather over the vocabulary. Top-k keeps rank < k;
    top-p keeps the smallest prefix whose mass reaches p (the head token
    always survives, so the result is never empty); the pick is a
    Gumbel-max over the surviving entries, mapped back through the sort
    permutation.
    """
    V = logits.shape[-1]

    def one(lg, key, t, k, p):
        lg = lg.astype(jnp.float32)
        greedy = t <= 0.0
        scaled = lg / jnp.maximum(t, 1e-6)
        neg, order = lax.sort_key_val(-scaled, lax.iota(jnp.int32, V),
                                      is_stable=True)  # descending
        vals = -neg
        rank = jnp.arange(V)
        keep = rank < jnp.where(k <= 0, V, k)
        probs = jax.nn.softmax(vals)
        cum = jnp.cumsum(probs)
        keep &= (cum - probs) < p  # mass BEFORE this entry still below p
        keep |= rank == 0          # head always survives
        vals = jnp.where(keep, vals, -jnp.inf)
        g = jax.random.gumbel(key, (V,), jnp.float32)
        pick = order[jnp.argmax(vals + g)]
        return jnp.where(greedy, jnp.argmax(lg), pick).astype(jnp.int32)

    return jax.vmap(one)(logits, keys, temperature, top_k, top_p)
