"""Token sampling: greedy, temperature, top-k, top-p — all per slot.

One traced function covers every policy: the knobs are DATA ([N]
arrays), not static config, so a continuous batch mixing greedy and
nucleus-sampled requests still runs ONE decode executable.  Per-slot
`jax.random` key streams make results independent of slot assignment
and arrival order — the property the engine-vs-sequential-oracle
exactness test pins: request seed -> base key; generated token g is
sampled with ``fold_in(base_key, g)`` wherever and whenever that
request happens to be scheduled.

What a row asks for decides what the call costs, and the code sees it
in its operands: no sampling row, one argmax; a sampling row, a draw
over the full vocabulary; a ``top_k`` or a ``top_p`` among them, 32
rounds of compare-and-sum each (`_select`).  The vocabulary is never
sorted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SamplingParams", "sample_tokens", "token_logprobs",
           "make_base_key"]

NEG_INF = -1e30


class SamplingParams:
    """Per-request sampling policy.

    * ``temperature <= 0`` — greedy (argmax; top_k/top_p ignored).
    * ``top_k > 0``  — keep only the k highest-logit tokens.
    * ``top_p < 1``  — nucleus: keep the smallest prefix of the sorted
      distribution whose mass reaches ``top_p`` (the argmax token is
      always kept, so ``top_p=0`` degrades to greedy-with-noise, never
      to an empty support).
    * ``seed`` — the request's PRNG stream identity.
    """

    __slots__ = ("temperature", "top_k", "top_p", "seed")

    def __init__(self, temperature=1.0, top_k=0, top_p=1.0, seed=0):
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)

    @staticmethod
    def greedy():
        return SamplingParams(temperature=0.0)

    def to_dict(self):
        return {"temperature": self.temperature, "top_k": self.top_k,
                "top_p": self.top_p, "seed": self.seed}


def make_base_key(seed):
    """The request's base PRNG key as a host [2] uint32 row."""
    return np.asarray(jax.random.PRNGKey(int(seed)))


def _ordered(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return bits ^ jnp.where(bits >> 31 == 1, jnp.uint32(0xFFFFFFFF),
                            jnp.uint32(0x80000000))


def _unordered(u):
    """The float32 that `_ordered` sent to ``u``."""
    bits = u ^ jnp.where(u >> 31 == 1, jnp.uint32(0x80000000),
                         jnp.uint32(0xFFFFFFFF))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _select(okeys, weights, target, rows):
    """Per row, the largest uint32 ``t`` for which the ``weights`` of
    the entries with ``okeys >= t`` sum to at least ``target``: a radix
    select, one bit of ``t`` a round from the top down, each round one
    compare-and-sum over the row; never a sort.  ``weights`` None
    counts entries (``target`` is then the k of "k-th largest", any k,
    ties included); float weights give a mass.  With no row of ``rows``
    True no round runs and the answer is 0, below every key.

    On a v5e at [16, 50257] both cut-offs take 0.13 ms where two sorts
    took 2.14; two bits a round (three candidates) 0.15, four 0.27
    (PERF.md section 6, PR 32).

    okeys [N, V] uint32; weights None or [N, V] f32; target [N];
    rows [N] bool.  Returns [N] uint32."""
    def settle(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        above = okeys >= cand[:, None]
        if weights is None:
            total = jnp.sum(above, axis=-1, dtype=jnp.int32)
        else:
            total = jnp.sum(jnp.where(above, weights, 0.0), axis=-1)
        return jnp.where(total >= target, cand, t)

    return jax.lax.fori_loop(
        0, jnp.where(jnp.any(rows), 32, 0), settle,
        jnp.zeros(okeys.shape[0], jnp.uint32))


def _cut(scaled, samples, top_k, top_p):
    """The two filters on scaled logits [N, V], for the rows of
    ``samples``: what top-k leaves and what top-p then leaves of that,
    the dropped entries at NEG_INF.  Both cut-offs come from `_select`
    over the vocabulary; nothing is sorted."""
    v = scaled.shape[1]
    okeys = _ordered(scaled)

    # top-k: mask strictly below the kth-largest logit (k <= 0: off;
    # ties with the kth all stay)
    by_k = samples & (top_k > 0)
    kth = _unordered(_select(okeys, None, jnp.clip(top_k, 1, v), by_k))
    after_k = jnp.where(by_k[:, None] & (scaled < kth[:, None]),
                        NEG_INF, scaled)

    # top-p over the top-k survivors' softmax: a token stays while the
    # mass of the strictly larger ones is below top_p, so the cut-off
    # is the largest t whose mass at or above reaches top_p (the argmax
    # always: t never passes the row's maximum)
    by_p = samples & (top_p < 1.0)
    top = jnp.max(scaled, axis=-1)
    mass = jnp.exp(after_k - top[:, None])
    nucleus = _select(okeys, mass, top_p * jnp.sum(mass, axis=-1), by_p)
    floor = _unordered(jnp.minimum(nucleus, _ordered(top)))
    after_p = jnp.where(by_p[:, None] & (after_k < floor[:, None]),
                        NEG_INF, after_k)
    return after_k, after_p


def sample_tokens(logits, keys, steps, temperature, top_k, top_p):
    """Sample one token per row.

    logits [N, V] (any float dtype); keys [N, 2] uint32 base keys;
    steps [N] int32 (the per-request generated-token index, folded into
    the key); temperature/top_p [N] float; top_k [N] int32.
    Returns [N] int32.  A call in which no row samples is one argmax."""
    with jax.named_scope("sampling"):
        logits = logits.astype(jnp.float32)
        samples = jnp.asarray(temperature) > 0.0
        best = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def draw():
            scaled = logits / jnp.where(samples, temperature, 1.0)[:, None]
            kept = _cut(scaled, samples, top_k, top_p)[1]
            step_keys = jax.vmap(jax.random.fold_in)(keys, steps)
            drawn = jax.vmap(jax.random.categorical)(step_keys, kept)
            return jnp.where(samples, drawn.astype(jnp.int32), best)

        return jax.lax.cond(jnp.any(samples), draw, lambda: best)


def token_logprobs(logits, tokens):
    """Per-row log-probability of ``tokens`` under the RAW policy
    distribution: ``log_softmax(logits)[token]``, temperature-1 and
    unfiltered.  This is deliberately NOT the density of the sampling
    distribution the knobs shaped — the trainer (`paddle_tpu.rl`)
    optimizes the raw softmax and recomputes new-policy logprobs the
    same way, so the PPO ratio ``exp(new - old)`` is consistent no
    matter what temperature/top-k/top-p drew the rollout.

    logits [N, V] (any float dtype); tokens [N] int.  Returns [N] f32.
    """
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(
        lp, tokens.astype(jnp.int32)[:, None], axis=-1)[:, 0]
