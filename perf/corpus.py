"""Synthetic corpora from a seed, by vectorised inverse CDF (no
``rng.choice(p=...)``, which takes minutes at these sizes).

Every seed gives the same multiset of document lengths (the quantiles of
one fixed distribution) in another order, and words drawn from the same
zipf law, so the work of a run does not depend on the seed.
"""

from __future__ import annotations

import numpy as np


def prng_key(seed: int, salt: int = 0):
    """A jax key from any whole-number seed (the driver's pass 2**31) and
    a salt that keeps the benchmark's streams apart."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, salt)


def zipf_words(seed: int, n: int, vocab: int, exponent: float):
    """``n`` word ids in ``[0, vocab)``, id 0 the most frequent, drawn on
    the default device in one jitted call: the inverse CDF of the
    continuous zipf (bounded Pareto) law with ``exponent`` != 1, floored.
    Returns a device array."""
    import jax
    import jax.numpy as jnp

    a = 1.0 - float(exponent)
    top = float(vocab + 1) ** a - 1.0

    @jax.jit
    def draw(key):
        u = jax.random.uniform(key, (n,), jnp.float32)
        x = jnp.power(1.0 + u * top, 1.0 / a)
        return jnp.clip(x.astype(jnp.int32) - 1, 0, vocab - 1)

    return draw(prng_key(seed, 1))


def doc_lengths(seed: int, docs: int, mean: float, sd: float,
                lo: int, hi: int) -> np.ndarray:
    """``docs`` lengths: the (i + 0.5) / docs quantiles of a gamma law
    with the given mean and standard deviation, clipped to [lo, hi] —
    the same multiset for every seed — permuted by the seed."""
    shape = (mean / sd) ** 2
    fixed = np.random.default_rng(0x5EED).gamma(shape, mean / shape,
                                                docs)
    lens = np.clip(np.rint(np.sort(fixed)), lo, hi).astype(np.int64)
    return np.random.default_rng(int(seed)).permutation(lens)
