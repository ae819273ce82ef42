"""Plain reference for the word2vec cell: skip-gram with negative
sampling and plain SGD in straightforward ``jax.numpy``, float32, dot
products at the highest precision. It imports nothing of the program.

Semantics, as the configuration states them. Input vectors start uniform
in +-0.5/dim from ``numpy.random.default_rng(seed)``, output vectors at
zero. A call is ``steps_per_call`` steps in order; a step takes
``batch_size`` (centre, context) pairs, draws ``negative`` noise words a
pair from word2vec's unigram table (counts ** 0.75, ``table_size``
slots, one uniform a draw from the call's key: ``fold_in(key(seed),
call)``, split once a step), forms the logistic loss on the centre's
input vector against the 1 + negative output vectors, and adds
``-lr * gradient`` of every pair to both tables at once (duplicates
add). ``dtype`` is the type of the tables and of the arithmetic:
``float32`` is the reference; ``bfloat16`` only ever the control.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def init_input_vectors(seed: int, vocab: int, dim: int,
                       rows: int = 1 << 17) -> np.ndarray:
    """[vocab, dim] float32: ``default_rng(seed).uniform(-0.5/dim,
    0.5/dim, (vocab, dim))`` cast to float32, drawn in blocks of rows on
    a few threads. A uniform double takes one step of PCG64, so a block
    starts from the seeded stream advanced by its offset: the same
    numbers as one serial draw, in a quarter of the time."""
    out = np.empty((vocab, dim), np.float32)

    def block(lo: int) -> None:
        bits = np.random.PCG64(seed)
        bits.advance(lo * dim)
        n = min(rows, vocab - lo)
        out[lo:lo + n] = np.random.Generator(bits).uniform(
            -0.5 / dim, 0.5 / dim, (n, dim))

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(block, range(0, vocab, rows)))
    return out


def unigram_table(counts: np.ndarray, power: float, size: int
                  ) -> np.ndarray:
    """word2vec's InitUnigramTable: slot i holds the word whose share of
    the counts ** power mass covers (i + 0.5) / size."""
    p = counts.astype(np.float64) ** power
    p = (p / p.sum()).astype(np.float32)
    cum = np.cumsum(p.astype(np.float64))
    cum /= cum[-1]
    return np.searchsorted(cum, (np.arange(size) + 0.5) / size) \
        .astype(np.int32)


def learning_rates(call: int, planned_calls: int, steps: int, lr: float,
                   min_frac: float) -> np.ndarray:
    """Linear decay over the planned calls, floored."""
    hi = lr * (1.0 - min(call / planned_calls, 1.0))
    lo = lr * (1.0 - min((call + 1) / planned_calls, 1.0))
    return np.maximum(np.linspace(hi, lo, steps), lr * min_frac) \
        .astype(np.float32)


@functools.partial(jax.jit, static_argnames=("negative",),
                   donate_argnums=(0, 1))
def call(w_in, w_out, src, tgt, key, lrs, table, *, negative: int):
    """One call: [S, B] centres and contexts; returns the tables and the
    mean of the steps' losses."""
    dt = w_in.dtype

    def step(carry, xs):
        w_in, w_out = carry
        s, t, k, lr = xs
        u01 = jax.random.uniform(k, (s.shape[0], negative))
        negs = table[(u01 * table.shape[0]).astype(jnp.int32)]
        ids = jnp.concatenate([t[:, None], negs], axis=1)
        v = w_in[s]
        u = w_out[ids]
        logits = jnp.einsum("bd,bkd->bk", v, u, precision="highest")
        labels = jnp.zeros_like(logits).at[:, 0].set(1.0)
        loss = -jnp.mean(jnp.sum(
            labels * jax.nn.log_sigmoid(logits)
            + (1.0 - labels) * jax.nn.log_sigmoid(-logits), axis=1))
        g = (jax.nn.sigmoid(logits) - labels) * lr.astype(dt)
        grad_v = jnp.einsum("bk,bkd->bd", g, u, precision="highest")
        grad_u = g[:, :, None] * v[:, None, :]
        w_out = w_out.at[ids.reshape(-1)].add(
            -grad_u.reshape(-1, u.shape[-1]).astype(dt))
        w_in = w_in.at[s].add(-grad_v.astype(dt))
        return (w_in, w_out), loss.astype(jnp.float32)

    keys = jax.random.split(key, src.shape[0])
    (w_in, w_out), losses = lax.scan(step, (w_in, w_out),
                                     (src, tgt, keys, lrs))
    return w_in, w_out, losses.mean()


@jax.jit
def change_norm(w, w0):
    """Norm of the change of one table, in float32."""
    d = w.astype(jnp.float32) - w0.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(d * d))
