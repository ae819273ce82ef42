"""Plain reference for the LightLDA cells: collapsed Gibbs sampling of
LDA in straightforward ``jax.numpy`` and float32, no kernel, no blocked
layout, no mirror. It imports nothing of the program.

Semantics, as the configuration states them (``stale_words``,
``doc_blocked``, ``batch_tokens``): a sweep draws a new topic for every
token from the collapsed posterior

    p(k) ~ (n_dk - [k = z] + alpha) * (n_wk - [k = z] + beta)
           / (n_k + V * beta)

with the doc-topic and word-topic counts as they stood when the sweep
began (a document is visited once a sweep and all of its tokens are
drawn together; word counts are refreshed once a sweep) and the topic
totals ``n_k`` carried along from chunk to chunk. The draw is the
inverse CDF: the running sum of ``p`` over the topics, in order, against
one uniform. ``precision`` is the type of the posterior and of that
running sum: ``float32`` is the reference; ``bfloat16`` is the CONTROL
of ``correct`` (every product and every step of the running sum rounded
to bfloat16) and is never what a run is compared with.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CHUNK = 1 << 15          # tokens drawn together; [CHUNK, K] f32 is 128 MB


def _bf16(x):
    # reduce_precision is never elided (a convert pair may be)
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def pad_stream(words: np.ndarray, docs: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad the doc-contiguous stream to whole chunks; returns
    [chunks, CHUNK] words, docs and a 0/1 mask."""
    n = len(words)
    npad = -(-n // CHUNK) * CHUNK
    w = np.zeros(npad, np.int32)
    d = np.zeros(npad, np.int32)
    m = np.zeros(npad, np.int32)
    w[:n], d[:n], m[:n] = words, docs, 1
    return (w.reshape(-1, CHUNK), d.reshape(-1, CHUNK),
            m.reshape(-1, CHUNK))


@functools.partial(jax.jit, static_argnames=("D", "V", "K"))
def counts(z, w, d, m, *, D: int, V: int, K: int):
    """(n_dk [D, K], n_wk [V, K], n_k [K]) int32 from assignments. The
    scatter is written over [rows, K / 128, 128] because XLA:TPU takes
    minutes over the same adds into [rows, K]; the counts are the same."""
    zf, wf, df, mf = (x.reshape(-1) for x in (z, w, d, m))
    if K % 128 == 0:
        hi, lo = zf // 128, zf % 128
        ndk = jnp.zeros((D, K // 128, 128), jnp.int32) \
            .at[df, hi, lo].add(mf).reshape(D, K)
        nwk = jnp.zeros((V, K // 128, 128), jnp.int32) \
            .at[wf, hi, lo].add(mf).reshape(V, K)
    else:
        ndk = jnp.zeros((D, K), jnp.int32).at[df, zf].add(mf)
        nwk = jnp.zeros((V, K), jnp.int32).at[wf, zf].add(mf)
    nk = jnp.zeros((K,), jnp.int32).at[zf].add(mf)
    return ndk, nwk, nk


def _running_sum(p, precision: str):
    if precision == "float32":
        # the running sum over K, taken over blocks of 128 topics and
        # the blocks' totals: the same sums in another order, which
        # XLA:TPU takes a third of the time over
        n, K = p.shape
        if K % 128:
            return jnp.cumsum(p, axis=1)
        inner = jnp.cumsum(p.reshape(n, K // 128, 128), axis=2)
        before = jnp.cumsum(inner[:, :, -1], axis=1) - inner[:, :, -1]
        return (inner + before[:, :, None]).reshape(n, K)
    # the plain running sum c_k = c_{k-1} + p_k, every step rounded

    def step(c, col):
        c = _bf16(c + col)
        return c, c

    _, cols = lax.scan(step, jnp.zeros(p.shape[0], p.dtype), p.T)
    return cols.T


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "V",
                                             "precision", "keep"),
                   donate_argnums=(0,))
def sweep(z, w, d, m, ndk, nwk, nk, key, *, alpha: float, beta: float,
          V: int, precision: str = "float32", keep: int = 1):
    """One sweep; returns (z', n_k'). ``keep`` > 1 is a planted FAULT for
    the tests of ``correct``: only every ``keep``-th chunk is drawn."""
    K = nk.shape[0]
    vbeta = V * beta
    rnd = _bf16 if precision == "bfloat16" else (lambda x: x)

    def body(nk, xs):
        i, zc, wc, dc, mc, k = xs
        oh = jax.nn.one_hot(zc, K, dtype=jnp.float32) * mc[:, None]
        A = ndk[dc].astype(jnp.float32) - oh + alpha
        W = nwk[wc].astype(jnp.float32) - oh + beta
        sinv = rnd(1.0 / (nk.astype(jnp.float32) + vbeta))
        p = rnd(rnd(jnp.maximum(rnd(A) * rnd(W), 0.0)) * sinv[None])
        cdf = _running_sum(p, precision)
        u = jax.random.uniform(k, (zc.shape[0],), jnp.float32)
        t = rnd(u * cdf[:, -1])
        znew = jnp.minimum((cdf < t[:, None]).sum(1), K - 1) \
            .astype(jnp.int32)
        live = (mc > 0) & (i % keep == 0)
        znew = jnp.where(live, znew, zc)
        nk = nk.at[znew].add(mc).at[zc].add(-mc)
        return nk, znew

    n = z.shape[0]
    keys = jax.random.split(key, n)
    nk, znew = lax.scan(body, nk, (jnp.arange(n), z, w, d, m, keys))
    return znew, nk


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "V",
                                             "every"))
def loglik(z, w, d, m, ndk, nwk, nk, *, alpha: float, beta: float,
           V: int, every: int = 8):
    """Mean predictive log-likelihood a token, log sum_k theta_dk *
    phi_wk under the counts' point estimates, over every ``every``-th
    chunk of the stream."""
    K = nk.shape[0]
    S = nk.astype(jnp.float32) + V * beta
    nd = ndk.sum(1).astype(jnp.float32)

    def body(tot, xs):
        wc, dc, mc = xs
        theta = (ndk[dc].astype(jnp.float32) + alpha) \
            / (nd[dc][:, None] + K * alpha)
        phi = (nwk[wc].astype(jnp.float32) + beta) / S[None]
        ll = jnp.log(jnp.maximum((theta * phi).sum(1), 1e-30))
        mf = mc.astype(jnp.float32)
        return (tot[0] + (ll * mf).sum(), tot[1] + mf.sum()), None

    (s, c), _ = lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                         (w[::every], d[::every], m[::every]))
    return s / c


def doc_topics(ndk) -> float:
    """Mean number of distinct topics a document's tokens hold."""
    return float((ndk > 0).sum(1).astype(jnp.float32).mean())


def stats(cnt, z, w, d, m, *, alpha: float, beta: float, V: int,
          every: int) -> dict:
    """What ``correct`` reads off a set of assignments and their counts
    ``cnt``: the predictive log-likelihood a token, the topic sizes in
    order of size, and the mean number of distinct topics a document
    holds."""
    ndk, nwk, nk = cnt
    return {"loglik": float(loglik(z, w, d, m, ndk, nwk, nk, alpha=alpha,
                                   beta=beta, V=V, every=every)),
            "topic_sizes": np.sort(np.asarray(nk)),
            "doc_topics": doc_topics(ndk)}


def follow(z, w, d, m, key, sweeps: int, *, D: int, V: int, K: int,
           alpha: float, beta: float, every: int,
           precision: str = "float32", keep: int = 1,
           frozen: bool = False) -> list:
    """Follow ``sweeps`` sweeps from the assignments ``z`` and read
    :func:`stats` (with the share of tokens that moved) after each.
    ``frozen`` is the planted fault of a state returned unchanged."""
    kw = dict(alpha=alpha, beta=beta, V=V)
    cnt = counts(z, w, d, m, D=D, V=V, K=K)
    out = []
    for i in range(1, sweeps + 1):
        z_old = z + 0
        if not frozen:
            z, _ = sweep(z, w, d, m, *cnt, jax.random.fold_in(key, i),
                         precision=precision, keep=keep, **kw)
            cnt = counts(z, w, d, m, D=D, V=V, K=K)
        st = stats(cnt, z, w, d, m, every=every, **kw)
        st["moved_share"] = moved_share(z_old, z, m)
        out.append(st)
    return out


def random_start(key, shape, K: int):
    """The reference's own uniform start, from its key."""
    return jax.random.randint(jax.random.fold_in(key, 0), shape, 0, K,
                              jnp.int32)


def gaps(prog: dict, ref: dict, tokens: int) -> dict:
    """The numbers compared, program against reference, each a share of
    the reference's reading."""
    return {
        "loglik_gap": abs(prog["loglik"] - ref["loglik"])
        / abs(ref["loglik"]),
        "topic_sizes_gap": float(np.abs(
            prog["topic_sizes"] - ref["topic_sizes"]).sum())
        / (2.0 * tokens),
        "doc_topics_gap": abs(prog["doc_topics"] - ref["doc_topics"])
        / ref["doc_topics"],
        "moved_share_gap": abs(prog["moved_share"] - ref["moved_share"])
        / ref["moved_share"],
    }


def moved_share(z_before, z_after, m) -> float:
    """Share of the real tokens whose topic changed."""
    mv = ((z_before != z_after) & (m > 0)).sum()
    return float(mv) / float((m > 0).sum())
