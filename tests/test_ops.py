"""Pallas LDA sampler kernel tests (interpret mode on the CPU mesh) —
numpy-oracle validation of the fused posterior+two-level-inverse-CDF
doc-blocked samplers (SURVEY.md §5: numeric parity against a NumPy oracle)."""

import numpy as np
import pytest

from multiverso_tpu.ops import (gibbs_sample_docblock,
                                gibbs_sample_docblock_build)

C, L = 2, 128
K = C * L
ALPHA, BETA = 0.1, 0.01


def oracle(A, W, sinv, zi, msk, u1, u2):
    """The kernel's math in numpy (f32 like the kernel)."""
    B = A.shape[0]
    kk = np.arange(K, dtype=np.int32).reshape(1, C, L)
    soh = ((kk == zi[:, None, None]) & (msk[:, None, None] > 0))
    Af = (A - soh).astype(np.float32)
    Wf = (W - soh).astype(np.float32)
    probs = np.maximum((Af + np.float32(ALPHA)) * (Wf + np.float32(BETA)),
                       0.0) * sinv[None]
    cs = probs.sum(-1, dtype=np.float32)
    ccdf = np.cumsum(cs, axis=1, dtype=np.float32)
    t1 = u1 * ccdf[:, -1]
    c = np.minimum((ccdf < t1[:, None]).sum(1), C - 1)
    sub = probs[np.arange(B), c].astype(np.float32)
    scdf = np.cumsum(sub, axis=1, dtype=np.float32)
    t2 = u2 * scdf[:, -1]
    lane = np.minimum((scdf < t2[:, None]).sum(1), L - 1)
    zn = (c * L + lane).astype(np.int32)
    return np.where(msk > 0, zn, zi)


def _inputs(b, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.integers(0, 6, (b, C, L)).astype(np.int32)
    W = rng.integers(0, 60, (b, C, L)).astype(np.int32)
    nk = rng.integers(500, 5000, (C, L)).astype(np.int32)
    sinv = (1.0 / (nk + 50 * BETA)).astype(np.float32)
    zi = rng.integers(0, K, b).astype(np.int32)
    msk = np.ones(b, np.int32)
    msk[-3:] = 0  # padded lanes
    u1 = rng.random(b).astype(np.float32)
    u2 = rng.random(b).astype(np.float32)
    return A, W, sinv, zi, msk, u1, u2


def _block_counts(zi, drel, msk, nb, maxd, tb):
    """The blocked doc counts that ARE counts(z): what the streamed
    kernel builds in VMEM and the resident one reads."""
    ndk = np.zeros((nb, maxd, K), np.int16)
    blk = np.repeat(np.arange(nb), tb)
    np.add.at(ndk, (blk, drel, zi), msk.astype(np.int16))
    return ndk.reshape(nb, maxd, C, L), blk


def _build_inputs(nb, maxd, tb, seed):
    rng = np.random.default_rng(seed)
    b = nb * tb
    W = rng.integers(0, 60, (b, C, L)).astype(np.int32)
    nk = rng.integers(500, 5000, (C, L)).astype(np.int32)
    sinv = (1.0 / (nk + 50 * BETA)).astype(np.float32)
    # few topics a block, so a doc row holds counts above one
    zi = rng.integers(0, 12, b).astype(np.int32)
    drel = rng.integers(0, maxd, b).astype(np.int32)
    msk = np.ones(b, np.int32)
    msk[-3:] = 0  # padded lanes
    u1 = rng.random(b).astype(np.float32)
    u2 = rng.random(b).astype(np.float32)
    return W, sinv, zi, drel, msk, u1, u2


class TestGibbsSampleDocblock:
    def test_build_matches_numpy_oracle(self, mesh8):
        """The streamed kernel builds its block's doc counts from
        (zi, drel) in VMEM: its draws are the oracle's on those counts,
        and bit-equal to the resident kernel handed the same counts."""
        NB, MAXD, TB = 3, 4, 16
        W, sinv, zi, drel, msk, u1, u2 = _build_inputs(NB, MAXD, TB, 0)
        znew, _ = gibbs_sample_docblock_build(
            W, sinv, zi, drel, msk, u1, u2, alpha=ALPHA, beta=BETA,
            tb=TB, maxd=MAXD, interpret=True)
        znew = np.asarray(znew)
        ndk_blk, blk = _block_counts(zi, drel, msk, NB, MAXD, TB)
        want = oracle(ndk_blk[blk, drel].astype(np.int32), W, sinv, zi,
                      msk, u1, u2)
        # f32 CDF-boundary ties can flip a draw by one lane; demand
        # near-total agreement, not bit equality
        agree = float(np.mean(znew == want))
        assert agree >= 0.98, f"only {agree:.3f} agreement"
        # padded lanes keep their old assignment
        np.testing.assert_array_equal(znew[-3:], zi[-3:])
        _, resident, _ = gibbs_sample_docblock(
            ndk_blk, W, sinv, zi, drel, msk, u1, u2, alpha=ALPHA,
            beta=BETA, tb=TB, interpret=True)
        np.testing.assert_array_equal(znew, np.asarray(resident))

    def test_build_nk_delta_consistent(self, mesh8):
        NB, MAXD, TB = 4, 4, 16
        W, sinv, zi, drel, msk, u1, u2 = _build_inputs(NB, MAXD, TB, 1)
        znew, nkd = gibbs_sample_docblock_build(
            W, sinv, zi, drel, msk, u1, u2, alpha=ALPHA, beta=BETA,
            tb=TB, maxd=MAXD, interpret=True)
        znew, nkd = np.asarray(znew), np.asarray(nkd)
        want = np.zeros(K, np.int64)
        for t in range(len(zi)):
            if msk[t]:
                want[znew[t]] += 1
                want[zi[t]] -= 1
        np.testing.assert_array_equal(nkd.reshape(-1), want)
        assert nkd.sum() == 0  # token count conserved

    def test_samples_follow_posterior(self, mesh8):
        # one token repeated with fresh uniforms, every block holding
        # the same one document row: the empirical topic distribution
        # must match the collapsed posterior
        rng = np.random.default_rng(2)
        TB, MAXD = 512, 2
        b, nb = 4096, 4096 // TB
        A1 = rng.integers(0, 6, (1, C, L)).astype(np.int16)
        W1 = rng.integers(0, 60, (1, C, L)).astype(np.int32)
        nk = rng.integers(500, 5000, (C, L)).astype(np.int32)
        sinv = (1.0 / (nk + 50 * BETA)).astype(np.float32)
        ndk_blk = np.zeros((nb, MAXD, C, L), np.int16)
        ndk_blk[:, 1] = A1
        W = np.repeat(W1, b, 0)
        zi = np.zeros(b, np.int32)  # self-removal hits topic 0 only
        drel = np.ones(b, np.int32)
        msk = np.ones(b, np.int32)
        u1 = rng.random(b).astype(np.float32)
        u2 = rng.random(b).astype(np.float32)
        _, znew, _ = gibbs_sample_docblock(
            ndk_blk, W, sinv, zi, drel, msk, u1, u2, alpha=ALPHA,
            beta=BETA, tb=TB, interpret=True)
        counts = np.bincount(np.asarray(znew), minlength=K) / b
        Af = (A1[0].reshape(-1) - (np.arange(K) == 0)).astype(np.float64)
        Wf = (W1[0].reshape(-1) - (np.arange(K) == 0)).astype(np.float64)
        p = np.maximum((Af + ALPHA) * (Wf + BETA), 0) \
            * sinv.reshape(-1).astype(np.float64)
        p /= p.sum()
        # total-variation distance small for 4096 draws over 256 topics
        tv = 0.5 * np.abs(counts - p).sum()
        assert tv < 0.12, tv

    def test_docblock_matches_oracle_and_updates_counts(self, mesh8):
        rng = np.random.default_rng(5)
        NB, MAXD, TB = 3, 4, 16
        ndk_blk = rng.integers(0, 6, (NB, MAXD, C, L)).astype(np.int16)
        b = NB * TB
        W = rng.integers(0, 60, (b, C, L)).astype(np.int32)
        nk = rng.integers(500, 5000, (C, L)).astype(np.int32)
        sinv = (1.0 / (nk + 50 * BETA)).astype(np.float32)
        zi = rng.integers(0, K, b).astype(np.int32)
        drel = rng.integers(0, MAXD, b).astype(np.int32)
        msk = np.ones(b, np.int32)
        msk[-2:] = 0
        u1 = rng.random(b).astype(np.float32)
        u2 = rng.random(b).astype(np.float32)
        ndk_out, znew, nkd = gibbs_sample_docblock(
            ndk_blk, W, sinv, zi, drel, msk, u1, u2,
            alpha=ALPHA, beta=BETA, tb=TB, interpret=True)
        ndk_out, znew, nkd = map(np.asarray, (ndk_out, znew, nkd))
        # per-token draw equals the flat-kernel oracle on gathered A rows
        blk = np.repeat(np.arange(NB), TB)
        A = ndk_blk[blk, drel].astype(np.int32)
        want = oracle(A, W, sinv, zi, msk, u1, u2)
        agree = float(np.mean(znew == want))
        assert agree >= 0.98, agree
        np.testing.assert_array_equal(znew[-2:], zi[-2:])
        # blocked counts moved exactly (-1 old, +1 new per real token)
        want_ndk = ndk_blk.astype(np.int64).copy()
        for t in range(b):
            if msk[t]:
                want_ndk[blk[t], drel[t]].reshape(-1)[zi[t]] -= 1
                want_ndk[blk[t], drel[t]].reshape(-1)[znew[t]] += 1
        np.testing.assert_array_equal(ndk_out.astype(np.int64), want_ndk)
        # summary delta consistent and conserving
        want_nkd = np.zeros(K, np.int64)
        for t in range(b):
            if msk[t]:
                want_nkd[znew[t]] += 1
                want_nkd[zi[t]] -= 1
        np.testing.assert_array_equal(nkd.reshape(-1), want_nkd)

    def test_bad_lane_dim_raises(self, mesh8):
        with pytest.raises(ValueError, match="last dim"):
            gibbs_sample_docblock(
                np.zeros((1, 2, 2, 64), np.int16),
                np.zeros((8, 2, 64), np.int32),
                np.zeros((2, 64), np.float32), np.zeros(8, np.int32),
                np.zeros(8, np.int32), np.ones(8, np.int32),
                np.zeros(8, np.float32), np.zeros(8, np.float32),
                alpha=0.1, beta=0.01, tb=8, interpret=True)
