"""Multi-head latent attention over packed documents.

Queries per head; ONE compressed key-value latent a token
(``kv_lora_rank`` wide) plus one rotary key shared by all heads,
up-projected per head into the keys' non-rotary part and the values
(DeepSeek-V2, arXiv:2405.04434 §2.1). :func:`project` makes queries,
keys and values from the residual; :func:`attend` is the softmax
attention, causal and inside a document only, over blocks of queries
against the keys up to the block's end, so that no ``[S, S]`` score
matrix is ever whole in memory and the blocks above the diagonal are
never computed; :func:`output` projects back.

Matrix products take operands of ``LatentShape.dtype`` (bfloat16) and
accumulate in float32; norms, the rotary embedding and the softmax are
float32.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from multiverso_tpu import telemetry


class LatentShape(NamedTuple):
    heads: int
    nope: int           # qk_nope_head_dim
    rope: int           # qk_rope_head_dim
    v: int              # v_head_dim
    rank: int           # kv_lora_rank
    eps: float          # rms_norm_eps
    dtype: str = "bfloat16"     # of the matrix products' operands


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


class Rotary(NamedTuple):
    """Frequencies of the rotary dims, the factor on cos and sin, and
    the scale of the attention scores, from ``rope_theta`` and
    ``rope_scaling`` (YaRN, arXiv:2309.00071, or none)."""
    inv_freq: np.ndarray
    magnitude: float
    score_scale: float

    @classmethod
    def from_config(cls, *, rope_dim: int, qk_dim: int, theta: float,
                    scaling: Optional[dict]) -> "Rotary":
        plain = 1.0 / theta ** (np.arange(0, rope_dim, 2,
                                          dtype=np.float64) / rope_dim)
        if not scaling:
            return cls(plain.astype(np.float32), 1.0, qk_dim ** -0.5)
        if scaling.get("type", "yarn") != "yarn":
            raise NotImplementedError(
                f"rope_scaling type {scaling['type']!r}: only yarn")
        factor = scaling["factor"]
        orig = scaling["original_max_position_embeddings"]

        def correction(rotations):
            return rope_dim * math.log(orig / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(correction(scaling["beta_fast"])), 0)
        high = min(math.ceil(correction(scaling["beta_slow"])),
                   rope_dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rope_dim // 2, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        inv_freq = plain / factor * ramp + plain * (1.0 - ramp)
        all_dim = yarn_mscale(factor, scaling.get("mscale_all_dim", 0))
        return cls(inv_freq.astype(np.float32),
                   yarn_mscale(factor, scaling.get("mscale", 1)) / all_dim,
                   qk_dim ** -0.5 * (all_dim * all_dim if
                                     scaling.get("mscale_all_dim") else 1))


def rms_norm(x, weight, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _dot(a, b, dtype):
    return jnp.dot(a.astype(dtype), b.astype(dtype),
                   preferred_element_type=jnp.float32)


def _rotate(x, cos, sin):
    """Half-split rotary: dim ``i`` pairs with ``i + d/2``."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def project(x, pos, norm_w, w_q, w_kv_a, kv_norm_w, w_kv_b,
            shape: LatentShape, rotary: Rotary):
    """From the residual ``x`` [B, S, D] float32 and the positions
    inside the documents: ``q_nope`` [B, S, H, nope], ``q_pe``
    [B, S, H, rope], ``k_nope`` [B, S, H, nope], ``k_pe`` [B, S, rope]
    (one for all heads), ``v`` [B, S, H, v], all ``shape.dtype``."""
    @telemetry.scope("lm.mla.project")
    def run(x, pos, norm_w, w_q, w_kv_a, kv_norm_w, w_kv_b):
        B, S, _ = x.shape
        H, nope, rope, vd, rank, eps, dtype = shape
        h = rms_norm(x, norm_w, eps)
        q = _dot(h, w_q, dtype).reshape(B, S, H, nope + rope)
        kv_a = _dot(h, w_kv_a, dtype)
        c = rms_norm(kv_a[..., :rank], kv_norm_w, eps)
        kv = _dot(c, w_kv_b, dtype).reshape(B, S, H, nope + vd)
        ang = pos[..., None].astype(jnp.float32) \
            * jnp.asarray(rotary.inv_freq)
        cos = jnp.cos(ang) * rotary.magnitude
        sin = jnp.sin(ang) * rotary.magnitude
        q_pe = _rotate(q[..., nope:], cos[:, :, None], sin[:, :, None])
        k_pe = _rotate(kv_a[..., rank:], cos, sin)
        bf = lambda a: a.astype(dtype)
        return (bf(q[..., :nope]), bf(q_pe), bf(kv[..., :nope]), bf(k_pe),
                bf(kv[..., nope:]))
    return run(x, pos, norm_w, w_q, w_kv_a, kv_norm_w, w_kv_b)


def _attend_block(q_nope, q_pe, k_nope, k_pe, v, doc, first, size, scale):
    """Queries ``first … first + size - 1`` of ONE sequence against its
    keys ``0 … first + size - 1``; the arguments are the whole
    sequence's (``q_*`` [S, H, d], ``k_nope`` / ``v`` [S, H, d], ``k_pe``
    [S, rope]) and are cut here, so that what the backward pass keeps of
    a block is the sequence itself and no copy of a slice."""
    end = first + size
    q_nope, q_pe, q_doc = q_nope[first:end], q_pe[first:end], doc[first:end]
    k_nope, k_pe, v, k_doc = k_nope[:end], k_pe[:end], v[:end], doc[:end]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("qhd,kd->hqk", q_pe, k_pe,
                           preferred_element_type=jnp.float32)) * scale
    Q, K = q_doc.shape[0], k_doc.shape[0]
    allowed = (first + jnp.arange(Q)[:, None] >= jnp.arange(K)[None, :]) \
        & (q_doc[:, None] == k_doc[None, :])
    scores = jnp.where(allowed[None], scores, -1e30)
    prob = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("hqk,khd->qhd", prob, v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def attend(q_nope, q_pe, k_nope, k_pe, v, doc, *, scale: float,
           block: int):
    """Softmax attention, causal and inside a document: a token attends
    to the earlier tokens (and itself) whose ``doc`` id equals its own.
    One sequence at a time, ``block`` queries at a time against the keys
    up to the block's end; each block is recomputed in the backward
    pass. Returns [B, S, H * v] in the operands' dtype."""
    @telemetry.scope("lm.mla.attend")
    def run(q_nope, q_pe, k_nope, k_pe, v, doc):
        S = doc.shape[1]
        Q = min(block, S)
        if S % Q:
            raise ValueError(f"sequence {S} is no multiple of the "
                             f"attention block {Q}")
        one = jax.checkpoint(_attend_block, static_argnums=(6, 7, 8))

        def sequence(args):
            return jnp.concatenate([one(*args, a, Q, scale)
                                    for a in range(0, S, Q)])

        out = lax.map(sequence, (q_nope, q_pe, k_nope, k_pe, v, doc))
        return out.reshape(out.shape[0], S, -1)
    return run(q_nope, q_pe, k_nope, k_pe, v, doc)


@telemetry.scope("lm.mla.project")
def output(o, w_o):
    """``o`` [B, S, H * v] times ``w_o`` [D, H * v] transposed."""
    return lax.dot_general(o, w_o.astype(o.dtype), (((2,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)
