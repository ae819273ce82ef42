"""The gated short convolution over packed documents (the ``conv``
operator of the LFM2 family, ``model_type`` ``lfm2_moe``).

From the block's normed input ``u`` [B, S, D]: ``[B | C | X] = u W_in``
(three D-wide parts in that order, no bias), ``z = B * X``, each channel
of ``z`` through its own causal filter of ``conv_L_cache`` taps (no
bias, no activation), ``Op = (C * c) W_out``. A tap that would reach
into an earlier document — or before the sequence — reads 0: the rule
(and the code) of :func:`multiverso_tpu.ops.gated_delta.causal_taps`,
the counterpart of attention's document mask.

:func:`project_in` and :func:`project_out` are the two products
(operands of the products' dtype, float32 accumulation; scope
``lm.conv.project``); :func:`mix` is the two gates and the taps in
float32 (scope ``lm.conv.mix``). All three are plain XLA: the mix is
elementwise over a token and its ``taps - 1`` predecessors, which the
compiler fuses into one pass over ``B | C | X``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from multiverso_tpu import telemetry
from multiverso_tpu.ops.gated_delta import causal_taps


def project_in(u, w_in, dtype):
    """``B | C | X`` [B, S, 3 D] float32 from ``u`` [B, S, D] and
    ``w_in`` [D, 3 D]."""
    @telemetry.scope("lm.conv.project")
    def run(u, w_in):
        return jnp.dot(u.astype(dtype), w_in.astype(dtype),
                       preferred_element_type=jnp.float32)
    return run(u, w_in)


@telemetry.scope("lm.conv.mix")
def mix(bcx, taps, doc):
    """``C * taps(B * X)`` [B, S, D] float32: ``bcx`` [B, S, 3 D],
    ``taps`` [K, D], ``doc`` [B, S] the tokens' document ids."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return c * causal_taps(b * x, taps, doc)


def project_out(y, w_out, dtype):
    """``y`` [B, S, D] times ``w_out`` [D, D] (in x out)."""
    @telemetry.scope("lm.conv.project")
    def run(y, w_out):
        return lax.dot_general(y.astype(dtype), w_out.astype(dtype),
                               (((2,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    return run(y, w_out)
