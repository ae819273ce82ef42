"""The one general generator of key-value traffic: parameter-server
workers over hashed categorical features. It reads a traffic mix (a data
file: clients, examples a minibatch, the loop) and a configuration's
``program`` block (fields, their cardinalities and skew) and yields, for
(seed, client, iteration), that worker's next minibatch: the unique keys
of its examples and one pre-summed delta a key. jax-free, so the client
processes and the reference replay share it; every seed draws from the
same laws."""

from __future__ import annotations

import numpy as np

FIELD_SHIFT = 40           # key = field << 40 | value id
WARM_FIELD = 255           # keys of the warm-up frames, never traffic's


def minibatch(seed: int, client: int, iteration: int, sizes: dict,
              traffic: dict):
    """(keys uint64 [n] unique and sorted, deltas float32 [n])."""
    rng = np.random.default_rng([int(seed), int(client), int(iteration)])
    card = np.asarray(sizes["field_cardinalities"], np.float64)
    a = 1.0 - float(sizes["zipf_exponent"])
    u = rng.random((int(traffic["minibatch"]), len(card)))
    top = np.power(card + 1.0, a) - 1.0
    ids = np.floor(np.power(1.0 + u * top, 1.0 / a)).astype(np.int64) - 1
    ids = np.clip(ids, 0, card.astype(np.int64) - 1)
    fields = np.arange(len(card), dtype=np.uint64) << np.uint64(FIELD_SHIFT)
    keys, counts = np.unique((ids.astype(np.uint64) | fields).reshape(-1),
                             return_counts=True)
    grad = rng.uniform(-0.5, 0.5, len(keys))
    return keys, (grad * counts).astype(np.float32)


def warm_frame(n: int, salt: int):
    """``n`` distinct keys of the reserved field, for warming a shape."""
    keys = (np.uint64(WARM_FIELD) << np.uint64(FIELD_SHIFT)) \
        | (np.arange(n, dtype=np.uint64)
           + np.uint64(salt) * np.uint64(1 << 20))
    return keys, np.full(n, 0.25, np.float32)


def padded_sizes(sizes: dict, traffic: dict):
    """The power-of-two lane counts a minibatch's frames can pad to."""
    most = int(traffic["minibatch"]) * len(sizes["field_cardinalities"])
    out, n = [], 1
    while n < most:
        n *= 2
        if n >= int(traffic["least_padded"]):
            out.append(n)
    return out
