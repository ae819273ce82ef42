"""Plain reference for the served FTRL table: FTRL-Proximal a coordinate
(McMahan et al. 2013) in numpy, float32. It imports nothing of the
program.

A key starts at w = z = n = 0. An add of gradient g to a key does, in
float32: n' = n + g*g; sigma = (sqrt(n') - sqrt(n)) / alpha; z' = z + g
- sigma*w; w' = 0 if |z'| <= l1 else -(z' - sign(z')*l1) / ((beta +
sqrt(n')) / alpha + l2). ``dtype`` float32 is the reference; bfloat16
(every result rounded to 8 bits of mantissa) only ever the control.
"""

from __future__ import annotations

import numpy as np


def _round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest-even bfloat16 -> float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32)


class Table:
    """State of the keys of a fixed universe (sorted uint64)."""

    def __init__(self, universe: np.ndarray, *, alpha: float, beta: float,
                 l1: float, l2: float, dtype: str = "float32") -> None:
        self.universe = universe
        f = np.float32
        self.alpha, self.beta, self.l1, self.l2 = (f(alpha), f(beta),
                                                   f(l1), f(l2))
        self.w = np.zeros(len(universe), f)
        self.z = np.zeros(len(universe), f)
        self.n = np.zeros(len(universe), f)
        self.rnd = _round_bf16 if dtype == "bfloat16" else (lambda x: x)

    def add(self, keys: np.ndarray, grads: np.ndarray) -> None:
        """Unique ``keys``; those outside the universe are skipped."""
        at = np.searchsorted(self.universe, keys)
        at = np.minimum(at, len(self.universe) - 1)
        own = self.universe[at] == keys
        at, g = at[own], self.rnd(grads[own].astype(np.float32))
        r = self.rnd
        w, z, n = self.w[at], self.z[at], self.n[at]
        n_new = r(n + r(g * g))
        sigma = r(r(np.sqrt(n_new) - np.sqrt(n)) / self.alpha)
        z_new = r(r(z + g) - r(sigma * w))
        shrunk = np.sign(z_new) * np.maximum(np.abs(z_new) - self.l1,
                                             np.float32(0))
        denom = r(r(r(self.beta + np.sqrt(n_new)) / self.alpha) + self.l2)
        w_new = np.where(np.abs(z_new) <= self.l1, np.float32(0),
                         r(-shrunk / denom)).astype(np.float32)
        self.w[at], self.z[at], self.n[at] = w_new, z_new, n_new
