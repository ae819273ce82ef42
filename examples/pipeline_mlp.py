"""Pipeline-parallel MLP training (beyond-parity demo).

The trunk is S residual tanh blocks, one per device of the chosen mesh
axis, executed by :func:`multiverso_tpu.parallel.pipeline.pipeline_apply`
(GPipe microbatch schedule: shard_map + scan + neighbor ppermute).
`jax.grad` differentiates straight through the schedule, so the whole
training step — pipelined forward, pipelined backward, SGD on the
stage-stacked params — is ONE jitted program. Embedding (input
projection) and head live outside the trunk, as in any homogeneous
pipeline.

Run: python examples/pipeline_mlp.py   (uses the runtime mesh's model
axis; under tests an 8-stage data-axis mesh)
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from multiverso_tpu import core
from multiverso_tpu.parallel.pipeline import pipeline_apply


def synthetic_regression(n: int, d: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d,)).astype(np.float32)
    y = np.tanh(x @ w) + 0.05 * rng.normal(size=n).astype(np.float32)
    return x, y.astype(np.float32)


def init_params(stages: int, width: int, in_dim: int, seed: int = 0):
    rng = np.random.default_rng(seed)

    def glorot(*shape):
        lim = np.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jnp.asarray(rng.uniform(-lim, lim, shape), jnp.float32)

    return {
        "embed": glorot(in_dim, width),
        "trunk": {"w": glorot(stages, width, width),
                  "b": jnp.zeros((stages, width), jnp.float32)},
        "head": glorot(width, 1),
    }


def _block(p, h):
    # damped residual branch: S stacked blocks stay stable at depth
    return h + 0.2 * jnp.tanh(h @ p["w"] + p["b"])


class PipelineMLPTrainer:
    def __init__(self, width: int = 32, in_dim: int = 16,
                 learning_rate: float = 0.02,
                 mesh: Optional[Mesh] = None, axis: Optional[str] = None,
                 microbatches: Optional[int] = None, seed: int = 0):
        self.mesh = mesh if mesh is not None else core.mesh()
        self.axis = axis if axis is not None else core.MODEL_AXIS
        self.stages = self.mesh.shape[self.axis]
        self.params = init_params(self.stages, width, in_dim, seed)
        self.lr = learning_rate
        self.microbatches = microbatches

        @partial(jax.jit, donate_argnums=0)
        def step(params, x, y):
            def loss_fn(p):
                h = x @ p["embed"]
                h = pipeline_apply(p["trunk"], h, _block,
                                   mesh=self.mesh, axis=self.axis,
                                   microbatches=self.microbatches)
                pred = (h @ p["head"])[:, 0]
                return jnp.mean((pred - y) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            params = jax.tree.map(lambda p, g: p - self.lr * g,
                                  params, grads)
            return params, loss

        self._step = step

    def fit(self, x: np.ndarray, y: np.ndarray, steps: int,
            batch_size: int, seed: int = 0) -> np.ndarray:
        rng = np.random.default_rng(seed)
        losses = []
        for _ in range(steps):
            idx = rng.integers(0, len(x), batch_size)
            self.params, loss = self._step(
                self.params, jnp.asarray(x[idx]), jnp.asarray(y[idx]))
            # one program in flight: on the CPU's virtual devices the
            # collectives of two queued steps can wait on each other for
            # good when the host is busy (the process then aborts)
            losses.append(jax.block_until_ready(loss))
        return np.asarray(jax.device_get(jnp.stack(losses)))


def main() -> None:
    core.init()
    x, y = synthetic_regression(4096, 16, seed=1)
    trainer = PipelineMLPTrainer(width=32, in_dim=16, seed=1)
    losses = trainer.fit(x, y, steps=60, batch_size=256, seed=1)
    print(f"pipeline mlp ({trainer.stages} stages): "
          f"loss {losses[:5].mean():.4f} -> {losses[-5:].mean():.4f}")


if __name__ == "__main__":
    main()
