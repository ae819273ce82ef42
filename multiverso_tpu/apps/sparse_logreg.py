"""Sparse-feature logistic regression on KVTable — the reference's
`Applications/LogisticRegression` sparse path (SURVEY.md §3.6: "dense or
sparse features; weights in ArrayTable (dense) or KVTable (sparse)").

The dense app (:mod:`multiverso_tpu.apps.logreg`) densifies libsvm rows
into an ArrayTable-backed [input_dim, C] weight matrix. Here features
stay sparse end-to-end — weights live in a :class:`KVTable` keyed by the
64-bit hashed feature id, so the feature space is unbounded (hashing
trick); only the features a minibatch touches are ever fetched/updated.

TPU shape of the reference's worker loop (Get rows → local train → Add
deltas, SURVEY.md §4.2/§4.3):

- per minibatch, the UNIQUE feature keys are resolved host-side (the
  KVTable slot plan is host-side anyway) and their weight rows fetched
  in one ``kv.get`` — [U, C] with missing keys at ``default_value``,
- one jitted step computes logits via a gather-einsum over the
  fixed-width padded (feature-position, value) arrays, the softmax/CE
  gradient, and the per-key delta via duplicate-safe scatter-add (the
  client-side Aggregator role, fused on device),
- ``kv.add(uniq_keys, delta)`` folds the delta through the table's
  updater (sgd / adagrad / ftrl — state lives with the table, per key).

Static shapes: samples are padded to ``max_features`` features (extras
raise), unique-key counts are bucketed to powers of two, and padded
lanes point at a zero sentinel row.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu import client, core, telemetry
from multiverso_tpu.apps.logreg import _parse_libsvm
from multiverso_tpu.tables import KVTable
from multiverso_tpu.tables.matrix_table import _bucket
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils import log

BIAS_KEY = np.uint64(0xB1A5B1A5B1A5B1A5)


@dataclasses.dataclass
class SparseLRConfig:
    num_classes: int = 2
    max_features: int = 64        # per-sample nnz pad width (bias incl.)
    capacity: int = 1 << 20       # KVTable capacity (keys)
    slots_per_bucket: int = 16    # hash-bucket width (overflow headroom)
    minibatch_size: int = 4096
    learning_rate: float = 0.1
    regular_lambda: float = 0.0   # lazy L2 on touched rows
    updater: str = "sgd"          # "sgd" | "adagrad" | "ftrl"
    ftrl_l1: float = 0.0          # updater="ftrl": L1 / L2 / beta — the
    ftrl_l2: float = 0.0          # AddOption lam/rho/momentum fields
    ftrl_beta: float = 1.0        # (see updaters docstring mapping)
    epochs: int = 1
    use_bias: bool = True
    seed: int = 0


def read_libsvm_sparse(path: str) -> Tuple[List[List[Tuple[int, float]]],
                                           np.ndarray]:
    """Parse libsvm rows WITHOUT densifying: ([(idx, val), ...] per
    sample, labels). Indices are used as hash keys directly — no base
    detection needed (0- vs 1-based just shifts key identity)."""
    labels, rows = _parse_libsvm(path)
    y = np.asarray(labels)
    if set(np.unique(y)) <= {-1.0, 1.0}:
        y = (y > 0).astype(np.int32)
    return rows, y.astype(np.int32)


def synthetic_sparse(n: int, dim: int, num_classes: int, nnz: int = 20,
                     seed: int = 0) -> Tuple[List[List[Tuple[int, float]]],
                                             np.ndarray]:
    """Sparse classification data with a planted linear model over a
    ``dim``-sized feature space (exercises >=1e5 hashed dims cheaply)."""
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 1.0, (dim, num_classes))
    rows, ys = [], []
    for _ in range(n):
        idx = rng.choice(dim, size=nnz, replace=False)
        val = rng.normal(0, 1.0, nnz)
        logits = val @ w[idx]
        ys.append(int(np.argmax(logits)))
        rows.append(list(zip(idx.tolist(), val.tolist())))
    return rows, np.asarray(ys, np.int32)


class SparseLogisticRegression:
    """The app: KVTable-backed linear model over hashed sparse features."""

    def __init__(self, config: SparseLRConfig, *, mesh=None,
                 name: str = "sparse_logreg") -> None:
        self.config = config
        self.mesh = mesh if mesh is not None else core.mesh()
        c = config
        if c.num_classes < 2:
            raise ValueError("num_classes must be >= 2")
        opt = AddOption.for_ftrl(c.learning_rate, c.ftrl_l1, c.ftrl_l2,
                                 c.ftrl_beta) if c.updater == "ftrl" \
            else AddOption(learning_rate=c.learning_rate)
        self.table = KVTable(
            c.capacity, value_dim=c.num_classes, dtype="float32",
            slots_per_bucket=c.slots_per_bucket,
            updater=c.updater, mesh=self.mesh, name=name,
            default_option=opt)
        # MVTPU_COALESCE=K: the per-minibatch kv.add coalesces — K
        # minibatch gradients pre-sum by key host-side and flush as ONE
        # fused probe+updater dispatch (the reference's client-side
        # Aggregator). Gets then serve weights up to K minibatches
        # stale, the reference worker's own bounded-staleness semantics.
        self._coalescer = client.maybe_coalescing(self.table)
        self._step_jits: Dict[Tuple[int, int], object] = {}
        # fault tolerance (ft.checkpoint.wire_app): epoch-cursor
        # resume; the restored offset is consumed by the FIRST train()
        # after a resume (in-session train() calls keep restarting)
        self.run_ckpt = None
        self._epoch_done = 0
        self._resume_epochs = 0

    # -- batch packing -----------------------------------------------------

    def _pack(self, rows: Sequence[Sequence[Tuple[int, float]]]):
        """Fixed-shape (keys [B,F] uint64, vals [B,F] f32) + the unique
        key set; padded lanes carry key 0 with value 0 (they map to the
        sentinel row, so the key identity is irrelevant)."""
        c = self.config
        b = len(rows)
        f = c.max_features
        keys = np.zeros((b, f), np.uint64)
        vals = np.zeros((b, f), np.float32)
        for i, row in enumerate(rows):
            feats = list(row)
            if c.use_bias:
                feats.append((None, 1.0))
            if len(feats) > f:
                raise ValueError(
                    f"sample {i} has {len(feats)} features (incl. bias) "
                    f"> max_features={f}")
            for j, (idx, val) in enumerate(feats):
                keys[i, j] = BIAS_KEY if idx is None \
                    else np.uint64(idx) + np.uint64(1)  # avoid key 0 pad
                vals[i, j] = val
        uniq = np.unique(keys[vals != 0.0])
        return keys, vals, uniq

    def _positions(self, keys: np.ndarray, vals: np.ndarray,
                   uniq: np.ndarray, upad: int) -> np.ndarray:
        """Map each (sample, feature) lane to its row in the fetched
        unique-weight block; zero-value pad lanes -> sentinel row upad."""
        if len(uniq) == 0:      # all-zero minibatch: every lane is padding
            return np.full(keys.shape, upad, np.int32)
        pos = np.searchsorted(uniq, keys.ravel()).astype(np.int32)
        pos = np.minimum(pos, len(uniq) - 1)
        hit = uniq[pos] == keys.ravel()
        pos = np.where(hit & (vals.ravel() != 0.0), pos, upad)
        return pos.reshape(keys.shape).astype(np.int32)

    # -- the jitted step ---------------------------------------------------

    def _step_fn(self, b: int, upad: int):
        fn = self._step_jits.get((b, upad))
        if fn is None:
            c = self.config

            @jax.jit
            def step(w, pos, vals, y):
                # w [upad+1, C] (sentinel row zero), pos [B, F], vals
                # [B, F], y [B] -> (loss, dw [upad+1, C])
                def loss_fn(w):
                    rows = jnp.take(w, pos, axis=0)        # [B, F, C]
                    logits = jnp.einsum("bf,bfc->bc", vals, rows)
                    logp = jax.nn.log_softmax(logits)
                    nll = -jnp.mean(
                        jnp.take_along_axis(logp, y[:, None], axis=1))
                    reg = 0.5 * c.regular_lambda * jnp.sum(w[:-1] ** 2)
                    return nll + reg

                loss, dw = jax.value_and_grad(loss_fn)(w)
                return loss, dw

            fn = self._step_jits[(b, upad)] = step
        return fn

    def train_batch(self, rows, y: np.ndarray) -> float:
        """One Get -> fused grad -> Add round (the reference's per-block
        worker loop)."""
        keys, vals, uniq = self._pack(rows)
        upad = _bucket(len(uniq))
        uniq_pad = np.zeros(upad, np.uint64)
        uniq_pad[: len(uniq)] = uniq
        uniq_pad[len(uniq):] = BIAS_KEY ^ np.uint64(1)  # unused real key
        w, _found = self.table.get(uniq_pad)             # [upad, C]
        w_ext = np.concatenate(
            [w, np.zeros((1, self.config.num_classes), np.float32)])
        pos = self._positions(keys, vals, uniq, upad)
        step = self._step_fn(len(rows), upad)
        put = lambda a: core.place(np.asarray(a), mesh=self.mesh)
        loss, dw = step(put(w_ext.astype(np.float32)), put(pos),
                        put(vals), put(y.astype(np.int32)))
        dw = np.asarray(dw)[:len(uniq)]                  # drop pad+sentinel
        if len(uniq):           # all-zero minibatch has nothing to update
            if self._coalescer is not None:
                self._coalescer.add_kv(uniq, dw)
            else:
                self.table.add(uniq, dw)
        return float(loss)

    def train(self, rows, y: np.ndarray) -> float:
        c = self.config
        n = len(rows)
        loss = float("nan")
        t0 = time.perf_counter()
        step_no = 0
        # resume (applied ONCE): table state restored exactly at an
        # epoch boundary and each epoch's permutation seed derives from
        # its index, so the remaining epochs replay identically
        e = min(self._resume_epochs, c.epochs)
        self._resume_epochs = 0
        while e < c.epochs:
            # divergence rollback (MVTPU_HEALTH_ACTION=rollback):
            # restore_run_state just moved the cursor — replay from the
            # last clean generation (epoch RNG derives from the index,
            # so the replay is deterministic)
            if telemetry.health.maybe_rollback(self) is not None:
                e = min(self._resume_epochs, c.epochs)
                self._resume_epochs = 0
                continue
            order = np.random.default_rng(c.seed + e).permutation(n)
            losses = []
            for s in range(0, n, c.minibatch_size):
                idx = order[s:s + c.minibatch_size]
                # the step record links to the span (its ``parent``),
                # whose ``dur_s`` is the one timing
                with telemetry.span("sparse_logreg.step"):
                    losses.append(self.train_batch(
                        [rows[i] for i in idx], y[idx]))
                    telemetry.step_timeline("sparse_logreg", step_no,
                                            samples=len(idx))
                telemetry.beat()
                step_no += 1
            loss = float(np.mean(losses))
            log.info("sparse_logreg epoch %d: loss=%.4f", e, loss)
            self._epoch_done = e + 1
            if self.run_ckpt is not None:
                # export_checkpoint_async flushes the coalescer, so the
                # checkpoint observes every buffered delta
                self.run_ckpt.maybe_save(self._epoch_done, self.run_state)
            e += 1
        if self._coalescer is not None:
            # the tail partial group must land before eval/checkpoint
            self._coalescer.flush()
        dt = time.perf_counter() - t0
        telemetry.counter("sparse_logreg.samples").inc(n * c.epochs)
        telemetry.emit("sparse_logreg.samples_per_sec",
                       n * c.epochs / dt, "samples/s")
        return loss

    # -- fault tolerance (ft.checkpoint contract) --------------------------

    def run_state(self) -> dict:
        """Epoch cursor: the KVTable (weights + updater state + key
        layout) rides the manager's table export; minibatch RNG derives
        from the epoch index."""
        return {"epoch_done": self._epoch_done}

    def restore_run_state(self, restored) -> None:
        self._epoch_done = int(restored.get("epoch_done", 0))
        self._resume_epochs = self._epoch_done

    # -- inference ---------------------------------------------------------

    def predict(self, rows) -> np.ndarray:
        if self._coalescer is not None:
            self._coalescer.flush()     # eval reads are exact
        keys, vals, uniq = self._pack(rows)
        upad = _bucket(len(uniq))
        uniq_pad = np.zeros(upad, np.uint64)
        uniq_pad[: len(uniq)] = uniq
        uniq_pad[len(uniq):] = BIAS_KEY ^ np.uint64(1)
        w, _ = self.table.get(uniq_pad)
        w_ext = np.concatenate(
            [w, np.zeros((1, self.config.num_classes), np.float32)])
        pos = self._positions(keys, vals, uniq, upad)
        logits = np.einsum("bf,bfc->bc", vals, w_ext[pos])
        return np.argmax(logits, axis=1).astype(np.int32)

    def accuracy(self, rows, y: np.ndarray) -> float:
        return float(np.mean(self.predict(rows) == y))

    # -- checkpoint --------------------------------------------------------

    def store(self, uri: str) -> None:
        self.table.store(uri)

    def load(self, uri: str) -> None:
        self.table.load(uri)


def main(argv=None) -> None:
    """CLI mirroring the reference LR app's sparse configuration."""
    from multiverso_tpu.utils import configure
    configure.define_string("train_file", "", "libsvm training data",
                            overwrite=True)
    configure.define_string("test_file", "", "libsvm eval data",
                            overwrite=True)
    configure.define_int("num_classes", 2, "classes", overwrite=True)
    configure.define_int("max_features", 64, "per-sample nnz pad",
                         overwrite=True)
    configure.define_int("capacity", 1 << 20, "KVTable capacity",
                         overwrite=True)
    configure.define_int("minibatch_size", 4096, "samples per step",
                         overwrite=True)
    configure.define_float("learning_rate", 0.1, "lr", overwrite=True)
    configure.define_float("regular_lambda", 0.0, "L2", overwrite=True)
    configure.define_int("epoch", 1, "epochs", overwrite=True)
    configure.define_string("output_file", "", "checkpoint uri",
                            overwrite=True)
    from multiverso_tpu.ft.checkpoint import define_run_flags, wire_app
    define_run_flags()
    core.init(argv)
    path = configure.get_flag("train_file")
    if not path:
        raise SystemExit("-train_file is required")
    rows, y = read_libsvm_sparse(path)
    cfg = SparseLRConfig(
        num_classes=configure.get_flag("num_classes"),
        max_features=configure.get_flag("max_features"),
        capacity=configure.get_flag("capacity"),
        minibatch_size=configure.get_flag("minibatch_size"),
        learning_rate=configure.get_flag("learning_rate"),
        regular_lambda=configure.get_flag("regular_lambda"),
        epochs=configure.get_flag("epoch"))
    app = SparseLogisticRegression(cfg)
    # fault tolerance: run-level checkpoint/resume, cadence in epochs
    mgr = wire_app(app, [app.table], every_default=1)
    # flight recorder: env-gated stall watchdog + device capture (the
    # per-step beat is in train)
    with telemetry.maybe_watchdog("sparse_logreg"), \
            telemetry.profile_window("sparse_logreg"):
        app.train(rows, y)
    if mgr is not None:
        mgr.close()     # drain pending background checkpoint writes
    telemetry.record_device_memory()
    log.info("train accuracy: %.4f", app.accuracy(rows, y))
    test = configure.get_flag("test_file")
    if test:
        trows, ty = read_libsvm_sparse(test)
        log.info("test accuracy: %.4f", app.accuracy(trows, ty))
    out = configure.get_flag("output_file")
    if out:
        app.store(out)
    core.barrier()


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
