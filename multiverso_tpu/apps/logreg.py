"""Distributed logistic regression — TPU-native rebuild of the reference's
`Applications/LogisticRegression/` (upstream layout; SURVEY.md §3.6):
multi-threaded, multi-node linear classification over libsvm-style data,
weights in a dense ArrayTable, SGD-family objectives.

Reference shape (SURVEY.md §3.6 row 1): `LogReg` main + `Configure`
(key=value config) + `DataBlock`/`Sample` reader + trainer loop; weights in
ArrayTable (dense) across servers, deltas `Add`ed per minibatch.

TPU design:

- The weight matrix lives in an :class:`ArrayTable` (flat, sharded over the
  mesh ``"model"`` axis — the analog of the contiguous per-server blocks).
- The per-minibatch Get→local-grad→Add round trip of the reference becomes
  ONE jitted train step: batch sharded over the mesh ``"data"`` axis, loss
  grad computed per shard, and because the grad's output sharding equals
  the (data-replicated) param sharding, XLA inserts the cross-data-axis
  reduction (psum over ICI) automatically — the Aggregator + server
  round-trip collapsed into a collective.
- The server-side Updater runs fused in the same step on the sharded
  weights with donated buffers (SURVEY.md §3.9 mapping).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from multiverso_tpu import client, core, telemetry
from multiverso_tpu.tables import ArrayTable, make_superstep
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils import log


@dataclasses.dataclass
class LogRegConfig:
    """Flag set of the reference app's key=value `Configure` file."""
    input_dim: int
    num_classes: int
    minibatch_size: int = 256
    steps_per_call: int = 8         # minibatches per fused dispatch
    epochs: int = 1
    learning_rate: float = 0.1
    updater: str = "sgd"
    regular_lambda: float = 0.0     # L2 coefficient ("regular=L2" analog)
    ftrl_l1: float = 0.0            # updater="ftrl": L1 / L2 / beta — the
    ftrl_l2: float = 0.0            # AddOption lam/rho/momentum fields
    ftrl_beta: float = 1.0          # (see updaters docstring mapping)
    objective: str = "softmax"      # "softmax" | "sigmoid"
    shard_update: bool = False      # cross-replica weight-update
    # sharding: updater state (adagrad/ftrl/...) + update compute / dp
    # over the data axis (arXiv:2004.13336); no-op for stateless sgd
    seed: int = 0

    def __post_init__(self) -> None:
        if self.objective == "sigmoid" and self.num_classes != 2:
            raise ValueError(
                "objective='sigmoid' is the binary objective; it requires "
                f"num_classes == 2, got {self.num_classes}")


def read_libsvm(path: str, input_dim: int, dtype=np.float32,
                one_based: Optional[bool] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Parse libsvm/sparse text: `label idx:val idx:val ...` per line.

    The reference's `Sample` reader (Applications/LogisticRegression).
    Canonical libsvm is 1-based; ``one_based=None`` autodetects: a file
    containing index 0 is 0-based, one containing index == input_dim is
    1-based; ambiguous files default to 1-based (the libsvm convention —
    and pass the SAME explicit ``one_based`` for train and test files so
    an ambiguous one cannot silently shift feature columns between them).
    Returns dense (X, y) — dense is the TPU-friendly layout; the sparse
    path of the reference maps to the KVTable app variant
    (:mod:`multiverso_tpu.apps.sparse_logreg`).
    """
    labels, rows = _parse_libsvm(path)
    if one_based is None:
        one_based = _resolve_base(*_base_markers(rows, input_dim),
                                  what=repr(path), input_dim=input_dim)
    return _densify(labels, rows, input_dim, one_based, dtype)


def _parse_libsvm(path: str):
    """One parse pass: (labels list, rows list of [(idx, val), ...])."""
    labels, rows = [], []
    with open(path) as f:
        for line in f:
            parts = line.split()
            if not parts:
                continue
            labels.append(float(parts[0]))
            rows.append([(int(t[0]), float(t[1])) for t in
                         (tok.split(":") for tok in parts[1:])])
    return labels, rows


def _base_markers(rows, input_dim: int) -> Tuple[bool, bool]:
    has_zero = has_dim = False
    for r in rows:
        for i, _ in r:
            has_zero |= i == 0
            has_dim |= i == input_dim
    return has_zero, has_dim


def _resolve_base(has_zero: bool, has_dim: bool, *, what: str,
                  input_dim: int) -> bool:
    """THE autodetect rule (single definition — read_libsvm and
    detect_libsvm_base must never disagree on the same file): index 0 ⇒
    0-based, index == input_dim ⇒ 1-based, both ⇒ error, neither ⇒
    1-based (the libsvm convention)."""
    if has_zero and has_dim:
        raise ValueError(
            f"{what}: contains both index 0 and index {input_dim} — "
            "cannot autodetect base; pass one_based explicitly")
    return not has_zero


def _densify(labels, rows, input_dim: int, one_based: bool, dtype
             ) -> Tuple[np.ndarray, np.ndarray]:
    off = 1 if one_based else 0
    xs = []
    for r in rows:
        row = np.zeros(input_dim, dtype=dtype)
        for i, val in r:
            j = i - off
            if j < 0 or j >= input_dim:
                raise ValueError(
                    f"feature index {i} out of range for input_dim "
                    f"{input_dim} (one_based={one_based})")
            row[j] = val
        xs.append(row)
    X = np.stack(xs) if xs else np.zeros((0, input_dim), dtype)
    y = np.asarray(labels)
    # labels may be {-1,+1} (binary libsvm) or {0..C-1}
    if set(np.unique(y)) <= {-1.0, 1.0}:
        y = (y > 0).astype(np.int32)
    return X, y.astype(np.int32)


def detect_libsvm_base(paths, input_dim: int) -> bool:
    """Detect the index base JOINTLY over several libsvm files (train +
    test must agree or feature columns silently shift between them).
    Same rule as ``read_libsvm``'s autodetect (shared ``_resolve_base``)."""
    has_zero = has_dim = False
    for path in paths:
        hz, hd = _base_markers(_parse_libsvm(path)[1], input_dim)
        has_zero |= hz
        has_dim |= hd
    return _resolve_base(has_zero, has_dim, what=repr(list(paths)),
                         input_dim=input_dim)


def synthetic_blobs(n: int, input_dim: int, num_classes: int,
                    seed: int = 0, spread: float = 3.0
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Gaussian class blobs — the test/benchmark stand-in dataset."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0.0, spread, (num_classes, input_dim))
    y = rng.integers(0, num_classes, n).astype(np.int32)
    X = centers[y] + rng.normal(0.0, 1.0, (n, input_dim))
    return X.astype(np.float32), y


class LogisticRegression:
    """The app: ArrayTable-backed linear model + fused DP train step."""

    def __init__(self, config: LogRegConfig, *, mesh=None,
                 name: str = "logreg") -> None:
        self.config = config
        self.mesh = mesh if mesh is not None else core.mesh()
        c = config
        self.n_weights = (c.input_dim + 1) * c.num_classes  # + bias row
        rng = np.random.default_rng(c.seed)
        init = np.zeros(self.n_weights, np.float32)
        init[: c.input_dim * c.num_classes] = rng.normal(
            0.0, 0.01, c.input_dim * c.num_classes)
        opt = AddOption.for_ftrl(c.learning_rate, c.ftrl_l1, c.ftrl_l2,
                                 c.ftrl_beta) if c.updater == "ftrl" \
            else AddOption(learning_rate=c.learning_rate)
        self.table = ArrayTable(
            self.n_weights, "float32", init_value=init, updater=c.updater,
            mesh=self.mesh, name=name, default_option=opt,
            shard_update=c.shard_update)
        # MVTPU_STALENESS: weights() (a logging/inspection read — the
        # train step never feeds it back) serves from a bounded-staleness
        # cached view instead of a blocking whole-table fetch per call
        self._view = client.maybe_cached_view(self.table)
        self._data_sharding = NamedSharding(self.mesh, P(core.DATA_AXIS))
        # fault tolerance (ft.checkpoint.wire_app): run-level manager +
        # resume cursor — epochs are the checkpoint/restart unit here.
        # _epoch_done counts completed epochs (what a checkpoint
        # records); _resume_epochs is the restored offset, consumed by
        # the FIRST train() after a resume — repeated in-session
        # train() calls keep their run-all-epochs meaning
        self.run_ckpt = None
        self._epoch_done = 0
        self._resume_epochs = 0
        self._build_step()

    # -- model math --------------------------------------------------------

    def _unflatten(self, w_flat: jax.Array) -> Tuple[jax.Array, jax.Array]:
        c = self.config
        w = w_flat[: c.input_dim * c.num_classes].reshape(
            c.input_dim, c.num_classes)
        b = w_flat[c.input_dim * c.num_classes: self.n_weights].reshape(
            c.num_classes)
        return w, b

    def _loss(self, w_flat, x, y):
        c = self.config
        w, b = self._unflatten(w_flat)
        logits = x @ w + b
        if c.objective == "sigmoid":
            # binary: y in {0,1}, logits[:, 1] - logits[:, 0] as score
            score = logits[:, 1] - logits[:, 0]
            nll = jnp.mean(jnp.logaddexp(0.0, score) - y * score)
        else:
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.mean(
                jnp.take_along_axis(logp, y[:, None], axis=1))
        reg = 0.5 * c.regular_lambda * jnp.sum(w * w)
        return nll + reg

    def _build_step(self) -> None:
        table = self.table

        def body(params, states, locals_, options, x, y):
            (param,), (state,), (opt,) = params, states, options
            loss, grad = jax.value_and_grad(self._loss)(param, x, y)
            param, state = table.updater.apply(param, state, grad, opt)
            return (param,), (state,), locals_, loss

        # supported fused path: grad + updater in one compiled program,
        # donation/sharding/step-counting handled by the table layer
        self._fused = make_superstep((table,), body, name="logreg_step")

        def body_scan(params, states, locals_, options, xs, ys):
            # the scan-superstep treatment the other apps get: S
            # minibatches per dispatch (one host round-trip, not S)
            (param,), (state,), (opt,) = params, states, options

            def sb(carry, inp):
                param, state = carry
                x, y = inp
                loss, grad = jax.value_and_grad(self._loss)(param, x, y)
                param, state = table.updater.apply(param, state, grad,
                                                   opt)
                return (param, state), loss

            (param, state), losses = lax.scan(sb, (param, state),
                                              (xs, ys))
            return (param,), (state,), locals_, losses

        self._fused_scan = make_superstep((table,), body_scan,
                                          name="logreg_superstep")

        @jax.jit
        def predict(param, x):
            w, b = self._unflatten(param)
            return jnp.argmax(x @ w + b, axis=1)

        self._predict = predict

    # -- data plumbing -----------------------------------------------------

    def _shard_batch(self, x: np.ndarray, y: np.ndarray):
        """Pad the batch to a multiple of the data-axis size and place it
        sharded over "data" (per-chip sample shards)."""
        d = self.mesh.shape[core.DATA_AXIS]
        n = len(x)
        m = -(-n // d) * d
        if m != n:
            # pad by repeating the first samples — keeps loss a true mean
            # only when n % d == 0; callers batch accordingly; remainder
            # batches get a slightly reweighted mean, which matches the
            # reference's per-block SGD semantics closely enough.
            reps = np.arange(m - n) % max(n, 1)
            x = np.concatenate([x, x[reps]])
            y = np.concatenate([y, y[reps]])
        xs = jax.device_put(x.astype(np.float32),
                            NamedSharding(self.mesh, P(core.DATA_AXIS, None)))
        ys = jax.device_put(y.astype(np.int32), self._data_sharding)
        return xs, ys

    def _shard_scan(self, xs: np.ndarray, ys: np.ndarray):
        """Place a stacked [S, B, ...] group, batch dim sharded over
        "data" (full minibatches only — B is already a size multiple)."""
        d = self.mesh.shape[core.DATA_AXIS]
        if xs.shape[1] % d:
            reps = np.arange(-xs.shape[1] % d) % xs.shape[1]
            xs = np.concatenate([xs, xs[:, reps]], axis=1)
            ys = np.concatenate([ys, ys[:, reps]], axis=1)
        xd = jax.device_put(xs.astype(np.float32), NamedSharding(
            self.mesh, P(None, core.DATA_AXIS, None)))
        yd = jax.device_put(ys.astype(np.int32), NamedSharding(
            self.mesh, P(None, core.DATA_AXIS)))
        return xd, yd

    # -- training ----------------------------------------------------------

    def train_epoch(self, X: np.ndarray, y: np.ndarray,
                    shuffle_seed: Optional[int] = None) -> float:
        c = self.config
        n = len(X)
        order = np.arange(n)
        if shuffle_seed is not None:
            np.random.default_rng(shuffle_seed).shuffle(order)
        losses = []
        t0 = time.perf_counter()
        # full minibatches group into scanned supersteps (S per dispatch);
        # the trailing partial group falls back to single-step dispatches
        starts = list(range(0, n, c.minibatch_size))
        full = [s for s in starts if s + c.minibatch_size <= n]
        tail = [s for s in starts if s + c.minibatch_size > n]
        S = max(c.steps_per_call, 1)
        step_no = 0
        for g in range(0, len(full) - len(full) % S, S):
            grp = full[g:g + S]
            xs = np.stack([X[order[s:s + c.minibatch_size]] for s in grp])
            ys = np.stack([y[order[s:s + c.minibatch_size]] for s in grp])
            xd, yd = self._shard_scan(xs, ys)
            # the step record links to the span (its ``parent``), whose
            # ``dur_s`` / ``span.seconds`` series is the one timing
            with telemetry.span("logreg.superstep"):
                _, lg = self._fused_scan((), xd, yd)
                telemetry.step_timeline(
                    "logreg", step_no, samples=S * c.minibatch_size)
            telemetry.beat()
            step_no += 1
            losses.extend(lg)
        for s in full[len(full) - len(full) % S:] + tail:
            idx = order[s:s + c.minibatch_size]
            xs, ys = self._shard_batch(X[idx], y[idx])
            with telemetry.span("logreg.step"):
                _, loss = self._fused((), xs, ys)
                telemetry.step_timeline("logreg", step_no,
                                        samples=len(idx))
            telemetry.beat()
            step_no += 1
            losses.append(loss)
        # one transfer for all loss scalars instead of one blocking
        # fetch each (per-fetch cost not measured on the current host)
        mean_loss = float(np.asarray(jnp.stack(losses)).mean())
        dt = time.perf_counter() - t0
        telemetry.counter("logreg.samples").inc(n)
        telemetry.emit("logreg.samples_per_sec", n / dt, "samples/s")
        if self._view is not None:
            # logging-only read off the cached view: within the
            # staleness bound, zero extra device dispatches
            telemetry.gauge("logreg.weight_norm").set(
                float(np.linalg.norm(self._view.get())))
        log.info("logreg epoch done: loss=%.4f %.0f samples/s",
                 mean_loss, n / dt)
        return mean_loss

    def train(self, X: np.ndarray, y: np.ndarray) -> float:
        loss = float("nan")
        # resume picks up at the restored epoch cursor (applied ONCE):
        # the table state is exact (CRC-verified restore) and each
        # epoch's shuffle seed derives from its index, so the remaining
        # epochs replay identically to the uninterrupted run
        e = min(self._resume_epochs, self.config.epochs)
        self._resume_epochs = 0
        while e < self.config.epochs:
            # divergence rollback (MVTPU_HEALTH_ACTION=rollback): the
            # restore ran restore_run_state, so re-read the cursor and
            # replay from the last clean generation
            if telemetry.health.maybe_rollback(self) is not None:
                e = min(self._resume_epochs, self.config.epochs)
                self._resume_epochs = 0
                continue
            loss = self.train_epoch(X, y, shuffle_seed=self.config.seed + e)
            self._epoch_done = e + 1
            if self.run_ckpt is not None:
                self.run_ckpt.maybe_save(self._epoch_done, self.run_state)
            e += 1
        return loss

    # -- fault tolerance (ft.checkpoint contract) --------------------------

    def run_state(self) -> dict:
        """App train-state for the run checkpoint manager: the epoch
        cursor (RNG state is derived from it — shuffle seeds fold the
        epoch index)."""
        return {"epoch_done": self._epoch_done}

    def restore_run_state(self, restored) -> None:
        self._epoch_done = int(restored.get("epoch_done", 0))
        self._resume_epochs = self._epoch_done

    # -- inference / eval --------------------------------------------------

    def predict(self, X: np.ndarray) -> np.ndarray:
        xs = core.place(np.asarray(X, np.float32), mesh=self.mesh)
        return np.asarray(self._predict(self.table.raw(), xs))

    def accuracy(self, X: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(X) == y))

    def weights(self) -> Tuple[np.ndarray, np.ndarray]:
        w_flat = self._view.get() if self._view is not None \
            else self.table.get()
        c = self.config
        w = w_flat[: c.input_dim * c.num_classes].reshape(
            c.input_dim, c.num_classes)
        b = w_flat[c.input_dim * c.num_classes:].reshape(c.num_classes)
        return w, b

    # -- checkpoint --------------------------------------------------------

    def store(self, uri: str) -> None:
        self.table.store(uri)

    def load(self, uri: str) -> None:
        self.table.load(uri)


def main(argv=None) -> None:
    """CLI entry mirroring the reference binary's config-file interface."""
    from multiverso_tpu.utils import configure
    configure.define_string("train_file", "", "libsvm training data", overwrite=True)
    configure.define_string("test_file", "", "libsvm test data", overwrite=True)
    configure.define_int("input_dimension", 784, "feature dimension", overwrite=True)
    configure.define_int("output_dimension", 10, "number of classes", overwrite=True)
    configure.define_int("minibatch_size", 256, "minibatch size", overwrite=True)
    configure.define_int("train_epoch", 1, "epochs", overwrite=True)
    configure.define_float("learning_rate", 0.1, "learning rate", overwrite=True)
    configure.define_float("regular_lambda", 0.0, "L2 coefficient", overwrite=True)
    configure.define_bool("shard_update", False,
                          "cross-replica weight-update sharding "
                          "(updater state + update FLOPs / dp)",
                          overwrite=True)
    configure.define_string("output_model_file", "", "checkpoint URI", overwrite=True)
    from multiverso_tpu.ft.checkpoint import define_run_flags, wire_app
    define_run_flags()
    core.init(argv)
    # the global updater_type default is "default" (plain add) — for a
    # gradient-descent app that means ascent; this app's default is sgd
    updater = configure.get_flag("updater_type")
    if updater == "default":
        updater = "sgd"
    cfg = LogRegConfig(
        input_dim=configure.get_flag("input_dimension"),
        num_classes=configure.get_flag("output_dimension"),
        minibatch_size=configure.get_flag("minibatch_size"),
        epochs=configure.get_flag("train_epoch"),
        learning_rate=configure.get_flag("learning_rate"),
        regular_lambda=configure.get_flag("regular_lambda"),
        updater=updater,
        shard_update=configure.get_flag("shard_update"),
    )
    app = LogisticRegression(cfg)
    train_file = configure.get_flag("train_file")
    test_file = configure.get_flag("test_file")
    # parse each file ONCE, then detect the index base jointly over all of
    # them: per-file detection could assign different bases to train and
    # test, silently shifting feature columns between them
    parsed = {f: _parse_libsvm(f) for f in (train_file, test_file) if f}
    base = True
    if parsed:
        has_zero = has_dim = False
        for _, rows in parsed.values():
            hz, hd = _base_markers(rows, cfg.input_dim)
            has_zero |= hz
            has_dim |= hd
        base = _resolve_base(has_zero, has_dim,
                             what=repr(list(parsed)),
                             input_dim=cfg.input_dim)
    if train_file:
        X, y = _densify(*parsed[train_file], cfg.input_dim, base,
                        np.float32)
    else:
        X, y = synthetic_blobs(20000, cfg.input_dim, cfg.num_classes)
    # fault tolerance: -run_dir/-resume (or MVTPU_RUN_DIR/MVTPU_RESUME)
    # enable run-level checkpoint/resume, cadence in EPOCHS (default:
    # every epoch once a run dir is configured)
    mgr = wire_app(app, [app.table], every_default=1)
    # flight recorder: MVTPU_WATCHDOG=<s> arms a stall watchdog (the
    # per-step beat is in train_epoch); MVTPU_PROFILE_DIR captures a
    # device profile of the whole training run
    with telemetry.maybe_watchdog("logreg"), \
            telemetry.profile_window("logreg"):
        app.train(X, y)
    if mgr is not None:
        mgr.close()     # drain pending background checkpoint writes
    telemetry.record_device_memory()
    log.info("train accuracy: %.4f", app.accuracy(X, y))
    if test_file:
        Xt, yt = _densify(*parsed[test_file], cfg.input_dim, base,
                          np.float32)
        log.info("test accuracy: %.4f", app.accuracy(Xt, yt))
    out = configure.get_flag("output_model_file")
    if out:
        app.store(out)
    core.barrier()


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
