"""Driver of the word2vec cell: builds ``apps.word_embedding
.WordEmbedding`` over a synthetic corpus from the seed and trains
through ``WordEmbedding.train(total_steps=...)`` as a user does — host
pair generation, placement, fused superstep.

Set-up builds ONE trainer, drives it through its first calls with the
window's own entry (the first compiles; ``correct`` follows all of
them) and hands that same object to the window.
"""

from __future__ import annotations

import time

import numpy as np

from perf import corpus, program
from perf.reference import w2v as ref

TINY = {
    "program": {"vocab_size": 2000, "embedding_dim": 32,
                "batch_size": 64, "steps_per_call": 8,
                "ns_table_size": 1 << 12, "corpus_tokens": 60000},
    "limits": {},
}


def _norm(x: np.ndarray) -> float:
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


class Cell:
    def __init__(self, *, config, traffic, seed, seconds, chips, devices,
                 tiny, log):
        self.cfg = dict(config)
        self.sizes = dict(config["program"])
        self.limits = dict(config["correct"]["limits"])
        self.checked = int(config["correct"]["checked_calls"])
        self.traffic = dict(traffic)
        if tiny:
            self.sizes.update(TINY["program"])
            self.limits.update(TINY["limits"])
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.chips = chips
        self.devices = devices
        self.log = log
        self.app = None

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        import jax
        from multiverso_tpu import core
        from multiverso_tpu.apps.word_embedding import (W2VConfig,
                                                        WordEmbedding)
        from multiverso_tpu.data.corpus import Corpus
        from multiverso_tpu.data.native import CorpusData

        self._jax = jax
        s = self.sizes
        mesh = program.init_mesh(self.cfg, self.traffic, self.chips,
                                 self.devices)
        t0 = time.perf_counter()
        V = s["vocab_size"]
        self.ids = np.asarray(corpus.zipf_words(
            self.seed, s["corpus_tokens"], V, s["zipf_exponent"]))
        self.counts = np.bincount(self.ids, minlength=V).astype(np.int64)
        data = CorpusData(words=range(V), counts=self.counts,
                          ids=self.ids, total_raw_tokens=len(self.ids))
        self.prog_seed = self.seed & 0x7FFFFFFF
        self.app = WordEmbedding(
            Corpus(data, subsample=s["subsample"]),
            W2VConfig(embedding_dim=s["embedding_dim"], window=s["window"],
                      negative=s["negative"], model="skipgram",
                      objective="ns", batch_size=s["batch_size"],
                      steps_per_call=s["steps_per_call"],
                      learning_rate=s["learning_rate"],
                      min_lr_frac=s["min_lr_frac"], epochs=1,
                      subsample=s["subsample"],
                      unigram_power=s["unigram_power"],
                      ns_sampler="table",
                      ns_table_size=s["ns_table_size"],
                      seed=self.prog_seed, dtype=s["dtype"]),
            mesh=mesh)
        self.log(f"corpus and WordEmbedding built in "
                 f"{time.perf_counter() - t0:.1f} s")
        # the pairs the checked calls use, drawn as the program draws them
        S, B = s["steps_per_call"], s["batch_size"]
        it = self.app.corpus.skipgram_batches(
            B, window=s["window"], seed=self.prog_seed, epochs=1)
        first = [next(it) for _ in range(S)]
        it.close()
        self.pairs = (np.stack([a for a, _ in first]).astype(np.int32),
                      np.stack([b for _, b in first]).astype(np.int32))
        # the first calls, through the window's own entry. A call
        # changes the input table only in the rows of its centres, so
        # the change's norm is read off those rows, kept on the host:
        # the program's call needs 8.6 GB beside its 7.2 GB of tables,
        # and a copy of a table does not fit next to that
        app = self.app
        self.rows = np.unique(self.pairs[0])
        rows0 = self._rows(app.w_in)
        self.losses, self.norms, took = [], [], []
        for i in range(self.checked):
            t0 = time.perf_counter()
            self._train(S)
            took.append(time.perf_counter() - t0)
            self.losses.append(float(app.loss_history[-1]))
            if i in (0, self.checked - 1):
                self.norms.append({
                    "w_in": _norm(self._rows(app.w_in) - rows0),
                    "w_out": float(ref.change_norm(app.w_out.raw(), 0.0))})
        del rows0
        self.call_s = min(took[1:]) if len(took) > 1 else took[0]
        self.log(f"checked calls took {took}")
        # train() ends by stacking its calls' losses in one transfer, a
        # program whose shape is the number of calls: warm it for the
        # window's count, so that nothing compiles inside the window
        import jax.numpy as jnp
        self.calls = max(1, int(self.seconds / self.call_s))
        x = core.place(np.zeros((), np.float32), mesh=mesh)
        np.asarray(jnp.stack([x] * self.calls))

    def _rows(self, table) -> np.ndarray:
        import jax.numpy as jnp
        width = self.sizes["embedding_dim"]
        return np.asarray(jnp.take(table.raw(), jnp.asarray(self.rows),
                                   axis=0))[:, :width]

    def _train(self, steps: int) -> None:
        with self._jax.profiler.TraceAnnotation("bench.w2v.train"):
            self.app.train(total_steps=steps)

    # -- the window ----------------------------------------------------------

    registry_snapshot = staticmethod(program.registry_snapshot)

    def window(self, seconds: float) -> dict:
        s = self.sizes
        S, B = s["steps_per_call"], s["batch_size"]
        calls = self.calls
        t0 = time.perf_counter()
        self._train(calls * S)          # ends on the tables' fence
        elapsed = time.perf_counter() - t0
        done = len(self.app.loss_history)
        pairs = done * S * B
        words = pairs / (s["window"] + 1)
        # a corpus word is a training token: the cell's rate under its
        # own name and under the one that names no app (PERF.md, 2)
        rate = words / elapsed
        return {"attempted": calls, "failed": calls - done,
                "metrics": {"w2v_words_per_s": rate,
                            "train_tokens_per_s": rate},
                "work": {"pairs": pairs, "calls": done},
                "values": {"window_s": elapsed}}

    # -- what correct compares ---------------------------------------------

    def collect(self) -> None:
        self.app = None
        program.free()

    def check(self) -> list:
        import jax
        import jax.numpy as jnp

        s = self.sizes
        t0 = time.perf_counter()
        dt = jnp.dtype(s["dtype"])
        w0 = ref.init_input_vectors(self.prog_seed, s["vocab_size"],
                                    s["embedding_dim"])
        w_in = jnp.asarray(w0, dt)
        rows = jnp.asarray(self.rows)
        rows0 = np.asarray(w_in[rows], np.float32)
        del w0
        w_out = jnp.zeros_like(w_in)
        table = jnp.asarray(ref.unigram_table(
            self.counts, s["unigram_power"], s["ns_table_size"]))
        src, tgt = (jnp.asarray(x) for x in self.pairs)
        # every checked call is train(total_steps=S): one planned call,
        # call number 0, the same pairs (the stream restarts) and key
        key = jax.random.fold_in(jax.random.PRNGKey(self.prog_seed), 0)
        lrs = jnp.asarray(ref.learning_rates(
            0, 1, s["steps_per_call"], s["learning_rate"],
            s["min_lr_frac"]))
        checks, norms = [], []
        for i in range(self.checked):
            w_in, w_out, loss = ref.call(w_in, w_out, src, tgt, key, lrs,
                                         table, negative=s["negative"])
            loss = float(loss)
            checks.append((f"loss_gap_c{i + 1}",
                           abs(self.losses[i] - loss) / abs(loss)))
            self.log(f"call {i + 1}: loss program {self.losses[i]:.7f} "
                     f"reference {loss:.7f}")
            if i in (0, self.checked - 1):
                norms.append({
                    "w_in": _norm(np.asarray(w_in[rows], np.float32)
                                  - rows0),
                    "w_out": float(ref.change_norm(w_out, 0.0))})
        for name, got, want in zip(
                ("first_change_norm_gap",
                 f"change_norm_gap_c{self.checked}"), self.norms, norms):
            # worst leaf: gap of the norms over the reference's norm
            checks.append((name, max(abs(got[k] - want[k]) / want[k]
                                     for k in want)))
            self.log(f"{name}: program {got} reference {want}")
        self.log(f"reference took {time.perf_counter() - t0:.1f} s")
        return [{"name": n, "value": v, "limit": self.limits[n]}
                for n, v in checks]

    def close(self) -> None:
        self.app = None
