"""Driver of the LightLDA cells: builds ``apps.lightlda.LightLDA`` from
the seed as a user would, drives whole Gibbs sweeps back to back, and
hands the plain reference (``perf/reference/lda.py``) what ``correct``
compares.

Set-up builds ONE sampler, drives it through its first sweeps (which
compile, and whose assignments are kept for the check) and hands that
same object to the window.
"""

from __future__ import annotations

import json
import time

import numpy as np

from perf import corpus, program
from perf.reference import lda as ref

# the harness's own rehearsal sizes (never a cell's): a CPU run in the
# Pallas interpreter, with limits as wide as so few tokens need
TINY = {
    "program": {"docs": 96, "vocab_size": 300, "num_topics": 128,
                "block_tokens": 256, "batch_tokens": 1024,
                "doc_len_mean": 60.0, "doc_len_sd": 20.0,
                "doc_len_min": 8, "doc_len_max": 128},
    "limits": {"moved_share_gap_s1": 0.1,
               "loglik_gap_s1": 0.05,
               "topic_sizes_gap_s1": 0.2,
               "doc_topics_gap_s1": 0.1},
    "loglik_every": 1,
}


class Cell:
    def __init__(self, *, config, traffic, seed, seconds, chips, devices,
                 tiny, log):
        self.cfg = dict(config)
        self.sizes = dict(config["program"])
        self.limits = dict(config["correct"]["limits"])
        self.loglik_every = int(config["correct"]["loglik_every"])
        if tiny:
            self.sizes.update(TINY["program"])
            self.limits.update(TINY["limits"])
            self.loglik_every = TINY["loglik_every"]
        self.traffic = traffic
        self.seed = int(seed)
        self.chips = chips
        self.devices = devices
        self.log = log
        self.app = None

    # -- set-up ------------------------------------------------------------

    def _corpus(self):
        s = self.sizes
        lens = corpus.doc_lengths(self.seed, s["docs"], s["doc_len_mean"],
                                  s["doc_len_sd"], s["doc_len_min"],
                                  s["doc_len_max"])
        n = int(lens.sum())
        words = np.asarray(corpus.zipf_words(
            self.seed, n, s["vocab_size"], s["zipf_exponent"]))
        docs = np.repeat(np.arange(s["docs"], dtype=np.int32), lens)
        return words, docs

    def setup(self) -> None:
        import jax
        from multiverso_tpu.apps.lightlda import LDAConfig, LightLDA

        s = self.sizes
        mesh = program.init_mesh(self.cfg, self.traffic, self.chips,
                                 self.devices)
        t0 = time.perf_counter()
        self.words, self.docs = self._corpus()
        self.tokens = len(self.words)
        self.log(f"corpus: {s['docs']} docs, {self.tokens} tokens in "
                 f"{time.perf_counter() - t0:.1f} s")
        t0 = time.perf_counter()
        self.app = LightLDA(
            self.words, self.docs, s["vocab_size"],
            LDAConfig(num_topics=s["num_topics"], alpha=s["alpha"],
                      beta=s["beta"], sampler="tiled", stale_words=True,
                      doc_blocked=True, block_tokens=s["block_tokens"],
                      block_docs=s["block_docs"],
                      batch_tokens=s["batch_tokens"], steps_per_call=1,
                      precision=s["precision"],
                      seed=self.seed & 0x7FFFFFFF),
            mesh=mesh)
        self.log(f"LightLDA built in {time.perf_counter() - t0:.1f} s, "
                 f"{self.app.calls_per_sweep} calls a sweep")
        self._jax = jax
        # the first sweeps: they compile, and the check follows them
        # (assignments() reads z in the order the corpus was handed over)
        self.z_first = [self.app.assignments()]
        for _ in range(int(self.traffic["checked_sweeps"])):
            self._sweep()
            self.z_first.append(self.app.assignments())

    def _sweep(self) -> None:
        jax = self._jax
        with jax.profiler.TraceAnnotation("bench.lda.sweep"):
            self.app.sweep()
        with jax.profiler.TraceAnnotation("bench.lda.sync"):
            # the fence: the rebuilt word table and the summary table
            jax.block_until_ready((self.app.word_topic.raw(),
                                   self.app.summary.raw()))

    # -- the window ----------------------------------------------------------

    registry_snapshot = staticmethod(program.registry_snapshot)

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        sweeps, times = 0, []
        end = t0
        while True:
            self._sweep()
            now = time.perf_counter()
            took, end = now - end, now
            times.append(round(took, 4))
            sweeps += 1
            if end - t0 + took > seconds:   # whole sweeps, inside it
                break
        elapsed = end - t0
        self.log(f"sweeps of the window took {times} s")
        done = sweeps * self.tokens
        return {"attempted": sweeps, "failed": 0,
                "metrics": {"lda_doc_tokens_per_s": done / elapsed},
                "work": {"tokens": done, "sweeps": sweeps},
                "values": {"window_s": elapsed}}

    # -- what correct compares ---------------------------------------------

    def collect(self) -> None:
        import jax.numpy as jnp

        app = self.app
        self.z_last = self.app.assignments()
        self.nwk_prog = app.word_topic.get()
        self.nk_prog = app.summary.get()
        rows = app._blk_of_doc * app._maxd + app._row_of_doc
        flat = app._ndk.reshape(-1, app.K)
        self.ndk_prog = np.asarray(jnp.take(
            flat, jnp.asarray(rows.astype(np.int32)), axis=0))
        self.app = app = flat = None
        program.free()

    def check(self) -> list:
        import jax.numpy as jnp

        s = self.sizes
        D, V, K = s["docs"], s["vocab_size"], s["num_topics"]
        priors = dict(alpha=float(s["alpha"]), beta=float(s["beta"]))
        t0 = time.perf_counter()
        w, d, m = (jnp.asarray(x)
                   for x in ref.pad_stream(self.words, self.docs))

        def dev(z):
            zp = np.zeros(w.size, np.int32)
            zp[: len(z)] = z
            return jnp.asarray(zp.reshape(w.shape))

        sizes = dict(D=D, V=V, K=K)
        # 1. the tables the window left, against counts of its own z.
        #    The word table agrees only where z[i] IS the topic of
        #    words[i]: the order assignments() promises
        ndk, nwk, nk = ref.counts(dev(self.z_last), w, d, m, **sizes)
        order_bad = int((np.asarray(nwk) != self.nwk_prog[:V, :K]).sum())
        bad = order_bad \
            + int((np.asarray(ndk) != self.ndk_prog[:, :K]).sum()) \
            + int((np.asarray(nk) != self.nk_prog[:K]).sum())
        del ndk, nwk, nk
        # 2. the first sweeps, followed by the reference from its own
        #    random start
        key = corpus.prng_key(self.seed, 2)
        followed = ref.follow(ref.random_start(key, w.shape, K), w, d, m,
                              key, len(self.z_first) - 1,
                              every=self.loglik_every, **sizes, **priors)
        checks = []
        for i, st_ref in enumerate(followed, 1):
            zp = dev(self.z_first[i])
            st = ref.stats(ref.counts(zp, w, d, m, **sizes), zp, w, d, m,
                           every=self.loglik_every, V=V, **priors)
            del zp
            st["moved_share"] = float(
                (self.z_first[i - 1] != self.z_first[i]).mean())
            for name, gap in ref.gaps(st, st_ref, self.tokens).items():
                checks.append((f"{name}_s{i}", gap))
            self.log(f"sweep {i}: program " + json.dumps(
                {k: v for k, v in st.items() if k != "topic_sizes"})
                + " reference " + json.dumps(
                {k: v for k, v in st_ref.items() if k != "topic_sizes"}))
        self.log(f"reference took {time.perf_counter() - t0:.1f} s")
        out = [{"name": n, "value": v, "limit": self.limits[n]}
               for n, v in checks]
        out.append({"name": "count_tables_mismatch", "value": bad,
                    "limit": 0})
        out.append({"name": "stream_order_mismatch", "value": order_bad,
                    "limit": 0})
        return out

    def close(self) -> None:
        self.app = None
