"""Distributed word2vec — TPU-native rebuild of the reference's
`Applications/WordEmbedding/` (upstream layout; SURVEY.md §3.6/§4.5):
skip-gram & CBOW, negative sampling & hierarchical softmax, embeddings in
two row-sharded MatrixTables.

Reference shape (SURVEY.md §4.5): `Distributed_wordembedding` main +
`WordEmbedding` model math + N `Trainer` threads doing local scalar SGD on
per-block row copies + `ParameterLoader` prefetch + per-block delta
aggregation `Add`ed to the MatrixTables.

TPU design (the whole point — nothing here is a translation):

- The per-pair scalar loop (dot/sigmoid/axpy over one row pair at a time)
  becomes a **batched jitted superstep**: ``lax.scan`` over S minibatches
  of B pairs, each step = gather rows → one einsum against the MXU →
  analytic sigmoid gradients → duplicate-safe scatter-add. One dispatch
  trains S*B pairs.
- The reference's Trainer-thread Hogwild + per-block aggregation becomes
  the batched scatter-add: duplicate rows within a minibatch accumulate
  additively, exactly the reference's Aggregator semantics. The tables
  are held in whole (8, 128) tiles, row by row. On one device the step's
  lanes are sorted by row and each distinct 8-row group of a table is
  read, added to and written once (`ops/distinct_rows.py`); sharded
  tables go through XLA's `.at[].add`.
- Negative sampling runs **on device**: by default a precomputed unigram
  table (the reference word2vec's own ``InitUnigramTable`` — one uniform
  + ONE gather per draw), or the exact Vose alias method
  (``ns_sampler="alias"``); no host RNG in the hot loop
  (`jax.random.fold_in`-per-step keys keep it reproducible across chips).
- Data parallelism: the pair stream is sharded over the mesh ``"data"``
  axis; the embedding tables keep their row sharding, so XLA inserts the
  cross-chip reduction of the scatter contributions (psum over ICI) —
  the Get/Add round-trip of SURVEY.md §4.2/§4.3 collapsed into one
  compiled program.
- Hierarchical softmax uses the Huffman (codes, points) arrays from the
  data layer, padded to fixed length with a masked scratch row — static
  shapes for XLA.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from functools import partial
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from multiverso_tpu import client, core, telemetry
from multiverso_tpu.data.corpus import Corpus
from multiverso_tpu.ops import distinct_rows, interpret_mode
from multiverso_tpu.tables import MatrixTable, make_superstep
from multiverso_tpu.utils import log


@dataclasses.dataclass
class W2VConfig:
    """The reference app's argv config (word2vec-style flags)."""
    embedding_dim: int = 100
    window: int = 5
    negative: int = 5           # negatives per positive (NS objective)
    model: str = "skipgram"     # "skipgram" | "cbow"
    objective: str = "ns"       # "ns" (negative sampling) | "hs" (Huffman)
    batch_size: int = 1024      # pairs per scan step
    steps_per_call: int = 16    # scan length: pairs/dispatch = B * S
    learning_rate: float = 0.025
    min_lr_frac: float = 1e-4   # linear decay floor (lr * frac)
    epochs: int = 1
    subsample: Optional[float] = None   # None -> keep the corpus's setting
    unigram_power: float = 0.75
    ns_sampler: str = "table"   # "table" — the reference word2vec's own
    # unigram-table draw (one uniform + ONE gather from a precomputed id
    # table; measured ~130us/step cheaper than alias on the chip) |
    # "alias" — exact Vose alias draw (two gathers; use when the vocab
    # is too skewed for table quantization, see ns_table_size)
    ns_table_size: int = 1 << 20    # table quantization: each table slot
    # is 2^-20 of the noise mass (the reference used a 1e8-entry table
    # for the same purpose; 1M slots bounds per-word probability error
    # at ~1e-6 of mass, negligible for NS)
    max_code_len: int = 40      # HS: Huffman code pad length
    local_data: bool = False    # multi-process: each process generates
    # ONLY its devices' share of every batch from ITS OWN corpus shard
    # (seed folded with the rank so streams differ) — the reference's
    # workers-each-stream-their-own-corpus model. batch_size stays the
    # GLOBAL batch; processes must own disjoint data lanes (validated).
    # Call counts are agreed collectively from the shards' sizes; each
    # process cycles its local corpus to fill the agreed schedule.
    checkpoint_prefix: str = ""     # periodic mid-train checkpoints
    checkpoint_interval: int = 0    # store every N superstep calls
    # (0 = end-of-training dumps only — the reference worker's [H]
    # behavior; the periodic trigger mirrors SURVEY §6.4's flag-driven
    # periodic server dump)
    seed: int = 0
    dtype: str = "float32"


def _normalized_rows(emb: np.ndarray) -> np.ndarray:
    """Rows scaled to unit norm (zero rows guarded)."""
    return emb / np.maximum(
        np.linalg.norm(emb, axis=1, keepdims=True), 1e-12)


def _topk_excluding(norm: np.ndarray, q: np.ndarray,
                    exclude, k: int) -> np.ndarray:
    """Top-k row ids of ``norm`` by dot with ``q``, excluding ids
    (shared by nearest() and the compute-accuracy analogy rule)."""
    sims = norm @ q
    sims[list(exclude)] = -np.inf
    return np.argsort(-sims)[:k]


def build_alias(probs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Vose alias-table construction, O(V).

    Returns (prob f32[V], alias int32[V]): sample j ~ U[0,V), u ~ U[0,1);
    result = j if u < prob[j] else alias[j].
    """
    v = len(probs)
    prob = np.zeros(v, np.float64)
    alias = np.zeros(v, np.int32)
    scaled = probs.astype(np.float64) * v
    small = [i for i in range(v) if scaled[i] < 1.0]
    large = [i for i in range(v) if scaled[i] >= 1.0]
    while small and large:
        s, l = small.pop(), large.pop()
        prob[s] = scaled[s]
        alias[s] = l
        scaled[l] = scaled[l] - (1.0 - scaled[s])
        (small if scaled[l] < 1.0 else large).append(l)
    for i in large + small:
        prob[i] = 1.0
    return prob.astype(np.float32), alias


def alias_sample(key, prob: jax.Array, alias: jax.Array, shape):
    """Draw ids from the alias table (two gathers, no host round-trip)."""
    kj, ku = jax.random.split(key)
    j = jax.random.randint(kj, shape, 0, prob.shape[0])
    u = jax.random.uniform(ku, shape)
    return jnp.where(u < prob[j], j, alias[j]).astype(jnp.int32)


def build_unigram_table(probs: np.ndarray, size: int) -> np.ndarray:
    """The reference word2vec's ``InitUnigramTable``: an int32[size]
    table where word w fills a run of slots proportional to probs[w];
    a draw is one uniform scaled to a slot index — ONE gather on device
    (vs the alias method's two), at a quantization of 1/size of the
    total mass per slot."""
    cum = np.cumsum(probs.astype(np.float64))
    cum /= cum[-1]
    # slot i covers mass ((i+0.5)/size); searchsorted maps it to a word
    return np.searchsorted(
        cum, (np.arange(size) + 0.5) / size).astype(np.int32)


def table_sample(key, table: jax.Array, shape):
    """Draw ids from the unigram table: uniform -> slot -> id."""
    u = jax.random.uniform(key, shape)
    idx = (u * table.shape[0]).astype(jnp.int32)
    return jnp.take(table, idx, axis=0)


class WordEmbedding:
    """The app: two MatrixTables + the fused scan superstep."""

    def __init__(self, corpus: Corpus, config: W2VConfig, *,
                 mesh=None, name: str = "w2v") -> None:
        self.corpus = corpus
        self.config = config
        self.mesh = mesh if mesh is not None else core.mesh()
        c = config
        # an explicit config subsample (word2vec's -sample) overrides the
        # corpus's; None defers to whatever the corpus was built with
        if c.subsample is not None:
            corpus.set_subsample(c.subsample)
        v, d = corpus.vocab_size, c.embedding_dim
        # the distinct-row writer is one Pallas call, which GSPMD cannot
        # split: it runs where both tables lie whole on one device (and
        # hold 32-bit words, what its (8, 128) row groups are made of);
        # sharded tables keep XLA's scatter (_scatter_rows, the one fork)
        self._whole = (self.mesh.devices.size == 1
                       and jnp.dtype(c.dtype).itemsize == 4)
        with telemetry.span("w2v.setup.init_tables"):
            def drawn(shape, dtype, sharding):
                # reference init: input embeddings ~ U(-0.5/dim, 0.5/dim),
                # drawn into the table's padded shape (one 3.6 GB copy
                # less on the host)
                rng = np.random.default_rng(c.seed)
                init = np.zeros(shape, dtype)
                init[:v, :d] = rng.uniform(-0.5 / d, 0.5 / d, (v, d))
                return jax.device_put(init, sharding)
            # on every mesh the tables are held in whole (8, 128) tiles,
            # row by row: what the writer's DMAs need, and what spares
            # every call a transposed copy of both (PERF.md §6, PR 33);
            # rows past v and columns past d stay zero. The output
            # table's zeros are made on the device
            self.w_in = MatrixTable(v, d, c.dtype, init_value=drawn,
                                    updater="default", mesh=self.mesh,
                                    name=f"{name}_in", tile_aligned=True)
            self.w_out = MatrixTable(v, d, c.dtype,
                                     init_value=core.sharded_zeros,
                                     updater="default", mesh=self.mesh,
                                     name=f"{name}_out", tile_aligned=True)
        self._scratch = self.w_in.padded_shape[0] - 1  # masked-lane row
        # MVTPU_STALENESS: embeddings() (logging/eval — nearest,
        # similarity, analogy; never fed back into training) serves from
        # a bounded-staleness cached view; save_text stays exact
        self._emb_view = client.maybe_cached_view(self.w_in)

        # negative-sampling alias table: device-resident constants, placed
        # replicated ON THE MESH (a bare jnp.asarray would land them on the
        # process default device, which may be a different platform)
        rep = partial(core.place, mesh=self.mesh)
        with telemetry.span("w2v.setup.vocab_tables"):
            if c.objective == "ns":
                if c.ns_sampler == "table":
                    self._ns_table = rep(build_unigram_table(
                        corpus.unigram_probs(c.unigram_power),
                        c.ns_table_size))
                elif c.ns_sampler == "alias":
                    p, a = build_alias(
                        corpus.unigram_probs(c.unigram_power))
                    self._alias_prob = rep(p)
                    self._alias_idx = rep(a)
                else:
                    raise ValueError(f"ns_sampler must be 'table' or "
                                     f"'alias', got {c.ns_sampler!r}")
            elif c.objective == "hs":
                codes, points, lengths = corpus.huffman(c.max_code_len)
                L = c.max_code_len
                # mask beyond each word's code length; park masked lanes
                # on the scratch row so the scatter is shape-static
                msk = np.arange(L)[None, :] < lengths[:, None]
                pts = np.where(msk, points[:, :L], self._scratch)
                self._hs_points = rep(pts.astype(np.int32))
                self._hs_codes = rep(codes[:, :L].astype(np.float32))
                self._hs_mask = rep(msk.astype(np.float32))
            else:
                raise ValueError(f"objective must be 'ns' or 'hs', "
                                 f"got {c.objective!r}")
        if c.model not in ("skipgram", "cbow"):
            raise ValueError(f"model must be 'skipgram' or 'cbow', "
                             f"got {c.model!r}")
        self._key = core.prng_key(c.seed, mesh=self.mesh)
        self.run_ckpt = None        # ft.checkpoint.wire_app attaches
        self._step_no = 0
        self._sched_offset = 0      # set by load(): resumed-call count
        self._sched_plan = 0        # set by load(): original planned
        # call count (0 = fresh run; train() re-plans per call as today)
        self._train_plan = 0        # last train()'s effective plan
        self._scattered: list = []  # per call, until train()'s fence
        self._last_store = ()       # (prefix, step) of the last store
        self.loss_history: list = []
        self._local_chunks = None   # local_data: [(device, b0, b1), ...]
        if c.local_data and jax.process_count() > 1:
            self._setup_local_data()
        self._build_superstep()

    def _setup_local_data(self) -> None:
        """Per-process data lanes: which contiguous B-chunks of the
        global batch this process's devices own (sorted by offset), with
        a single-owner validation across processes and a shared-
        dictionary check (the replicated NS table / Huffman arrays and
        the table shapes are all built from the local corpus — every
        process must hold the SAME dictionary, only the token stream is
        per-process)."""
        import zlib
        from multiverso_tpu.parallel.multihost import (
            allgather_i64, owned_axis_slices, validate_single_owner)
        c = self.config
        B = c.batch_size
        sh = NamedSharding(self.mesh, P(None, core.DATA_AXIS, None))
        self._dev_slices = owned_axis_slices(
            sh, (c.steps_per_call, B, 1), axis=1)
        # distinct chunks (in-process model replicas share one), sorted:
        # the local batch is their concatenation in offset order
        self._local_chunks = sorted({(b0, b1)
                                     for _, b0, b1 in self._dev_slices})
        self._local_batch = sum(b1 - b0 for b0, b1 in self._local_chunks)
        mask = np.zeros(B, np.int32)
        for b0, b1 in self._local_chunks:
            mask[b0:b1] = 1
        validate_single_owner(mask, "local_data")
        counts = np.ascontiguousarray(
            np.asarray(self.corpus.unigram_probs(c.unigram_power),
                       np.float64))
        digest = np.array([self.corpus.vocab_size,
                           zlib.crc32(counts.tobytes())], np.int64)
        gathered = allgather_i64(digest)
        if not np.all(gathered == gathered[0]):
            raise ValueError(
                "local_data requires the SAME dictionary (vocab + "
                "frequencies) on every process — only the token stream "
                f"is per-process; got per-rank (vocab, counts-crc32) = "
                f"{gathered.tolist()}")

    # -- the fused superstep ----------------------------------------------

    def _pos_neg_step(self, w_out, v, tgt, key, lr):
        """Shared NS inner math: v [B,D] input vectors vs target ids [B].
        Returns (w_out', the scatter's [lanes, rows written], grad wrt
        v [B,D], mean loss)."""
        c = self.config

        @telemetry.scope("w2v.negatives")
        def target_ids(tgt, key):
            if c.ns_sampler == "table":
                negs = table_sample(key, self._ns_table,
                                    (tgt.shape[0], c.negative))
            else:
                negs = alias_sample(key, self._alias_prob,
                                    self._alias_idx,
                                    (tgt.shape[0], c.negative))
            return jnp.concatenate([tgt[:, None], negs], axis=1)

        @telemetry.scope("w2v.math")
        def math(v, u, lr):
            logits = jnp.einsum("bd,bkd->bk", v, u)
            labels = jnp.zeros_like(logits).at[:, 0].set(1.0)
            sig = jax.nn.sigmoid(logits)
            # binary CE on (pos, negs); analytic grad dL/dlogit = sig - label
            loss = -jnp.mean(
                jnp.sum(labels * jax.nn.log_sigmoid(logits)
                        + (1.0 - labels) * jax.nn.log_sigmoid(-logits),
                        axis=1))
            g = (sig - labels) * lr                           # [B, 1+K]
            grad_v = jnp.einsum("bk,bkd->bd", g, u)
            return loss, grad_v, g

        ids = target_ids(tgt, key)                            # [B, 1+K]
        u = self._gather_out(w_out, ids)                      # [B, 1+K, D]
        loss, grad_v, g = math(v, u, lr)
        w_out, rows = self._scatter_out(w_out, ids, g, v)
        return w_out, rows, grad_v, loss

    # the phases of the fused body that both objectives share, each under
    # a program scope: the compiled ops carry the name
    # (profiling.op_scopes)
    def _gather_out(self, w_out, ids):
        @telemetry.scope("w2v.gather_out")
        def gather(w_out, ids):
            return self._take(w_out, ids)                     # [B, n, D]
        return gather(w_out, ids)

    def _take(self, table, ids):
        """Rows of a table without the padding columns it is held with:
        the step's arithmetic is the reference's, D wide."""
        return jnp.take(table, ids, axis=0)[..., :self.config.embedding_dim]

    def _scatter_rows(self, table, ids, coef, rows):
        """``table[ids[b, k]] -= coef[b, k] * rows[b]``, duplicates
        summed: both scatters of a step are this outer product, widened
        by the table's padding columns. Where the table lies whole on
        one device the lanes go sorted through the distinct-row writer,
        each lane's update row formed from ``coef`` and ``rows`` in the
        order it asks for ([B, n, D] is never built and permuted); where
        it is sharded, through XLA's scatter. Returns the table and the
        step's [lanes, rows written]: a row once a run of its lanes
        under the writer, once a lane under the scatter."""
        flat_ids, n = ids.reshape(-1), ids.shape[1]
        lanes = flat_ids.shape[0]
        pad = ((0, 0), (0, table.shape[1] - rows.shape[1]))
        if self._whole:
            flat = coef.reshape(-1)
            table, written = distinct_rows.add_rows(
                table, flat_ids,
                lambda order: jnp.pad(-jnp.take(flat, order)[:, None]
                                      * jnp.take(rows, order // n, axis=0),
                                      pad),
                interpret=interpret_mode(self.mesh))
        else:
            # the outer product as the scatter's own operand, as wide as
            # the table (a scatter into its first D columns alone lowers
            # to a loop of slice updates: 40x slower, PERF.md §6)
            update = -(coef[:, :, None] * jnp.pad(rows, pad)[:, None, :])
            table = table.at[flat_ids].add(
                update.reshape(lanes, -1).astype(table.dtype))
            written = jnp.int32(lanes)
        return table, jnp.stack([jnp.int32(lanes), written])

    def _scatter_out(self, w_out, ids, g, v):
        """``w_out[ids[b, k]] -= g[b, k] * v[b]``: ``grad_u``
        scatter-added."""
        return telemetry.scope("w2v.scatter_out")(self._scatter_rows)(
            w_out, ids, g, v)

    def _hs_step(self, w_out, v, tgt, lr):
        """Hierarchical-softmax inner math along the Huffman path."""
        pts = jnp.take(self._hs_points, tgt, axis=0)          # [B, L]
        code = jnp.take(self._hs_codes, tgt, axis=0)          # [B, L] 0/1
        msk = jnp.take(self._hs_mask, tgt, axis=0)            # [B, L]

        @telemetry.scope("w2v.math")
        def math(v, u, lr):
            logits = jnp.einsum("bd,bld->bl", v, u)
            sig = jax.nn.sigmoid(logits)
            # label = code bit: P(go-right) modeled by sigmoid
            loss = -jnp.sum(
                msk * (code * jax.nn.log_sigmoid(logits)
                       + (1 - code) * jax.nn.log_sigmoid(-logits))
            ) / jnp.maximum(jnp.sum(msk), 1.0)
            g = (sig - code) * msk * lr                       # [B, L]
            grad_v = jnp.einsum("bl,bld->bd", g, u)
            return loss, grad_v, g

        u = self._gather_out(w_out, pts)                      # [B, L, D]
        loss, grad_v, g = math(v, u, lr)
        w_out, rows = self._scatter_out(w_out, pts, g, v)
        return w_out, rows, grad_v, loss

    def _build_superstep(self) -> None:
        c = self.config
        cbow = c.model == "cbow"

        @telemetry.scope("w2v.gather_in")
        def gather_in(w_in, src):
            if not cbow:
                return self._take(w_in, src), None, None         # [B, D]
            # src [B, 2w] context ids (scratch row = padding)
            ctx_mask = (src != self._scratch).astype(w_in.dtype)
            n_ctx = jnp.maximum(ctx_mask.sum(axis=1, keepdims=True), 1.0)
            vecs = self._take(w_in, src)                      # [B, 2w, D]
            return (jnp.einsum("bwd,bw->bd", vecs, ctx_mask) / n_ctx,
                    ctx_mask, n_ctx)

        @telemetry.scope("w2v.scatter_in")
        def scatter_in(w_in, src, grad_v, ctx_mask, n_ctx):
            if not cbow:
                # a centre's pairs arrive together, so the writer's sort
                # finds its runs nearly made; any order is right
                return self._scatter_rows(
                    w_in, src[:, None], jnp.ones_like(grad_v[:, :1]), grad_v)
            # the input-side gradient spread over the context words
            return self._scatter_rows(w_in, src, ctx_mask, grad_v / n_ctx)

        def scan_body(carry, inp):
            w_in, w_out, rows = carry
            src, tgt, key, lr = inp
            v, ctx_mask, n_ctx = gather_in(w_in, src)
            if c.objective == "ns":
                w_out, rows_out, grad_v, loss = self._pos_neg_step(
                    w_out, v, tgt, key, lr)
            else:
                w_out, rows_out, grad_v, loss = self._hs_step(
                    w_out, v, tgt, lr)
            w_in, rows_in = scatter_in(w_in, src, grad_v, ctx_mask, n_ctx)
            return (w_in, w_out, rows + jnp.stack([rows_in, rows_out])), loss

        def body(params, states, locals_, options, pairs, key, lrs):
            # pairs [S, B, ctx+1]: context ids + target in ONE operand
            # (one H2D placement per call instead of two; what a
            # placement costs is not measured on the current host);
            # may arrive int16 (see _place) — widen on device
            pairs = pairs.astype(jnp.int32)
            srcs = pairs[..., :-1] if cbow else pairs[..., 0]
            tgts = pairs[..., -1]
            keys = jax.random.split(key, pairs.shape[0])
            # rows: the call's [in | out] x [lanes, rows written]
            (w_in, w_out, rows), losses = lax.scan(
                scan_body, (*params, jnp.zeros((2, 2), jnp.int32)),
                (srcs, tgts, keys, lrs))
            return (w_in, w_out), states, locals_, (losses.mean(), rows)

        # the supported fused-update path: donation, out-shardings, and
        # step/generation counting live in the table layer
        self._fused = make_superstep((self.w_in, self.w_out), body,
                                     name="w2v_superstep")

    # -- data placement ----------------------------------------------------

    def _place(self, srcs: np.ndarray, tgts: np.ndarray):
        """Shard the pair stream over the data axis — ONE combined
        [S, B, ctx+1] placement per call (src ids + target packed along
        the trailing axis; the fused body unslices for free). Ids ship
        as int16 when the padded vocab fits — the pair stream is the
        whole H2D byte budget of training, so halving it halves the
        transfer cost on any host; the fused body widens back to
        int32."""
        if srcs.ndim == 2:      # skipgram: [S, B] -> [S, B, 1]
            srcs = srcs[..., None]
        pairs = np.concatenate([srcs, tgts[..., None]], axis=-1)
        if self._scratch < np.iinfo(np.int16).max:
            pairs = pairs.astype(np.int16)
        sh = NamedSharding(self.mesh, P(None, core.DATA_AXIS, None))
        if self._local_chunks is None:
            return jax.device_put(pairs, sh)
        # local_data: ``pairs`` is this process's [S, B_local, C] share;
        # slice it back out per device (replicas get the same chunk) and
        # assemble the global array — no process ships another's lanes
        c = self.config
        off = {}
        acc = 0
        for b0, b1 in self._local_chunks:
            off[b0] = acc
            acc += b1 - b0
        shards = [jax.device_put(
            pairs[:, off[b0]:off[b0] + (b1 - b0)], d)
            for d, b0, b1 in self._dev_slices]
        return jax.make_array_from_single_device_arrays(
            (c.steps_per_call, c.batch_size, pairs.shape[-1]), sh, shards)

    # -- training ----------------------------------------------------------

    def _batches(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        c = self.config
        if self._local_chunks is not None:
            return self._local_batches()
        if c.model == "skipgram":
            it = self.corpus.skipgram_batches(
                c.batch_size, window=c.window, seed=c.seed, epochs=c.epochs)
            # skip-gram trains (center → context): src = center
            return it
        return self.corpus.cbow_batches(
            c.batch_size, window=c.window, seed=c.seed, epochs=c.epochs,
            pad_id=self._scratch)

    def _local_batches(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """local_data: this process's [*, B_local] share of every batch
        from ITS corpus shard, rank-folded seed, cycling the shard
        forever (train() bounds the loop with the agreed call count)."""
        c = self.config
        rank = jax.process_index()
        bl = self._local_batch
        epoch = 0
        while True:
            seed = c.seed + 7919 * (rank + 1) + 104729 * epoch
            if c.model == "skipgram":
                it = self.corpus.skipgram_batches(
                    bl, window=c.window, seed=seed, epochs=1)
            else:
                it = self.corpus.cbow_batches(
                    bl, window=c.window, seed=seed, epochs=1,
                    pad_id=self._scratch)
            got = False
            for item in it:
                got = True
                yield item
            if not got:
                # an empty shard must fail LOUDLY: returning here would
                # leave this process with zero dispatches while the
                # others run the agreed collective schedule — deadlock
                raise ValueError(
                    f"local_data: this process's corpus shard yields no "
                    f"{self._local_batch}-pair batches; every process "
                    "must contribute data (or drop local_data)")
            epoch += 1

    def train(self, total_steps: Optional[int] = None) -> float:
        """Run the full training loop; returns the final mean loss."""
        c = self.config
        d = self.mesh.shape[core.DATA_AXIS]
        if c.batch_size % d:
            raise ValueError(f"batch_size {c.batch_size} not divisible by "
                             f"data-axis size {d}")
        # linear lr decay over the whole corpus (reference's alpha decay);
        # skip-gram emits ~2b pairs per center, b ~ U[1, window] -> E = w+1
        tokens = self.corpus.num_tokens
        if self._local_chunks is not None and jax.process_count() > 1:
            # local_data: the schedule must be identical on every
            # process — agree on the GLOBAL token count (int64-safe)
            from multiverso_tpu.parallel.multihost import allgather_i64
            tokens = int(allgather_i64([tokens]).sum())
        est_pairs = tokens * c.epochs * (c.window + 1) \
            if c.model == "skipgram" else tokens * c.epochs
        est_calls = max(int(est_pairs) //
                        (c.batch_size * c.steps_per_call), 1)
        if total_steps is not None:
            est_calls = max(total_steps // c.steps_per_call, 1)
        elif self._local_chunks is not None:
            # the cycling local generator never exhausts — the agreed
            # schedule is the stop condition
            total_steps = est_calls * c.steps_per_call

        # the plan a periodic store persists: the original schedule when
        # resumed, else this run's own estimate
        self._train_plan = self._sched_plan or est_calls
        S = c.steps_per_call
        buf: list = []              # one call's (src, tgt) batches
        losses, call_no = [], 0
        self._scattered = []        # _dispatch: what the scatters met
        t0 = time.perf_counter()
        # host pair generation overlaps device compute (the reference's
        # ParameterLoader/ASyncBuffer pipelining role, SURVEY.md §4.5);
        # named, so the producer thread times itself (w2v.pairs.produce,
        # w2v.pairs.backpressure)
        from multiverso_tpu.utils.async_buffer import prefetch_iterator
        batches = prefetch_iterator(self._batches(), depth=2 * S,
                                    name="w2v.pairs")
        try:
            while True:
                # what this call waited for data/ (the producer runs
                # ahead while the device works, so mostly the first)
                with telemetry.span("w2v.wait_data"):
                    buf = list(itertools.islice(batches, S))
                if len(buf) < S:
                    break
                losses.append(self._dispatch(*self._stack(buf), call_no,
                                             est_calls))
                buf = []
                call_no += 1
                if telemetry.health.maybe_rollback(self) is not None:
                    # divergence rollback: tables + the step cursor are
                    # back at the last clean generation (LR decay and the
                    # fold_in key sequence re-align through _step_no). The
                    # pair stream itself cannot rewind — training resumes
                    # on fresh batches from the restored parameters, which
                    # for a stochastic stream is equivalent to a replay.
                    # Checked BEFORE maybe_save so a diverged state is
                    # never committed as a generation.
                    continue
                if self.run_ckpt is not None:
                    # run-level manager (preferred over the bespoke prefix
                    # dump): atomically-committed generations, keep-K
                    # retention, overlapped writes; collective — every
                    # process reaches the same call_no in lockstep
                    self.run_ckpt.maybe_save(
                        self._step_no // S, self.run_state)
                elif c.checkpoint_interval > 0 and c.checkpoint_prefix \
                        and call_no % c.checkpoint_interval == 0:
                    # legacy periodic mid-train dump (SURVEY §6.4's
                    # flag-driven trigger); collective
                    self.store(c.checkpoint_prefix)
                if total_steps is not None and call_no * S >= total_steps:
                    break
        finally:
            batches.close()         # cancels the producer thread
        if call_no == 0 and buf:
            # corpus smaller than one superstep: pad by cycling the
            # buffered batches to the static scan length (slight pair
            # over-weighting beats training nothing / a full recompile)
            log.warn("w2v corpus yields < %d batches; cycling %d to fill "
                     "one superstep", S, len(buf))
            losses.append(self._dispatch(
                *self._stack([buf[i % len(buf)] for i in range(S)]),
                0, est_calls))
            call_no = 1
        # trailing partial buffer is otherwise dropped (like per-batch
        # remainders): a shorter scan length would force a full XLA
        # recompile for one leftover call's worth of pairs
        with telemetry.span("w2v.fence"):
            self.w_in.wait()
            dt = time.perf_counter() - t0
            # ONE device->host transfer for the whole loss list instead
            # of a blocking fetch per scalar (per-fetch cost not measured
            # on the current host)
            self.loss_history = [float(l) for l in
                                 np.asarray(jnp.stack(losses))] \
                if losses else []
            # what the two scatters met, summed on the device over each
            # call's steps: lanes offered, and rows written for them
            if self._scattered:
                met = np.sum(jax.device_get(self._scattered), axis=0)
                for table, (lanes, written) in zip(("in", "out"), met):
                    telemetry.counter("w2v.scatter.rows",
                                      table=table).inc(int(lanes))
                    telemetry.counter("w2v.scatter.rows_written",
                                      table=table).inc(int(written))
        # count the work actually dispatched: with total_steps (or a
        # short corpus) the full-corpus token count would overstate
        # throughput by corpus_batches/steps_run
        pairs_done = call_no * S * c.batch_size
        est_ppt = (c.window + 1) if c.model == "skipgram" else 1.0
        words = pairs_done / est_ppt
        telemetry.counter("w2v.pairs").inc(pairs_done)
        telemetry.emit("w2v.words_per_sec", words / dt, "words/s")
        final = float(np.mean(self.loss_history[-10:])) \
            if losses else float("nan")
        log.info("w2v train done: %d calls, loss=%.4f, %.0f words/s",
                 call_no, final, words / dt)
        return final

    @staticmethod
    def _stack(batches: list) -> Tuple[np.ndarray, np.ndarray]:
        """One call's (src, tgt) batches as two [S, B, ...] arrays."""
        return (np.stack([b[0] for b in batches]),
                np.stack([b[1] for b in batches]))

    def _dispatch(self, srcs: np.ndarray, tgts: np.ndarray,
                  call_no: int, est_calls: int) -> jax.Array:
        c = self.config
        s = srcs.shape[0]
        if self._sched_plan:
            # checkpoint resume: continue the ORIGINAL run's decay and
            # key sequence (past the plan's end the LR floor holds)
            call_no += self._sched_offset
            est_calls = max(self._sched_plan, 1)
        frac = min(call_no / est_calls, 1.0)
        lr_hi = c.learning_rate * (1.0 - frac)
        lr_lo = c.learning_rate * (1.0 - min((call_no + 1) / est_calls, 1.0))
        floor = c.learning_rate * c.min_lr_frac
        lrs = np.maximum(np.linspace(lr_hi, lr_lo, s), floor) \
            .astype(np.float32)
        key = jax.random.fold_in(self._key, call_no)
        with telemetry.span("w2v.place"):
            pd = self._place(srcs, tgts)
        # the host side of the fused dispatch; the step record links to
        # the span (its ``parent``), whose ``dur_s`` is the one timing
        with telemetry.span("w2v.superstep"):
            _, (loss, rows) = self._fused((), pd, key,
                                          core.place(lrs, mesh=self.mesh))
            telemetry.step_timeline("w2v", call_no,
                                    pairs=s * c.batch_size)
        telemetry.beat()    # flight recorder: one heartbeat per dispatch
        self._step_no += s
        # the call's [in | out] x [lanes, rows written], left on the
        # device until train()'s fence
        self._scattered.append(rows)
        return loss

    # -- embeddings out / eval --------------------------------------------

    def embeddings(self) -> np.ndarray:
        """The trained input embeddings [V, D] (the reference saves
        W_in). Under ``MVTPU_STALENESS`` this is a bounded-staleness
        cached read — mid-train eval (nearest/similarity/analogy) stops
        paying a blocking whole-table fetch per call."""
        if self._emb_view is not None:
            return self._emb_view.get()
        return self.w_in.get()

    def nearest(self, word_id: int, k: int = 10) -> np.ndarray:
        """Top-k neighbor ids by cosine similarity (excluding self)."""
        norm = _normalized_rows(self.embeddings())
        return _topk_excluding(norm, norm[word_id], (word_id,), k)

    def similarity(self, a: int, b: int) -> float:
        emb = self.embeddings()
        va, vb = emb[a], emb[b]
        return float(va @ vb / max(np.linalg.norm(va) * np.linalg.norm(vb),
                                   1e-12))

    def analogy(self, a: int, b: int, c: int, k: int = 1) -> np.ndarray:
        """``a : b :: c : ?`` — top-k ids by cosine to (b - a + c), the
        reference word2vec's compute-accuracy evaluation rule (query
        words excluded from the candidates)."""
        norm = _normalized_rows(self.embeddings())
        q = norm[b] - norm[a] + norm[c]
        q = q / max(np.linalg.norm(q), 1e-12)
        return _topk_excluding(norm, q, (a, b, c), k)

    def save_text(self, path: str) -> None:
        """The reference word2vec's text output format: a header line
        ``vocab_size dim`` then one ``word v1 .. vD`` line per word.
        Collective (the embedding fetch is); only process 0 writes."""
        emb = self.w_in.get()   # exact — the persisted artifact never
        # serves from the staleness-bounded view
        if core.rank() != 0:
            return
        words = self.corpus.words
        with open(path, "w", encoding="utf-8") as f:
            f.write(f"{len(words)} {emb.shape[1]}\n")
            for w, row in zip(words, emb):
                f.write(w + " " + " ".join(f"{x:.6g}" for x in row) + "\n")

    META_MAGIC = "mvtpu.w2v.meta.v1"

    def store(self, uri_prefix: str) -> None:
        """Checkpoint both tables + a meta manifest. The meta is
        written LAST and records each table's step, so load() can
        detect a torn set (crash between the three per-file-atomic
        writes) instead of silently training mismatched tables."""
        from multiverso_tpu.tables.base import savez_stream
        self.w_in.store(f"{uri_prefix}.in.npz")
        self.w_out.store(f"{uri_prefix}.out.npz")
        savez_stream(f"{uri_prefix}.meta.npz",
                     {"magic": self.META_MAGIC,
                      "step_no": self._step_no,
                      "steps_per_call": self.config.steps_per_call,
                      "w_in_step": self.w_in.default_option.step,
                      "w_out_step": self.w_out.default_option.step,
                      "sched_plan": self._sched_plan
                      or self._train_plan}, {})
        self._last_store = (uri_prefix, self._step_no)

    def load(self, uri_prefix: str) -> None:
        self.w_in.load(f"{uri_prefix}.in.npz")
        self.w_out.load(f"{uri_prefix}.out.npz")
        from multiverso_tpu.tables.base import loadz_stream
        try:
            manifest, _ = loadz_stream(f"{uri_prefix}.meta.npz",
                                       self.META_MAGIC)
        except FileNotFoundError:
            return          # pre-meta checkpoint: tables only
        # any OTHER failure (corrupt meta, wrong magic, transient read
        # error) must RAISE: silently skipping resume here would leave
        # this process with a different step counter than its peers —
        # lockstep collective training then diverges without an error
        for table, key in ((self.w_in, "w_in_step"),
                           (self.w_out, "w_out_step")):
            if key in manifest and \
                    table.default_option.step != int(manifest[key]):
                raise ValueError(
                    f"w2v checkpoint {uri_prefix!r} is torn: "
                    f"{key}={manifest[key]} in the meta but the loaded "
                    f"table is at step {table.default_option.step} — a "
                    "crash interrupted the three-file store; use an "
                    "older complete checkpoint")
        spc = int(manifest.get("steps_per_call",
                               self.config.steps_per_call))
        if spc != self.config.steps_per_call:
            raise ValueError(
                f"w2v checkpoint {uri_prefix!r} was written with "
                f"steps_per_call={spc}, this app uses "
                f"{self.config.steps_per_call}: the resume offset and "
                "fold_in key sequence are call-indexed, so resuming "
                "under a different call size would replay RNG — "
                "construct the app with the original steps_per_call")
        self._step_no = int(manifest["step_no"])
        # resume CONTINUES the stored run's schedule: the original
        # planned call count rides the meta, so the LR decay picks up
        # exactly where the stored run left off (training past the
        # plan's end stays at the floor LR), and the fold_in key
        # sequence advances instead of replaying. In-session repeated
        # train() calls keep their restart-the-schedule behavior —
        # only load() sets these (and only from a checkpoint whose run
        # actually had a plan).
        self._sched_plan = int(manifest.get("sched_plan", 0))
        if self._sched_plan:
            self._sched_offset = \
                self._step_no // self.config.steps_per_call

    # -- fault tolerance (ft.checkpoint contract) --------------------------

    def run_state(self) -> dict:
        """Train-state for the run manager: the step cursor and the
        ORIGINAL planned call count, so a resumed run continues the
        stored run's LR decay and ``fold_in`` key sequence instead of
        restarting them (same semantics as the meta-file resume)."""
        return {"step_no": self._step_no,
                "steps_per_call": self.config.steps_per_call,
                "sched_plan": self._sched_plan or self._train_plan}

    def restore_run_state(self, restored) -> None:
        spc = int(restored.get("steps_per_call",
                               self.config.steps_per_call))
        if spc != self.config.steps_per_call:
            raise ValueError(
                f"run checkpoint was written with steps_per_call={spc}, "
                f"this app uses {self.config.steps_per_call}: the "
                "resume offset and fold_in key sequence are "
                "call-indexed — construct the app with the original "
                "steps_per_call")
        self._step_no = int(restored.get("step_no", 0))
        self._sched_plan = int(restored.get("sched_plan", 0))
        if self._sched_plan:
            self._sched_offset = \
                self._step_no // self.config.steps_per_call


def main(argv=None) -> None:
    """CLI mirroring the reference's word2vec-style argv."""
    from multiverso_tpu.utils import configure
    configure.define_string("train_file", "", "corpus text file", overwrite=True)
    configure.define_int("size", 100, "embedding dimension", overwrite=True)
    configure.define_int("window", 5, "context window", overwrite=True)
    configure.define_int("negative", 5, "negative samples (0 -> HS)", overwrite=True)
    configure.define_bool("cbow", False, "CBOW instead of skip-gram", overwrite=True)
    configure.define_int("epoch", 1, "epochs", overwrite=True)
    configure.define_int("batch_size", 1024, "pairs per step", overwrite=True)
    configure.define_float("alpha", 0.025, "initial learning rate", overwrite=True)
    configure.define_float("sample", 1e-3, "subsampling threshold", overwrite=True)
    configure.define_int("min_count", 5, "vocab min count", overwrite=True)
    configure.define_string("output_file", "", "embedding checkpoint prefix", overwrite=True)
    configure.define_string("output_text", "", "text-format embedding dump (the reference's output format)", overwrite=True)
    configure.define_int("checkpoint_interval", 0,
                         "store -output_file every N superstep calls "
                         "(0 = only at end)", overwrite=True)
    from multiverso_tpu.ft.checkpoint import define_run_flags, wire_app
    define_run_flags()
    core.init(argv)
    train_file = configure.get_flag("train_file")
    if not train_file:
        raise SystemExit("-train_file is required")
    corpus = Corpus.from_file(train_file,
                              min_count=configure.get_flag("min_count"),
                              subsample=configure.get_flag("sample"))
    neg = configure.get_flag("negative")
    cfg = W2VConfig(
        embedding_dim=configure.get_flag("size"),
        window=configure.get_flag("window"),
        negative=max(neg, 1),
        objective="ns" if neg > 0 else "hs",
        model="cbow" if configure.get_flag("cbow") else "skipgram",
        batch_size=configure.get_flag("batch_size"),
        learning_rate=configure.get_flag("alpha"),
        epochs=configure.get_flag("epoch"),
        subsample=configure.get_flag("sample"),
        checkpoint_prefix=configure.get_flag("output_file"),
        checkpoint_interval=configure.get_flag("checkpoint_interval"),
    )
    app = WordEmbedding(corpus, cfg)
    # fault tolerance: run-level checkpoint/resume, cadence in superstep
    # calls (-ckpt_every / MVTPU_CKPT_EVERY; falls back to the legacy
    # -checkpoint_interval cadence, default 50 calls)
    mgr = wire_app(app, [app.w_in, app.w_out],
                   every_default=cfg.checkpoint_interval or 50)
    # flight recorder: env-gated stall watchdog + device capture (the
    # per-dispatch beat is in _dispatch)
    with telemetry.maybe_watchdog("w2v"), telemetry.profile_window("w2v"):
        app.train()
    if mgr is not None:
        mgr.close()     # drain pending background checkpoint writes
    telemetry.record_device_memory()
    out = configure.get_flag("output_file")
    # skip the end-of-train dump when the last periodic store already
    # wrote this exact state (a second full collective dump is pure
    # waste at scale)
    if out and app._last_store != (out, app._step_no):
        app.store(out)
    out_text = configure.get_flag("output_text")
    if out_text:
        app.save_text(out_text)
    core.barrier()


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
