"""Distributed LDA — TPU-native rebuild of the reference's LightLDA
companion app (SURVEY.md §3.6: `lightlda` main, `Trainer`,
`LightDocSampler` (MH + alias), `AliasTable`, `DataBlock`, `Meta`,
`Eval`): web-scale topic modeling over a word-topic count matrix
(SparseMatrixTable) + topic-summary row (ArrayTable), doc blocks streamed,
local deltas aggregated then sparse-added.

TPU-first redesign (deliberate — NOT a port of the sampler):

LightLDA's O(1)-per-token Metropolis-Hastings-with-alias-tables sampler
exists because O(K) per token is unaffordable on a scalar CPU. On TPU the
economics invert: an O(K) **vectorized collapsed-Gibbs** step — gather the
token's doc-topic and word-topic count rows, form the K posterior weights
on the VPU in linear space, sample by inverse-CDF (cumsum + one uniform
per token) — is *exact* (no proposal bias, no MH rejections) and
converges in fewer sweeps than MH. The alias tables, proposal splitting
and acceptance ratios are CPU machinery with no TPU reason to exist; what
is preserved is the *model contract*: same collapsed posterior
p(z=k | rest) ∝ (N_dk + α)(N_wk + β)/(N_k + Vβ), same count-matrix state
in the same tables, same streamed-block training shape.

Batch-parallel sampling uses batch-stale counts — exactly the AD-LDA
approximation the reference already makes across workers (its workers
sample against a stale model fetched per slice); here the staleness
window is one minibatch instead of one model-slice fetch.

Two samplers, chosen by ``LDAConfig.sampler``, both on dp x mp meshes:

- ``"gibbs"`` — the exact vectorized collapsed Gibbs in plain XLA over a
  shuffled token stream. The reference the tests hold the other to, and
  the only path for a topic count that is not a multiple of 128 or a
  document longer than ``block_tokens``.
- ``"tiled"`` — the doc-blocked stale sampler, the one the benchmark's
  LDA cells run (14.59M doc-tokens/s on one v5e chip: PERF_LEDGER.jsonl,
  PR 29, ``lda_nytimes_dp1``). The doc-sorted stream is packed into
  whole-document kernel blocks that own exclusive slices of a blocked
  int16 doc-count array, so the doc side (row gather + count moves)
  happens in VMEM by one-hot matmuls inside the Pallas kernel
  (ops.gibbs_sample_docblock). Word rows follow the reference's own
  slice-level staleness: gathered from a bf16 mirror refreshed once a
  sweep, the int32 master rebuilt from z at sweep end. Resident, a
  step's blocks — lanes, z, doc counts — are split over EVERY chip of
  the mesh, data x model, via shard_map (summary deltas psum'd over
  both axes): no two chips sample the same tokens. With
  ``stream_blocks`` (and ``local_corpus``) the stream stays on the host
  and its blocks split over the data axis alone.

The int32 word table stays row-sharded over the model axis — the
reference's Meta vocab-slicing role — and the per-sweep master rebuild
scatters each chip's data shard into its vocab slice, psum'd over the
data axis, so no chip ever materialises the full int32 [V, K]. The bf16
mirror is the worker's per-sweep CACHE of that table: the cast
all-gathers it over the model axis once a sweep, every chip holds it
whole (2*V*K bytes), and the sweep's word-row gather is a plain local
in-bounds read — no mask, no collective inside the superstep. Eval reads
the sharded int32 master through the partial-gather + psum form (exact —
each row lives in one shard).

Counts live in:
- ``SparseMatrixTable [V, K] int32`` — word-topic counts (row-sharded
  over the mesh model axis like the reference's server shards; ``tiled``
  stores it tile-aligned),
- ``ArrayTable [K] int32`` — topic summary,
- a worker-local doc-topic array (dense ``[D, K]`` under ``gibbs``, int16
  blocked ``[NB, MAXD, C, 128]`` under ``tiled`` — the reference keeps
  doc-topic counts worker-local too),
- ``z`` — per-token assignments, device-resident (host-resident when
  streamed).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from multiverso_tpu import client, core, telemetry
from multiverso_tpu.data.corpus import backend as data_backend
from multiverso_tpu.ops import interpret_mode
from multiverso_tpu.tables import (ArrayTable, SparseMatrixTable,
                                   make_superstep)
from multiverso_tpu.utils import log


@dataclasses.dataclass
class LDAConfig:
    """The reference app's flag set (lightlda argv)."""
    num_topics: int = 100
    alpha: Optional[float] = None   # doc-topic prior; default 50/K
    beta: float = 0.01              # word-topic prior
    batch_tokens: int = 4096        # tokens per scan step
    steps_per_call: int = 16        # scan length
    num_iterations: int = 10        # full Gibbs sweeps
    eval_every: int = 1             # likelihood eval cadence (sweeps)
    checkpoint_prefix: str = ""     # periodic mid-train checkpoints
    checkpoint_interval: int = 0    # store every N sweeps (0 = off;
    # SURVEY §6.4's flag-driven periodic dump trigger)
    sampler: str = "gibbs"          # "gibbs" (exact O(K), plain XLA) |
    #                               "tiled" (the doc-blocked stale pallas
    #                               sampler; K%128==0, docs <= block_tokens)
    # two keywords kept for callers that predate the two-sampler config
    # (perf/drivers/lda.py among them): "tiled" implies both and reads
    # neither; under "gibbs" a true value is refused
    stale_words: bool = False
    doc_blocked: bool = False
    block_tokens: int = 512         # tiled: tokens per kernel block
    block_docs: int = 16            # tiled: max docs per block
    stream_blocks: bool = False     # tiled only: OUT-OF-CORE mode —
    # the packed token stream, z assignments, and doc counts stay
    # HOST-resident (the reference streams doc blocks from disk; SURVEY
    # §3.6 DataBlock role). Each superstep call stages one [S, B] slice
    # of (words, doc-rows, z) to device through a double-buffered
    # prefetch (utils.async_buffer), the blocked doc counts are REBUILT
    # on device from z (they are a pure function of it — cheaper than
    # round-tripping 64B/token of counts), and z comes back per call.
    # The word master updates incrementally from (z_in, z_out) instead
    # of a sweep-end full-stream rebuild (integer-identical). Device HBM
    # use is INDEPENDENT of corpus size: word table + mirror + summary
    # + two in-flight call buffers.
    local_corpus: bool = False      # stream_blocks only: PER-PROCESS
    # corpus shards — each process passes ONLY its own (token_words,
    # token_docs) slice (global doc ids, disjoint doc sets) and packs
    # its docs into exactly the block slots its devices own; host RAM
    # per process scales with the LOCAL shard, the reference's
    # workers-each-read-their-own-DataBlocks model. Geometry (calls per
    # sweep, global doc/token counts) is agreed collectively at init.
    # z init hashes (seed, GLOBAL block slot, position), so a slot's
    # draw doesn't depend on which process owns it — but a doc's slot
    # comes from greedy packing of the LOCAL shard, so changing the
    # doc-to-process split (or process count) still changes
    # trajectories; only a fixed layout is deterministic.
    precision: str = "float32"      # gibbs posterior/CDF dtype; bfloat16
    # is measured equal-speed at large batches (the op mix is not
    # bandwidth-bound there) and drops topics w/ conditional mass below
    # ~0.2% under bf16 CDF resolution — float32 is the safe default
    seed: int = 0

    def resolved_alpha(self) -> float:
        return self.alpha if self.alpha is not None \
            else 50.0 / self.num_topics


def load_docs(path: str) -> Tuple[np.ndarray, np.ndarray, int]:
    """Read 'word:count' bag-of-words docs into a flat token stream.

    Returns (token_words [T], token_docs [T], vocab_size). The reference's
    DataBlock/Document layout flattened: counts expanded to one entry per
    token occurrence (Gibbs assigns a topic per occurrence).
    """
    offsets, word_ids, word_counts = data_backend().lda_read_docs(path)
    doc_of_entry = np.repeat(
        np.arange(len(offsets) - 1, dtype=np.int32),
        np.diff(offsets).astype(np.int64))
    token_words = np.repeat(word_ids.astype(np.int32), word_counts)
    token_docs = np.repeat(doc_of_entry, word_counts)
    vocab = int(word_ids.max()) + 1 if len(word_ids) else 1
    return token_words, token_docs, vocab


def _hash_z(seed: int, gblocks: np.ndarray, tb: int, K: int) -> np.ndarray:
    """Process-independent z init for local_corpus mode: splitmix64 of
    (seed, global block, position) mod K — any process computes the same
    draw for a given slot without materialising the global stream."""
    x = (gblocks.astype(np.uint64)[:, None] * np.uint64(tb)
         + np.arange(tb, dtype=np.uint64)[None, :]
         + (np.uint64(seed & 0xFFFFFFFF) << np.uint64(32)))
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x = x ^ (x >> np.uint64(31))
    return (x % np.uint64(K)).astype(np.int32)


def _predictive_ll(A, W, S, m, alpha, beta, K, vbeta):
    """Per-token predictive log-likelihood under point estimates:
    log sum_k theta_dk * phi_wk (the reference's `Eval` math), shared by
    every sampler's eval path. A/W are the gathered 2-D f32 count rows,
    S the [K] summary, m the f32 token mask."""
    theta = (A + alpha) / (A.sum(1, keepdims=True) + K * alpha)
    phi = (W + beta) / (S + vbeta)
    ll = jnp.log(jnp.maximum((theta * phi).sum(1), 1e-30))
    return (ll * m).sum()


def _require_mirror_fits(whole: int, sliced: int, stats) -> None:
    """Refuse a replicated bf16 mirror that cannot fit beside what the
    chip already holds. ``stats`` is the device's ``memory_stats()``
    (None where the backend reports none: nothing to check against)."""
    if not stats or "bytes_limit" not in stats:
        return
    free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
    if whole > free:
        raise ValueError(
            f"the stale sweep gathers from a whole bf16 word-topic mirror "
            f"on every chip: {whole} bytes (its vocab slice alone: "
            f"{sliced} bytes), and {free} bytes of the chip are free; a "
            f"model this large needs an owned-row exchange (PERF.md §7)")


def _take_word_rows(table3, w):
    """``table3[w]`` for word ids that are in bounds by construction:
    every id is a word < V (checked in ``LightLDA.__init__``) or the
    packers' scratch word, the last storage row. So no fill mask:
    ``jnp.take``'s default mode costs a select over the whole
    [B, C, 128] result."""
    return jnp.take(table3, w, axis=0, mode="clip")


def _flat_z(z):
    """The resident z ``[steps, blocks a step, TB]`` as the flat packed
    stream, for a scatter's index math — BEHIND a barrier: fused into
    that math the 3-D reshape takes XLA:TPU minutes to compile and comes
    out as a real ``reshape`` op (the rebuild at the benchmark's size:
    78.8 s against 13.8, compiled for a described v5e)."""
    return lax.optimization_barrier(z.reshape(-1))


def _to_host(x: jax.Array) -> np.ndarray:
    """A device array whole on this host. A process reads the shards of
    an array it can address; one split over several processes' chips
    (the resident z and doc counts on a multi-host mesh) is replicated
    on the devices first, as ``Table.get_jax`` does — COLLECTIVE there."""
    if not x.is_fully_addressable:
        x = jax.jit(lambda a: a, out_shardings=NamedSharding(
            x.sharding.mesh, P()))(x)
    return np.asarray(x)


class LightLDA:
    """The app: count tables + the fused Gibbs-sweep superstep."""

    def __init__(self, token_words: np.ndarray, token_docs: np.ndarray,
                 vocab_size: int, config: LDAConfig, *, mesh=None,
                 name: str = "lightlda") -> None:
        self.config = config
        self.mesh = mesh if mesh is not None else core.mesh()
        c = config
        self.V = vocab_size
        self.K = c.num_topics
        self.num_docs = int(token_docs.max()) + 1 if len(token_docs) else 1
        self.num_tokens = len(token_words)
        # the owner of the in-range invariant every word-row gather and
        # scatter leans on (out-of-range indices raise nothing on the
        # device: reads clamp, updates drop)
        if len(token_words) and not (0 <= int(token_words.min())
                                     and int(token_words.max()) < self.V):
            raise ValueError(
                f"token_words must lie in [0, vocab_size={self.V}); got "
                f"ids from {int(token_words.min())} to "
                f"{int(token_words.max())}")
        if c.sampler not in ("gibbs", "tiled"):
            raise ValueError(f"sampler is one of gibbs | tiled, got "
                             f"{c.sampler!r}")
        if c.precision not in ("float32", "bfloat16"):
            raise ValueError(f"precision must be 'float32' or 'bfloat16', "
                             f"got {c.precision!r}")
        self.alpha = c.resolved_alpha()
        self.beta = c.beta
        # fault tolerance (ft.checkpoint.wire_app): run manager +
        # sweep cursor. _sweep_done counts completed sweeps (what a
        # checkpoint records); _resume_sweeps is the restored offset,
        # consumed by the FIRST train() after a resume — repeated
        # in-session train(n) calls keep their "n more sweeps" meaning
        self.run_ckpt = None
        self._sweep_done = 0
        self._resume_sweeps = 0

        # THE sampler decision: the doc-blocked stale sampler, or gibbs
        self._docblock = c.sampler == "tiled"
        if self._docblock and self.K % 128:
            raise ValueError(f"sampler='tiled' needs num_topics % 128 "
                             f"== 0, got {self.K}")
        if (c.stale_words or c.doc_blocked) and not self._docblock:
            raise ValueError(
                f"stale_words/doc_blocked are what sampler='tiled' is; "
                f"got sampler={c.sampler!r}")
        if c.stream_blocks and not self._docblock:
            raise ValueError(f"stream_blocks requires sampler='tiled', "
                             f"got sampler={c.sampler!r}")
        if c.local_corpus and not c.stream_blocks:
            raise ValueError("local_corpus requires stream_blocks=True")
        if c.local_corpus and jax.process_count() > 1:
            # per-process corpus shards: agree on the global doc-id
            # space and token count (loglik normalization, count
            # invariants) before any geometry is derived (int64-safe:
            # process_allgather truncates int64 to int32 without x64)
            from multiverso_tpu.parallel.multihost import allgather_i64
            g = allgather_i64([self.num_docs, self.num_tokens])
            self.num_docs = int(g[:, 0].max())
            self.num_tokens = int(g[:, 1].sum())
        # stream_blocks works multi-host: staging assembles each call's
        # operand from per-device slices (every process device_puts only
        # its addressable lanes) and z readback walks addressable shards,
        # so no process ever materialises another host's device data.
        # By default each process keeps the full HOST-side packed corpus
        # (deterministic packing keeps layouts agreed); with
        # local_corpus=True each process passes and packs ONLY its own
        # doc shard, so host RAM also scales 1/P — the reference's
        # workers-each-read-their-own-DataBlocks model.
        # the pallas kernel needs the Mosaic TPU backend; on a CPU mesh
        # (tests) it runs in interpreter mode
        self._interpret = self._docblock and interpret_mode(self.mesh)

        # tables (the reference's server-side state); tiled storage puts
        # one word's topic row in exactly one (8,128) int32 tile
        self.word_topic = SparseMatrixTable(
            self.V, self.K, "int32", updater="default", mesh=self.mesh,
            name=f"{name}_word_topic", tiled=self._docblock)
        self.summary = ArrayTable(self.K, "int32", updater="default",
                                  mesh=self.mesh, name=f"{name}_summary")
        self._scratch_word = self.word_topic.padded_shape[0] - 1
        # MVTPU_STALENESS: serve logging/eval reads of the word-topic
        # model (word_topics/top_words) from a bounded-staleness cached
        # view instead of a blocking whole-table fetch per call;
        # dump_model/store stay exact
        self._wt_view = client.maybe_cached_view(self.word_topic)

        if self._docblock:
            self._setup_docblock(token_words, token_docs)
            if c.stream_blocks:
                self._build_docblock_stream_superstep()
                self._init_streamed_counts()
            else:
                self._build_docblock_superstep()
        else:
            self._setup_gibbs_stream(token_words, token_docs)
            self._init_counts()
            self._build_superstep()
        self._key = core.prng_key(c.seed, mesh=self.mesh)
        self._count_calls_from(0)
        self.ll_history: list = []
        self._last_store = ()

    # -- gibbs stream / state ----------------------------------------------

    def _setup_gibbs_stream(self, token_words, token_docs) -> None:
        """The ``gibbs`` sampler's staging: the shuffled token stream
        padded to whole superstep calls and placed once, and random
        initial assignments."""
        c = self.config
        B, S = c.batch_tokens, c.steps_per_call
        d_axis = self.mesh.shape[core.DATA_AXIS]
        if B % d_axis:
            raise ValueError(f"batch_tokens {B} not divisible by "
                             f"data-axis size {d_axis}")
        call_tokens = B * S
        T_pad = -(-max(self.num_tokens, 1) // call_tokens) * call_tokens
        self._mask = np.zeros(T_pad, bool)
        self._mask[: self.num_tokens] = True
        tw = np.full(T_pad, self._scratch_word, np.int32)
        tw[: self.num_tokens] = token_words
        td = np.full(T_pad, self.num_docs, np.int32)   # +1 scratch doc
        td[: self.num_tokens] = token_docs
        # shuffle the stream: doc-contiguous order would put a whole doc
        # in one batch, zeroing its doc-topic row under the batch-stale
        # decrement and badly slowing mixing; a fixed permutation spreads
        # each doc/word across the sweep (padded lanes shuffle in too —
        # harmless, they are masked)
        perm = np.random.default_rng(c.seed ^ 0x5EED).permutation(T_pad)
        self._tw, self._td = tw[perm], td[perm]
        self._mask = self._mask[perm]
        self.calls_per_sweep = T_pad // call_tokens
        # pre-place the static token stream on device once (the stream
        # never changes; re-uploading it every sweep would put ~4 host
        # transfers of the whole corpus in the hot loop)
        spec = P(None, core.DATA_AXIS)
        self._calls = []
        for call in range(self.calls_per_sweep):
            lo = call * call_tokens
            sl = slice(lo, lo + call_tokens)
            self._calls.append(tuple(
                self._place(a[sl].reshape(S, B), spec) for a in
                (self._tw, self._td, np.arange(T_pad, dtype=np.int32),
                 self._mask.astype(np.int32))))
        # random initial assignments (the count build is _init_counts)
        rng = np.random.default_rng(c.seed)
        z0 = rng.integers(0, self.K, T_pad).astype(np.int32)
        self._z = self._place(z0, P())

    # -- doc-blocked stream / state ---------------------------------------

    def _setup_docblock(self, token_words, token_docs) -> None:
        """Pack the doc-sorted stream into whole-doc kernel blocks and
        build the blocked int16 doc-topic counts."""
        c = self.config
        TB, MAXD = c.block_tokens, c.block_docs
        B, S = c.batch_tokens, c.steps_per_call
        if TB % 8 or B % TB:
            raise ValueError(f"block_tokens {TB} must be a multiple of 8 "
                             f"dividing batch_tokens {B}")
        # the host's part: sort, greedy block assignment, the padded
        # stream and its placement on the device
        with telemetry.span("lda.setup.pack"):
            order = np.argsort(token_docs, kind="stable")
            tw, td = token_words[order], token_docs[order]
            # assignments() undoes the sort; a doc-contiguous corpus
            # (the usual) sorts to itself and keeps nothing
            self._doc_order = None if np.array_equal(td, token_docs) \
                else order
            doc_ids, doc_starts = np.unique(td, return_index=True) \
                if len(td) else (np.zeros(0, np.int64), np.zeros(0, np.int64))
            doc_ends = np.append(doc_starts[1:], len(td)) if len(td) \
                else doc_starts
            lens = doc_ends - doc_starts
            if len(lens) and lens.max() >= 32767:
                raise ValueError(
                    f"the doc-blocked sampler stores doc counts int16; a "
                    f"document has {lens.max()} tokens (>= 32767)")
            if len(lens) and lens.max() > TB:
                raise ValueError(f"a document has {lens.max()} tokens > "
                                 f"block_tokens {TB}")
            # greedy whole-doc block assignment (sequential by nature; a
            # plain scalar loop over doc LENGTHS — the token-level copy
            # below is fully vectorized so web-scale corpora pack in seconds)
            n_real = len(doc_ids)
            blk = np.empty(n_real, np.int64)
            row = np.empty(n_real, np.int64)
            off = np.empty(n_real, np.int64)
            b = 0
            cur_r = cur_tok = 0
            for di, ln in enumerate(lens.tolist()):
                if cur_tok + ln > TB or cur_r >= MAXD:
                    b += 1
                    cur_r = cur_tok = 0
                blk[di], row[di], off[di] = b, cur_r, cur_tok
                cur_r += 1
                cur_tok += ln
            n_blocks = (b + 1) if n_real else 1
            nbs = B // TB                       # blocks per scan step
            self._split_blocks(nbs)
            per_call = S * nbs
            self._per_call = per_call
            self._tb, self._maxd = TB, MAXD
            local = c.stream_blocks and c.local_corpus
            if local:
                # per-process corpus shard: this process packs its docs into
                # ONLY the block slots its devices own (the reference's
                # workers-each-own-their-DataBlocks model); the other
                # processes fill the rest of the global block space
                self._own_offs = self._owned_call_offsets()
                self._own_per_call = cap = len(self._own_offs)
                n_calls = -(-n_blocks // cap)
                if jax.process_count() > 1:
                    from multiverso_tpu.parallel.multihost import (
                        allgather_i64, validate_single_owner)
                    mask = np.zeros(per_call, np.int32)
                    mask[self._own_offs] = 1
                    validate_single_owner(mask, "local_corpus")
                    n_calls = int(allgather_i64([n_calls]).max())
            else:
                cap = per_call
                n_calls = -(-n_blocks // cap)
            nb_alloc = n_calls * cap            # blocks on THIS process
            nb_pad = n_calls * per_call         # GLOBAL padded block count
            self.calls_per_sweep = n_calls
            self._nb_pad = nb_pad

            tw_p = np.full((nb_alloc, TB), self._scratch_word, np.int32)
            drel_p = np.full((nb_alloc, TB), MAXD - 1, np.int32)
            mask_p = np.zeros((nb_alloc, TB), np.int32)
            # -1 = document with zero tokens (never packed into any block);
            # doc_topics()/store() must yield zero rows for those, not some
            # other document's counts
            self._blk_of_doc = np.full(self.num_docs, -1, np.int64)
            self._row_of_doc = np.full(self.num_docs, -1, np.int64)
            if n_real:
                # each doc's tokens land at (blk, off + position-within-doc)
                tok_within = np.arange(len(td), dtype=np.int64) \
                    - np.repeat(doc_starts, lens)
                flat = np.repeat(blk * TB + off, lens) + tok_within
                tw_p.reshape(-1)[flat] = tw
                drel_p.reshape(-1)[flat] = np.repeat(row, lens)
                mask_p.reshape(-1)[flat] = 1
                self._blk_of_doc[doc_ids] = blk
                self._row_of_doc[doc_ids] = row
            fill = mask_p.sum() / max(nb_alloc * TB, 1)
            self.packing_fill = float(fill)
            log.info("lda tiled: %d blocks (%d/call, %.0f%% fill)",
                     nb_alloc, cap, 100 * fill)

            # init z — shared by both residency modes so the streamed and
            # in-memory runs are bit-identical for the same seed. local mode
            # instead hashes (seed, GLOBAL block, position) so the draw for
            # a given slot is independent of the process layout
            if local:
                z0 = _hash_z(c.seed, self._global_of_local(
                    np.arange(nb_alloc, dtype=np.int64)), TB, self.K)
            else:
                rng = np.random.default_rng(c.seed)
                z0 = rng.integers(0, self.K, (nb_pad, TB)).astype(np.int32)

            if c.stream_blocks:
                # OUT-OF-CORE: stream/z/doc-counts stay host-resident (the
                # reference's disk-streamed DataBlocks); mask is derived on
                # device (tw == scratch_word <=> padded lane)
                self._tw_host = tw_p
                self._drel_host = drel_p
                self._z_host = z0
                self._z_synced = True    # init z is globally consistent
                self._ndk = None
                # inverse packing map for doc_topics(): (block, row) -> doc
                self._doc_of_row = np.full((nb_alloc, MAXD), -1, np.int64)
                valid = self._blk_of_doc >= 0
                self._doc_of_row[self._blk_of_doc[valid],
                                 self._row_of_doc[valid]] = \
                    np.nonzero(valid)[0]
                return

            # per-call staging: [S, B] lanes, a step's blocks split over
            # every chip of the mesh, + the call's step numbers
            axes = self._block_axes
            spec = P(None, axes)
            self._calls = []
            self._loglik_rows = []   # eval-only gather rows into the
            #                          call's own window of doc counts (not
            #                          a fused operand: no sweep needs them)
            rows_call = (np.arange(per_call)[:, None] * MAXD) \
                .astype(np.int32)
            for call in range(n_calls):
                lo = call * per_call
                sl = slice(lo, lo + per_call)
                shp = (S, B)
                self._calls.append((
                    self._place(tw_p[sl].reshape(shp), spec),
                    self._place(drel_p[sl].reshape(shp), spec),
                    self._place(mask_p[sl].reshape(shp).astype(np.int32),
                                spec),
                    self._place(np.arange(call * S, (call + 1) * S,
                                          dtype=np.int32), P())))
                self._loglik_rows.append(self._place(
                    (rows_call + drel_p[sl]).reshape(shp), spec))

            # full flat stream for the per-sweep word-count rebuild
            self._tw_flat = self._place(tw_p.reshape(-1), P())
            self._mask_flat = self._place(mask_p.reshape(-1), P())

            # the sampler's state, block-major [steps, blocks a step, ..]
            # and split like the lanes: a step's window is one index on
            # the unsharded dimension, and a chip reads and writes only
            # the blocks it samples. Row-major order is the packed block
            # order, so a reshape reads what [nb_pad, ..] read
            n_steps = n_calls * S
            self._z_sharding = NamedSharding(self.mesh, P(None, axes, None))
            self._ndk_sharding = NamedSharding(
                self.mesh, P(None, axes, None, None, None))

            def blocks(a):
                return jax.device_put(a.reshape(n_steps, nbs, TB),
                                      self._z_sharding)

            self._z = blocks(z0)
            drel_dev = blocks(drel_p)
        tiles = self.K // 128

        def block_counts(z, drel, m):
            # one chip's blocks [steps, its blocks a step, TB], a step at
            # a time: a block's counts are its tokens' one-hot doc rows
            # times their one-hot topics — what the kernel does in VMEM.
            # Exact (sums of at most TB ones in f32), no scatter, and
            # nothing leaves the chip: a block owns its MAXD rows
            def step(_, x):
                zs, ds, ms = x
                topic = jax.nn.one_hot(zs, self.K, dtype=jnp.bfloat16) \
                    * ms[..., None].astype(jnp.bfloat16)
                row = jax.nn.one_hot(ds, MAXD, dtype=jnp.bfloat16)
                counts = jnp.einsum("btd,btk->bdk", row, topic,
                                    preferred_element_type=jnp.float32)
                return None, counts.astype(jnp.int16).reshape(
                    zs.shape[0], MAXD, tiles, 128)

            return lax.scan(step, None, (z, drel, m))[1]

        from jax import shard_map
        zspec = self._z_sharding.spec
        block_counts = shard_map(
            block_counts, mesh=self.mesh, in_specs=(zspec, zspec, zspec),
            out_specs=self._ndk_sharding.spec, check_vma=False)

        @partial(jax.jit, out_shardings=(None, self._ndk_sharding, None))
        def build(z, tw_flat, m_flat, drel):
            zf = _flat_z(z)
            # the flat mask is whole on every chip: its blocked view is
            # a local slice
            msk = lax.with_sharding_constraint(m_flat.reshape(z.shape),
                                               self._z_sharding)
            nwk = jnp.zeros(self.word_topic.storage_shape, jnp.int32)
            nwk = nwk.at[tw_flat, zf // 128, zf % 128].add(m_flat)
            nk = jnp.zeros(self.summary.padded_shape, jnp.int32)
            nk = nk.at[zf].add(m_flat)
            return nwk, block_counts(z, drel, msk), nk

        # the device's part, fenced so the span holds it (build's own
        # compile included: a bare jit, not in profile.compile.seconds)
        with telemetry.span("lda.setup.counts"):
            nwk, ndk, nk = jax.block_until_ready(build(
                self._z, self._tw_flat, self._mask_flat, drel_dev))
        self.word_topic.put_raw(nwk)
        self._ndk = ndk
        self.summary.put_raw(nk)

    def _split_blocks(self, nbs: int) -> None:
        """Decide which mesh axes split a step's ``nbs`` kernel blocks
        (``_block_axes``), and set ``lda.sample.chips`` to the number of
        chips that then sample distinct blocks. Resident: every chip of
        the mesh, data x model — each chip holds the whole bf16 mirror,
        so nothing needs two chips to see the same tokens, and the model
        axis keeps only the int32 master's slices and their rebuild.
        Streamed (``stream_blocks``): the data axis alone — its host
        staging, ``_owned_call_offsets`` and the z drain are keyed to the
        data axis, and the chips of a model group still sample the same
        blocks there (no benchmark cell runs it: ROADMAP R10)."""
        self._block_axes = (core.DATA_AXIS,) if self.config.stream_blocks \
            else (core.DATA_AXIS, core.MODEL_AXIS)
        chips = int(np.prod([self.mesh.shape[a]
                             for a in self._block_axes]))
        if nbs % chips:
            raise ValueError(
                f"tiled: blocks per step {nbs} not divisible by the "
                f"{chips} chips that split them (mesh axes "
                f"{self._block_axes})")
        telemetry.gauge("lda.sample.chips").set(chips)

    def _build_word_gather(self):
        """``take(nwk3, w)`` from the int32 MASTER, row-sharded over the
        model axis — the eval gather (the sweep reads the replicated
        mirror instead, see :meth:`_build_stale_helpers`): each chip
        gathers the rows its vocab slice owns and the partials psum over
        ICI — exact (a row lives in exactly one shard), no chip ever
        materialises the full int32 [V, K]. This is the TPU shape of the
        reference's Meta vocab-slicing: a worker fetches word rows per
        slice instead of holding the whole model. Works for any
        [*, C, 128] storage dtype. mp == 1 degenerates to the plain
        in-bounds gather."""
        mp = self.mesh.shape[core.MODEL_AXIS]
        if mp == 1:
            return _take_word_rows
        from jax import shard_map
        d, m = core.DATA_AXIS, core.MODEL_AXIS
        vshard = self.word_topic.storage_shape[0] // mp

        def local(ws_local, w):
            lo = lax.axis_index(m) * vshard
            idx = w - lo
            ok = (idx >= 0) & (idx < vshard)
            rows = jnp.take(ws_local, jnp.clip(idx, 0, vshard - 1),
                            axis=0)
            rows = jnp.where(ok[:, None, None], rows,
                             jnp.zeros((), rows.dtype))
            return lax.psum(rows, m)

        return shard_map(local, mesh=self.mesh,
                         in_specs=(P(m, None, None), P(d)),
                         out_specs=P(d, None, None), check_vma=False)

    def _wrap_docblock_dp(self, fn):
        """Multi-chip dispatch for the pallas sampler: a Mosaic custom
        call cannot be auto-partitioned by XLA, so on any multi-device
        mesh each chip runs the kernel on its own kernel blocks via
        ``shard_map``. A step's blocks are split over EVERY chip of the
        mesh (``_block_axes``: data x model) — each chip exclusively
        owns its blocks' doc counts and z, the block layout IS the
        partition — and the topic-summary delta is psum'd over both
        axes. Only ``sinv`` is replicated. Which chip samples a block
        does not change what it samples: the uniforms are drawn for the
        whole step and sliced."""
        if self.mesh.devices.size == 1:
            return fn
        from jax import shard_map
        axes = self._block_axes
        Pb = P(axes)

        def local(ndk_c, W3, sinv, zi, drel, msk, u1, u2):
            ndk_c, znew, nkd = fn(ndk_c, W3, sinv, zi, drel, msk, u1, u2)
            return ndk_c, znew, lax.psum(nkd, axes)

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(axes, None, None, None), P(axes, None, None),
                      P(None, None), Pb, Pb, Pb, Pb, Pb),
            out_specs=(P(axes, None, None, None), Pb, P(None, None)),
            check_vma=False)

    def _build_vocab_slice_scatter(self):
        """shard_map'd count scatter for a model-sharded word table:
        each chip scatters its DATA shard's in-range tokens into its
        vocab slice, psum over the data axis. Shared by the per-sweep
        rebuild and the streamed master accumulator (one copy of the
        slice math). Returns f(z_flat, tw, msk) -> [V/mp, C, 128]."""
        from jax import shard_map
        d, maxis = core.DATA_AXIS, core.MODEL_AXIS
        mp = self.mesh.shape[maxis]
        vshard = self.word_topic.storage_shape[0] // mp
        tail = self.word_topic.storage_shape[1:]

        def local(zf, tw, m):
            lo = lax.axis_index(maxis) * vshard
            idx = tw - lo
            ok = (idx >= 0) & (idx < vshard)
            add = jnp.where(ok, m, 0)
            nwk3 = jnp.zeros((vshard,) + tail, jnp.int32)
            nwk3 = nwk3.at[jnp.clip(idx, 0, vshard - 1),
                           zf // 128, zf % 128].add(add)
            return lax.psum(nwk3, d)

        return shard_map(local, mesh=self.mesh,
                         in_specs=(P(d), P(d), P(d)),
                         out_specs=P(maxis, None, None),
                         check_vma=False)

    def _build_stale_helpers(self) -> None:
        """Per-sweep word-count helpers of the doc-blocked sampler,
        resident and streamed: the bf16 gather mirror and the int32
        master rebuild from the blocked z (flattened).

        The int32 master stays sharded over the model axis — the one job
        that axis has: the rebuild scatters each chip's DATA shard of
        the stream into its own vocab slice, psum'd over the data axis —
        no chip ever holds the int32 [V, K]. (The resident z lies split
        over data x model by block; the rebuild's ``in_specs`` ask for
        the data shard, so the compiler moves z once a sweep.) The
        mirror is the worker's per-sweep cache of it (the stale-words
        model): ``to_stale`` casts the chip's slice and
        all-gathers it over the model axis, ONCE a sweep, so every chip
        holds the whole bf16 [V, K] and the gather inside the superstep
        (:func:`_take_word_rows`) is a plain local read — no ownership
        mask, no collective. A
        chip's word-table bytes are 4*V*K/mp + 2*V*K; a model whose
        whole mirror cannot fit is refused here (it wants an owned-row
        exchange, PERF.md §7, not this path)."""
        mp = self.mesh.shape[core.MODEL_AXIS]
        self._account_mirror()

        @partial(jax.jit, out_shardings=NamedSharding(self.mesh, P()))
        def to_stale(nwk3):
            return nwk3.astype(jnp.bfloat16)

        if mp == 1:
            @jax.jit
            def rebuild(z, tw, m):
                zf = _flat_z(z)
                nwk3 = jnp.zeros(self.word_topic.storage_shape, jnp.int32)
                return nwk3.at[tw, zf // 128, zf % 128].add(m)
        else:
            sharded = self._build_vocab_slice_scatter()

            @jax.jit
            def rebuild(z, tw, m):
                return sharded(_flat_z(z), tw, m)

        self._to_stale = to_stale
        self._rebuild = rebuild

    def _account_mirror(self) -> None:
        """Set ``lda.mirror.bytes_per_chip`` and refuse a mirror that
        cannot fit beside what the chip holds by now (tables, and the
        corpus where it is resident)."""
        whole = 2 * int(np.prod(self.word_topic.storage_shape))
        _require_mirror_fits(
            whole, whole // self.mesh.shape[core.MODEL_AXIS],
            self.mesh.local_devices[0].memory_stats())
        telemetry.gauge("lda.mirror.bytes_per_chip").set(whole)

    def _eval_chunk(self, n: int) -> int:
        """Largest chunk of ~64k tokens that divides ``n`` and keeps the
        data-axis sharding valid: eval gathers materialise [chunk, K]
        f32 intermediates, which must stay bounded no matter how large a
        call is (an unchunked 8M-token call at K=1024 wants 34 GB)."""
        dp = self.mesh.shape[core.DATA_AXIS]
        c = n
        while c > (1 << 16) and c % 2 == 0 and (c // 2) % dp == 0:
            c //= 2
        return c

    def _chunked_ll(self, gather_w):
        """Chunked predictive-likelihood core shared by the in-memory
        and streamed evals (ONE copy of the chunk/gather math): scans
        [chunk, K] gathers so eval intermediates stay bounded no matter
        the call size (see :meth:`_eval_chunk`)."""
        alpha, beta = self.alpha, self.beta
        K = self.K
        vbeta = self.V * beta
        chunk = self._eval_chunk

        # each scanned chunk's lanes shard over the data axis (what the
        # sharded word gather's shard_map takes). Stated on the
        # [chunks, c] operands BEFORE the scan: left to infer it inside
        # the loop, XLA:TPU fails to compile the dp x mp eval
        # ("Reshape should have supported layout before reaching the
        # emitter", jax 0.9.0 / libtpu 0.0.34).
        lanes = NamedSharding(self.mesh, P(None, core.DATA_AXIS))

        def run(nwk3, ndk_flat, Ssum, ws, rows, m):
            c = chunk(ws.shape[0])

            def step(tot, xs):
                wsc, rc, mc = xs
                A = jnp.take(ndk_flat, rc, axis=0).reshape(c, K) \
                    .astype(jnp.float32)
                W = gather_w(nwk3, wsc).reshape(c, K) \
                    .astype(jnp.float32)
                return tot + _predictive_ll(A, W, Ssum, mc, alpha,
                                            beta, K, vbeta), None

            tot, _ = lax.scan(
                step, jnp.zeros((), jnp.float32),
                tuple(lax.with_sharding_constraint(x.reshape(-1, c), lanes)
                      for x in (ws, rows, m)))
            return tot

        return run

    def _build_blocked_loglik(self) -> None:
        """Eval over the blocked doc counts: ``rows`` index the flattened
        [*, C, 128] doc-count rows of the call's own steps. Word rows come
        through the sharded gather, so eval never materialises the full
        [V, K] on one chip under model parallelism."""
        K = self.K
        tiles = K // 128
        run = self._chunked_ll(self._build_word_gather())

        @jax.jit
        def loglik(nwk3, ndk, nk, ws, rows, mask, steps):
            # the call's own steps of the doc counts: whatever the
            # compiler moves between chips to serve the gather is one
            # call's window, never the corpus's
            win = lax.dynamic_slice_in_dim(ndk, steps[0], ws.shape[0], 0)
            return run(nwk3, win.reshape(-1, tiles, 128),
                       nk[:K].astype(jnp.float32), ws.reshape(-1),
                       rows.reshape(-1),
                       mask.reshape(-1).astype(jnp.float32))

        self._loglik = loglik

    def _build_docblock_kernel(self) -> None:
        """The IN-MEMORY doc-blocked superstep's kernel dispatch + scan
        body (the streamed mode builds its own scan body around the
        count-building kernel variant — same draw math, verified
        bit-identical by tests/test_lightlda.py)."""
        c = self.config
        alpha, beta = self.alpha, self.beta
        vbeta = self.V * beta
        K = self.K
        B = c.batch_tokens
        TB = self._tb
        nbs = B // TB
        tiles = K // 128
        interpret = self._interpret
        from multiverso_tpu.ops import gibbs_sample_docblock
        sampler_call = self._wrap_docblock_dp(
            lambda ndk_c, W3, sinv, zi, drel, msk, u1, u2:
            gibbs_sample_docblock(ndk_c, W3, sinv, zi, drel, msk, u1,
                                  u2, alpha=alpha, beta=beta, tb=TB,
                                  interpret=interpret))
        self._build_stale_helpers()

        # each phase under a program scope, so the compiled ops carry its
        # name (profiling.op_scopes): lda.carry is what the scan hands on
        # (this step's window of z, in and out), lda.doc_counts the
        # doc-topic window and the topic totals
        scope = telemetry.scope

        # z and ndk are [steps, blocks a step, ..], split over the chips
        # on the second dimension: step number ``t`` picks the window
        @scope("lda.carry")
        def z_window(z, t):
            return lax.dynamic_index_in_dim(z, t, 0, False).reshape(B)

        @scope("lda.carry")
        def z_update(z, znew, t):
            return lax.dynamic_update_index_in_dim(
                z, znew.reshape(nbs, TB), t, 0)

        @scope("lda.doc_counts")
        def counts_window(ndk, t):
            return lax.dynamic_index_in_dim(ndk, t, 0, False)

        @scope("lda.doc_counts")
        def counts_update(ndk, ndk_c, nk, nkd, t):
            return (lax.dynamic_update_index_in_dim(ndk, ndk_c, t, 0),
                    nk.at[:K].add(nkd.reshape(-1)))

        @scope("lda.sample")
        def sample(ndk_c, W3, nk, zi, drel, msk, key):
            sinv = 1.0 / (nk[:K].astype(jnp.float32).reshape(tiles, 128)
                          + vbeta)
            k1, k2 = jax.random.split(key)
            u1 = jax.random.uniform(k1, (B,))
            u2 = jax.random.uniform(k2, (B,))
            return sampler_call(ndk_c, W3, sinv, zi, drel.reshape(B),
                                msk.reshape(B), u1, u2)

        gather_words = scope("lda.gather_words")(_take_word_rows)

        def scan_body(wstale, carry, inp):
            nk, ndk, z = carry
            w, drel, msk, t, key = inp
            ndk_c = counts_window(ndk, t)
            zi = z_window(z, t)
            W3 = gather_words(wstale, w.reshape(B))
            ndk_c, znew, nkd = sample(ndk_c, W3, nk, zi, drel, msk, key)
            ndk, nk = counts_update(ndk, ndk_c, nk, nkd, t)
            z = z_update(z, znew, t)
            return (nk, ndk, z), ()

        self._db_scan_body = scan_body

    def _build_docblock_superstep(self) -> None:
        self._build_docblock_kernel()
        scan_body = self._db_scan_body

        def body(params, states, locals_, options, wstale, ws, drels,
                 msks, steps, base_key):
            (nk,) = params
            ndk, z, calls = locals_
            keys = jax.random.split(jax.random.fold_in(base_key, calls),
                                    ws.shape[0])
            (nk, ndk, z), _ = lax.scan(
                lambda cy, inp: scan_body(wstale, cy, inp),
                (nk, ndk, z), (ws, drels, msks, steps, keys))
            return (nk,), states, (ndk, z, calls + 1), None

        self._fused = make_superstep(
            (self.summary,), body, name="lda_docblock",
            local_shardings=(self._ndk_sharding, self._z_sharding,
                             NamedSharding(self.mesh, P())))

        self._build_blocked_loglik()

    # -- out-of-core (streamed) doc-blocked mode ---------------------------

    def _build_master_accumulate(self):
        """(acc, z, w, mask) -> acc with ``counts(z)`` of the call's
        tokens added. ``acc`` is a donated carry: the single-device path
        scatters IN PLACE (no full-table temporary per call — measured
        ~0.2s/sweep of HBM traffic at V=50k, K=1024). Under model
        parallelism each chip scatters its data shard's in-range tokens
        into a vocab-slice delta, psum'd over the data axis (the
        per-sweep-rebuild pattern)."""
        mp = self.mesh.shape[core.MODEL_AXIS]
        if mp == 1:
            def accumulate(acc, z, tw, msk):
                return acc.at[tw, z // 128, z % 128].add(msk)
            return accumulate
        delta = self._build_vocab_slice_scatter()

        def accumulate(acc, z, tw, msk):
            return acc + delta(z, tw, msk)

        return accumulate

    def _wrap_docblock_build_dp(self, fn):
        """shard_map dispatch for the count-building kernel (no blocked
        count array: z is the only sampler state). The streamed path's
        blocks are split over the DATA axis alone, operands replicated
        over the model axis — its host staging and z drain are keyed to
        the data axis (:meth:`_split_blocks`); the resident path splits
        over data x model (:meth:`_wrap_docblock_dp`)."""
        if self.mesh.devices.size == 1:
            return fn
        from jax import shard_map
        d = self._block_axes
        Pb = P(d)

        def local(W3, sinv, zi, drel, msk, u1, u2):
            znew, nkd = fn(W3, sinv, zi, drel, msk, u1, u2)
            return znew, lax.psum(nkd, d)

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(d, None, None), P(None, None), Pb, Pb, Pb, Pb,
                      Pb),
            out_specs=(Pb, P(None, None)), check_vma=False)

    def _build_docblock_stream_superstep(self) -> None:
        c = self.config
        alpha, beta = self.alpha, self.beta
        vbeta = self.V * beta
        K = self.K
        S, B, TB = c.steps_per_call, c.batch_tokens, self._tb
        nbs, MAXD = B // TB, self._maxd
        tiles = K // 128
        scratch = self._scratch_word
        interpret = self._interpret
        from multiverso_tpu.ops import gibbs_sample_docblock_build
        sampler_call = self._wrap_docblock_build_dp(
            lambda W3, sinv, zi, drel, msk, u1, u2:
            gibbs_sample_docblock_build(
                W3, sinv, zi, drel, msk, u1, u2, alpha=alpha, beta=beta,
                tb=TB, maxd=MAXD, interpret=interpret))
        self._build_stale_helpers()
        accumulate = self._build_master_accumulate()
        self._stage_sharding = NamedSharding(
            self.mesh, P(None, None, core.DATA_AXIS))

        def unpack(stacked):
            tw, drel, z_in = stacked[0], stacked[1], stacked[2]
            msk = (tw != scratch).astype(jnp.int32)
            j = jnp.arange(S * B, dtype=jnp.int32)
            rows = (j // TB) * MAXD + drel.reshape(-1)
            return tw, drel, z_in, msk, rows

        def scan_body(wstale, carry, inp):
            nk, z = carry
            w, drel, msk, off, key = inp
            zi = lax.dynamic_slice_in_dim(z, off, nbs).reshape(B)
            W3 = _take_word_rows(wstale, w.reshape(B))
            sinv = 1.0 / (nk[:K].astype(jnp.float32).reshape(tiles, 128)
                          + vbeta)
            k1, k2 = jax.random.split(key)
            u1 = jax.random.uniform(k1, (B,))
            u2 = jax.random.uniform(k2, (B,))
            znew, nkd = sampler_call(W3, sinv, zi, drel.reshape(B),
                                     msk.reshape(B), u1, u2)
            z = lax.dynamic_update_slice_in_dim(
                z, znew.reshape(nbs, TB), off, 0)
            nk = nk.at[:K].add(nkd.reshape(-1))
            return (nk, z), ()

        def body(params, states, locals_, options, wstale, stacked,
                 base_key):
            (nk,) = params
            acc, calls = locals_   # fresh word-count accumulator: over one
            # sweep the per-call +/- master deltas TELESCOPE to
            # counts(z_end) (the subtracted counts(z_start) equal the
            # old master exactly), so one add-only scatter pass per call
            # into a fresh accumulator — swapped in at sweep end —
            # halves the scatter traffic of an incremental +/- update
            tw, drel, z_in, msk, _rows = unpack(stacked)
            z = z_in.reshape(S * nbs, TB)
            offs = jnp.arange(S, dtype=jnp.int32) * nbs
            keys = jax.random.split(jax.random.fold_in(base_key, calls), S)
            (nk, z), _ = lax.scan(
                lambda cy, inp: scan_body(wstale, cy, inp),
                (nk, z), (tw, drel, msk, offs, keys))
            z_out = z.reshape(S, B)
            acc = accumulate(acc, z_out.reshape(-1), tw.reshape(-1),
                             msk.reshape(-1))
            # pin the aux z to the STAGING layout (lanes over the data
            # axis): each process then drains exactly the lanes it will
            # stage next sweep — without the constraint XLA may pick a
            # different aux sharding and a multi-host process would read
            # back lanes it does not own
            z_out = lax.with_sharding_constraint(
                z_out, NamedSharding(self.mesh, P(None, core.DATA_AXIS)))
            return (nk,), states, (acc, calls + 1), z_out

        self._fused_stream = make_superstep(
            (self.summary,), body,
            local_shardings=(self.word_topic.sharding,
                             NamedSharding(self.mesh, P())),
            name="lda_docblock_stream")

        # streamed eval: stage (tw, drel, z), rebuild the call's doc
        # counts from z (XLA scatter — eval is periodic, not the hot
        # loop), gather word rows through the sharded gather
        def build_ndk(zf, rows, m):
            ndk = jnp.zeros((S * nbs * MAXD, tiles, 128), jnp.int16)
            return ndk.at[rows, zf // 128, zf % 128].add(
                m.astype(jnp.int16))

        run = self._chunked_ll(self._build_word_gather())

        @jax.jit
        def loglik_stream(nwk3, nk, stacked):
            tw, _drel, z_in, msk, rows = unpack(stacked)
            ndk = build_ndk(z_in.reshape(-1), rows, msk.reshape(-1))
            return run(nwk3, ndk, nk[:K].astype(jnp.float32),
                       tw.reshape(-1), rows,
                       msk.reshape(-1).astype(jnp.float32))

        self._loglik_stream = loglik_stream

        # per-call count init (the in-memory mode's build(), one staged
        # call at a time so HBM never sees the whole stream)
        @partial(jax.jit, donate_argnums=(0, 1))
        def init_call(master, nk, stacked):
            tw, _drel, z_in, msk, _rows = unpack(stacked)
            zf = z_in.reshape(-1)
            mf = msk.reshape(-1)
            master = accumulate(master, zf, tw.reshape(-1), mf)
            nk = nk.at[zf].add(mf)
            return master, nk

        self._init_call = init_call

    def _owned_call_offsets(self) -> np.ndarray:
        """Sorted per-call block offsets owned by THIS process's devices
        under the staging layout (lanes over the data axis). Model-axis
        replicas collapse to one entry."""
        c = self.config
        S, B = c.steps_per_call, c.batch_tokens
        sh = NamedSharding(self.mesh, P(None, core.DATA_AXIS))
        imap = sh.devices_indices_map((S, B))
        offs = set()
        for d in sh.addressable_devices:
            ssl, bsl = imap[d]
            s0 = 0 if ssl.start is None else ssl.start
            s1 = S if ssl.stop is None else ssl.stop
            b0 = 0 if bsl.start is None else bsl.start
            b1 = B if bsl.stop is None else bsl.stop
            # call-0 block ids ARE the per-call offsets — go through
            # _block_rows so ownership can never desync from staging
            offs.update(
                self._block_rows(0, s0, s1, b0, b1).reshape(-1).tolist())
        return np.sort(np.fromiter(offs, np.int64))

    def _global_of_local(self, l: np.ndarray) -> np.ndarray:
        """local_corpus: host-array block index -> global block id
        (identity otherwise — host arrays ARE globally indexed then)."""
        if not (self.config.stream_blocks and self.config.local_corpus):
            return l
        k, pos = np.divmod(l, self._own_per_call)
        return k * self._per_call + self._own_offs[pos]

    def _local_of_global(self, g: np.ndarray) -> np.ndarray:
        """local_corpus: global block id -> host-array index. Only ever
        called for blocks this process owns (staging/drain walk the
        process's own lanes)."""
        if not (self.config.stream_blocks and self.config.local_corpus):
            return g
        k, off = np.divmod(g, self._per_call)
        return k * self._own_per_call + np.searchsorted(self._own_offs,
                                                        off)

    def _block_rows(self, k: int, s0: int, s1: int, b0: int,
                    b1: int) -> np.ndarray:
        """Host block indices of the [s0:s1, b0:b1] lane rectangle of
        call ``k`` — THE single (step, B-lane) → packed-host-block
        mapping. Staging, z readback, and cross-host sync all go through
        it so they cannot disagree on which blocks a device owns."""
        TB = self._tb
        nbs = self.config.batch_tokens // TB
        return (k * self._per_call + np.arange(s0, s1)[:, None] * nbs
                + b0 // TB + np.arange((b1 - b0) // TB)[None, :])

    def _stream_stage(self, k: int):
        """Host side of staging call ``k``. Single-process: one stacked
        [3, S, B] int32 array (words, doc-rows, z) — a single H2D
        transfer per call. Multi-process: a list of (device, local
        chunk) covering ONLY this process's addressable lanes — the host
        never materialises (or copies) the other hosts' share of the
        call, so per-process host bandwidth scales with 1/P."""
        c = self.config
        S, B = c.steps_per_call, c.batch_tokens
        if jax.process_count() == 1:
            sl = slice(k * self._per_call, (k + 1) * self._per_call)
            return np.stack([self._tw_host[sl].reshape(S, B),
                             self._drel_host[sl].reshape(S, B),
                             self._z_host[sl].reshape(S, B)])
        imap = self._stage_sharding.devices_indices_map((3, S, B))
        parts = []
        for d in self._stage_sharding.addressable_devices:
            _csl, ssl, bsl = imap[d]
            s0 = 0 if ssl.start is None else ssl.start
            s1 = S if ssl.stop is None else ssl.stop
            b0 = 0 if bsl.start is None else bsl.start
            b1 = B if bsl.stop is None else bsl.stop
            bidx = self._local_of_global(
                self._block_rows(k, s0, s1, b0, b1))
            shp = (s1 - s0, b1 - b0)
            parts.append((d, np.stack([
                self._tw_host[bidx].reshape(shp),
                self._drel_host[bidx].reshape(shp),
                self._z_host[bidx].reshape(shp)])))
        return parts

    def _place_stream(self, staged) -> jax.Array:
        """Place one staged call on the mesh. Single-process: one async
        device_put. Multi-process: assemble the global array from the
        per-device chunks ``_stream_stage`` built — each process
        transfers ONLY its addressable lanes (process-local staging; the
        cross-host layout is implied by the sharding, no host ever ships
        another host's shard)."""
        sh = self._stage_sharding
        if jax.process_count() == 1:
            return jax.device_put(staged, sh)
        c = self.config
        shape = (3, c.steps_per_call, c.batch_tokens)
        shards = [jax.device_put(arr, d) for d, arr in staged]
        return jax.make_array_from_single_device_arrays(shape, sh, shards)

    def _stream_calls(self):
        """Double-buffered H2D pipeline: host slices are stacked on a
        prefetch thread (utils.async_buffer) and device_put (async) from
        the consumer, so call k+1's transfer overlaps call k's sweep."""
        from multiverso_tpu.utils.async_buffer import prefetch_iterator

        def gen():
            for k in range(self.calls_per_sweep):
                yield k, self._stream_stage(k)

        for k, stacked in prefetch_iterator(gen(), depth=2):
            yield k, self._place_stream(stacked)

    def _init_streamed_counts(self) -> None:
        master = core.sharded_zeros(self.word_topic.storage_shape,
                                    jnp.int32, self.word_topic.sharding)
        nk = core.sharded_zeros(self.summary.padded_shape, jnp.int32,
                                self.summary.sharding)
        for _k, dev in self._stream_calls():
            master, nk = self._init_call(master, nk, dev)
        self.word_topic.put_raw(master)
        self.summary.put_raw(nk)

    def _sync_z_host(self) -> None:
        """Make the host z copy globally complete (multi-process only).

        Training never needs this: each process stages and drains exactly
        the lanes its devices own. Full-z consumers (doc_topics, store)
        call it lazily — the owned lanes are exchanged with one
        ``process_allgather`` of equal-sized [cap, TB] slabs PER SWEEP
        CALL (uniform sharding ⇒ every process owns the same lane count;
        model-axis replicas write identical data, which is idempotent).
        Chunking by call keeps the peak device/host transfer bounded for
        out-of-core-scale corpora — a single whole-sweep allgather would
        materialise the global z through device memory on every host at
        once (ADVICE r3), exactly what stream_blocks exists to avoid."""
        if jax.process_count() == 1 or self._z_synced \
                or self.config.local_corpus:
            # local_corpus: z is per-process BY DESIGN (each process owns
            # its shard's lanes); there is no global host z to complete
            return
        offs = self._owned_call_offsets()
        from jax.experimental import multihost_utils
        # ownership offsets are call-invariant: gather them ONCE and
        # derive each call's global block ids locally (one collective
        # per chunk instead of two)
        all_offs = np.asarray(multihost_utils.process_allgather(offs))
        for k in range(self.calls_per_sweep):
            blocks = k * self._per_call + offs
            all_vals = np.asarray(multihost_utils.process_allgather(
                self._z_host[blocks]))
            for p in range(all_offs.shape[0]):
                self._z_host[k * self._per_call + all_offs[p]] = \
                    all_vals[p]
        self._z_synced = True

    def _sweep_streamed(self) -> None:
        wstale = self._refresh_mirror()
        per_call, TB = self._per_call, self._tb
        # fresh accumulator: after the sweep it IS the new master
        # (counts telescope — see the superstep body)
        acc = core.sharded_zeros(self.word_topic.storage_shape, jnp.int32,
                                 self.word_topic.sharding)
        pending: list = []

        def drain(item):
            # write back by addressable shard: each process updates only
            # the z lanes its own devices computed (multi-host safe;
            # model-axis replicas rewrite identical data, which is fine)
            k, z_out = item
            seen = set()
            for shard in z_out.addressable_shards:
                ssl, bsl = shard.index        # rectangular [S, B] chunk;
                # XLA may shard the aux over EITHER axis, so honor both.
                # Model-axis replicas carry identical data — fetch each
                # distinct chunk ONCE, not once per replica (mp x the
                # D2H bytes on the per-call hot path otherwise)
                key = (ssl.start, ssl.stop, bsl.start, bsl.stop)
                if key in seen:
                    continue
                seen.add(key)
                s0 = 0 if ssl.start is None else ssl.start
                b0 = 0 if bsl.start is None else bsl.start
                data = np.asarray(shard.data)  # [S_local, B_local]
                bidx = self._local_of_global(
                    self._block_rows(k, s0, s0 + data.shape[0],
                                     b0, b0 + data.shape[1]))
                self._z_host[bidx.reshape(-1)] = data.reshape(-1, TB)

        for k, dev in self._stream_calls():
            with telemetry.span("lda.dispatch"):
                (acc, self._calls_dev), z_out = self._fused_stream(
                    (acc, self._calls_dev), wstale, dev, self._key)
            self._calls_done += 1
            try:
                z_out.copy_to_host_async()
            except AttributeError:
                pass
            pending.append((k, z_out))
            if len(pending) > 2:
                drain(pending.pop(0))
        for item in pending:
            drain(item)
        self._z_synced = False   # other processes' lanes are now stale
        self.word_topic.put_raw(acc)

    # -- count init --------------------------------------------------------

    def _init_counts(self) -> None:
        """The ``gibbs`` stream's count build (one jitted scatter); the
        dense doc-topic counts are worker-local, +1 scratch doc for the
        padded lanes."""
        @jax.jit
        def build(z, tw, td, m):
            nwk = jnp.zeros(self.word_topic.storage_shape, jnp.int32)
            nwk = nwk.at[tw, z].add(m)
            ndk = jnp.zeros((self.num_docs + 1, self.K), jnp.int32)
            ndk = ndk.at[td, z].add(m)
            nk = jnp.zeros(self.summary.padded_shape, jnp.int32)
            nk = nk.at[z].add(m)
            return nwk, ndk, nk

        nwk, ndk, nk = build(
            self._z, self._place(self._tw, P()), self._place(self._td, P()),
            self._place(self._mask.astype(np.int32), P()))
        self.word_topic.put_raw(nwk)
        self._ndk = ndk
        self.summary.put_raw(nk)

    # -- the Gibbs superstep ----------------------------------------------

    def _build_superstep(self) -> None:
        c = self.config
        alpha, beta = self.alpha, self.beta
        vbeta = self.V * beta
        K = self.K

        def scan_body(carry, inp):
            nwk, ndk, nk, z = carry
            w, d, idx, msk, key = inp
            zi = jnp.take(z, idx)
            # padded lanes must not touch counts: nwk/ndk park them on
            # scratch rows, but nk has no scratch slot — phantom counts
            # would drift between topics across sweeps
            one = msk
            # remove the token's own count (proper collapsed Gibbs);
            # nk's element scatter (B updates into K bins, heavy
            # duplicates) is pathologically slow on TPU — use a masked
            # one-hot reduction instead (measured ~5x whole-step win)
            nwk = nwk.at[w, zi].add(-one)
            ndk = ndk.at[d, zi].add(-one)
            oh_old = jax.nn.one_hot(zi, K, dtype=jnp.int32) * one[:, None]
            nk = nk.at[:K].add(-oh_old.sum(0))
            ft = jnp.bfloat16 if c.precision == "bfloat16" \
                else jnp.float32
            A = jnp.take(ndk, d, axis=0).astype(ft)             # [B, K]
            W = jnp.take(nwk, w, axis=0).astype(ft)             # [B, K]
            S = (nk[:K].astype(jnp.float32) + vbeta).astype(ft)  # [K]
            # linear-space posterior + inverse-CDF sampling: one uniform
            # per token (vs K gumbels), no logs — the RNG was the hot op.
            # Batch-stale decrements can transiently dip below zero; clamp
            # (AD-LDA approximation, see module docstring)
            probs = jnp.maximum((A + ft(alpha)) * (W + ft(beta)),
                                ft(0.0)) / S                    # [B, K]
            cdf = jnp.cumsum(probs, axis=1)
            u = jax.random.uniform(key, (probs.shape[0], 1)) \
                .astype(ft) * cdf[:, -1:]
            znew = jnp.minimum((cdf < u).sum(axis=1),
                               K - 1).astype(jnp.int32)
            nwk = nwk.at[w, znew].add(one)
            ndk = ndk.at[d, znew].add(one)
            oh_new = jax.nn.one_hot(znew, K, dtype=jnp.int32) * one[:, None]
            nk = nk.at[:K].add(oh_new.sum(0))
            z = z.at[idx].set(znew)
            return (nwk, ndk, nk, z), ()

        def body(params, states, locals_, options, ws, ds, idxs, msks,
                 base_key):
            nwk, nk = params
            ndk, z, calls = locals_
            keys = jax.random.split(jax.random.fold_in(base_key, calls),
                                    ws.shape[0])
            (nwk, ndk, nk, z), _ = lax.scan(
                scan_body, (nwk, ndk, nk, z), (ws, ds, idxs, msks, keys))
            return (nwk, nk), states, (ndk, z, calls + 1), None

        # supported fused path: tables = (word_topic, summary); app-local
        # carry = (doc-topic counts, z assignments, the call counter)
        self._fused = make_superstep((self.word_topic, self.summary), body,
                                     name="lda_gibbs")

        @jax.jit
        def loglik(nwk, ndk, nk, ws, ds, mask):
            # operands are the pre-placed [S, B] superstep inputs (mask
            # int32) — flatten here rather than re-uploading the corpus
            # from host every eval
            ws, ds = ws.reshape(-1), ds.reshape(-1)
            m = mask.reshape(-1).astype(jnp.float32)
            A = jnp.take(ndk, ds, axis=0).astype(jnp.float32)
            W = jnp.take(nwk, ws, axis=0).astype(jnp.float32)
            S = nk[:K].astype(jnp.float32)
            return _predictive_ll(A, W, S, m, alpha, beta, K, vbeta)

        self._loglik = loglik

    def _place(self, arr: np.ndarray, spec) -> jax.Array:
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _count_calls_from(self, n: int) -> None:
        """Set the host's count of superstep calls (what a checkpoint
        records) and seed the supersteps' device copy from it: call i
        folds ``i`` into the base key inside its program and hands on
        i + 1, so no key is made on the host per call."""
        self._calls_done = int(n)
        self._calls_dev = self._place(np.asarray(n, np.int32), P())

    # -- training ----------------------------------------------------------

    def sweep(self) -> None:
        """One full sampling pass over the corpus. ``lda.sweep`` is the
        host's time to ISSUE it (dispatch is asynchronous): the mirror
        cast (``lda.to_stale``), every superstep call (``lda.dispatch``)
        and the master rebuild (``lda.rebuild``) nest inside it."""
        with telemetry.span("lda.sweep"):
            if self.config.stream_blocks:
                self._sweep_streamed()
            else:
                self._sweep_resident()

    def _refresh_mirror(self) -> jax.Array:
        """The sweep's bf16 word-row cache, whole on every chip: one
        all-gather over the model axis where that axis has chips."""
        with telemetry.span("lda.to_stale"):
            wstale = self._to_stale(self.word_topic.raw())
        if self.mesh.shape[core.MODEL_AXIS] > 1:
            telemetry.counter("lda.mirror.replications").inc()
        return wstale

    def _sweep_resident(self) -> None:
        # the doc-blocked body takes the sweep's mirror before its lanes
        mirror = (self._refresh_mirror(),) if self._docblock else ()
        for call in self._calls:
            with telemetry.span("lda.dispatch"):
                (self._ndk, self._z, self._calls_dev), _ = self._fused(
                    (self._ndk, self._z, self._calls_dev), *mirror, *call,
                    self._key)
            self._calls_done += 1
        if self._docblock:
            # fold the sweep's moves into the int32 master (the
            # reference's block-end Add of accumulated deltas)
            with telemetry.span("lda.rebuild"):
                nwk = self._rebuild(self._z, self._tw_flat,
                                    self._mask_flat)
                self.word_topic.put_raw(nwk)

    def train(self, num_iterations: Optional[int] = None) -> float:
        """Run Gibbs sweeps; returns the final per-token log-likelihood.
        Eval runs every ``eval_every`` sweeps (and always on the last):
        the predictive-likelihood pass re-gathers count rows for the
        whole corpus, a sweep-sized cost the reference's Eval role also
        pays only periodically."""
        iters = num_iterations if num_iterations is not None \
            else self.config.num_iterations
        every = max(self.config.eval_every, 1)
        t0 = time.perf_counter()
        ck_every = self.config.checkpoint_interval
        # the restored cursor applies ONCE (the resume); later train()
        # calls start from 0 like they always did
        start_sweep = min(self._resume_sweeps, iters)
        self._resume_sweeps = 0
        it = start_sweep
        while it < iters:
            # divergence rollback (MVTPU_HEALTH_ACTION=rollback):
            # restore_run_state moved the sweep cursor back to the last
            # clean generation — replay from there (sweep keys derive
            # from the call counter, which the restore re-seeds from the
            # rewound _calls_done)
            if telemetry.health.maybe_rollback(self) is not None:
                it = min(self._resume_sweeps, iters)
                self._resume_sweeps = 0
                continue
            self.sweep()        # times itself: the lda.sweep span
            telemetry.step_timeline("lda", it, tokens=self.num_tokens)
            telemetry.beat()    # flight recorder: a heartbeat per sweep
            self._sweep_done = it + 1
            if self.run_ckpt is not None:
                # run-level manager (replaces the bespoke
                # checkpoint_interval prefix dump): atomic generations,
                # keep-K retention, overlapped writes; collective
                self.run_ckpt.maybe_save(it + 1, self.run_state)
            elif ck_every > 0 and self.config.checkpoint_prefix \
                    and (it + 1) % ck_every == 0:
                # legacy periodic full-state dump (sampler state
                # included, so a crash resumes mid-training); collective
                self.store(self.config.checkpoint_prefix)
            it += 1
            if it % every and it != iters:
                continue
            ll = self.loglik()
            self.ll_history.append(ll)
            log.info("lightlda iter %d: loglik/token=%.4f", it - 1, ll)
        dt = time.perf_counter() - t0
        tokens = self.num_tokens * max(iters - start_sweep, 0)
        telemetry.counter("lda.tokens").inc(tokens)
        telemetry.emit("lda.doc_tokens_per_sec", tokens / dt,
                       "tokens/s")
        log.info("lightlda done: %d iters, %.0f doc-tokens/s",
                 iters, tokens / dt)
        return self.ll_history[-1] if self.ll_history else float("nan")

    # -- eval / output -----------------------------------------------------

    def loglik(self) -> float:
        """Mean per-token predictive log-likelihood (the reference's
        `Eval` role). Evaluates over the pre-placed device-resident call
        slices — the token stream is static, so no host re-upload."""
        total = 0.0
        if self.config.stream_blocks:
            for _k, dev in self._stream_calls():
                total += float(self._loglik_stream(
                    self.word_topic.raw(), self.summary.raw(), dev))
            return total / max(self.num_tokens, 1)
        for i, call in enumerate(self._calls):
            if self._docblock:
                ws, _drels, msks, steps = call
                args = (ws, self._loglik_rows[i], msks, steps)
            else:
                ws, ds, _idxs, msks = call
                args = (ws, ds, msks)
            total += float(self._loglik(
                self.word_topic.raw(), self._ndk, self.summary.raw(),
                *args))
        return total / max(self.num_tokens, 1)

    def doc_topics(self) -> np.ndarray:
        """[num_docs, K] doc-topic counts (worker-local state).

        Multi-process ``stream_blocks`` note: this is a COLLECTIVE —
        the lazy z sync all-gathers owned lanes, so every process must
        call it in lockstep (an ``if rank == 0:`` guard deadlocks).
        Under ``local_corpus`` there is no sync: the returned counts
        cover THIS process's docs; other processes' rows are zero."""
        if self.config.stream_blocks:
            self._sync_z_host()
            # host-side scatter over the host-resident z (chunked: the
            # temporaries stay bounded regardless of corpus size)
            out = np.zeros((self.num_docs, self.K), np.int32)
            chunk = max(1, (1 << 22) // self._tb)     # ~4M tokens
            for lo in range(0, len(self._tw_host), chunk):
                sl = slice(lo, lo + chunk)
                tw, drel = self._tw_host[sl], self._drel_host[sl]
                z = self._z_host[sl]
                blocks = np.arange(lo, lo + len(tw))[:, None]
                docs = self._doc_of_row[blocks, drel]
                valid = (tw != self._scratch_word) & (docs >= 0)
                np.add.at(out, (docs[valid], z[valid]), 1)
            return out
        if self._docblock:
            blocked = _to_host(self._ndk).reshape(
                self._nb_pad * self._maxd, self.K)
            out = np.zeros((self.num_docs, self.K), np.int32)
            valid = self._blk_of_doc >= 0
            out[valid] = blocked[self._blk_of_doc[valid] * self._maxd
                                 + self._row_of_doc[valid]]
            return out
        return np.asarray(self._ndk[: self.num_docs]).reshape(
            self.num_docs, self.K)

    def assignments(self) -> np.ndarray:
        """int32[num_tokens]: every token's current topic, in the order
        the corpus was handed to the constructor — whatever the sampler
        did to the stream (doc sort and block packing, or the fixed
        shuffle). One read of z from the device.

        Multi-process ``stream_blocks``: a COLLECTIVE, like
        :meth:`doc_topics` (the lazy z sync); under ``local_corpus`` the
        result covers this process's own shard."""
        if not self._docblock:
            # z lives in the shuffled stream's index space: position j
            # holds the token the seed's permutation drew from perm[j]
            perm = np.random.default_rng(
                self.config.seed ^ 0x5EED).permutation(len(self._mask))
            out = np.empty(len(perm), np.int32)
            out[perm] = np.asarray(self._z)
            return out[: self.num_tokens]
        if self.config.stream_blocks:
            self._sync_z_host()
            real = self._tw_host != self._scratch_word
            z = self._z_host[real]
        else:
            real = np.asarray(self._mask_flat).astype(bool)
            z = _to_host(self._z).reshape(-1)[real]
        # the packer keeps the doc-sorted order, so the real lanes in
        # packed order ARE the sorted stream
        if self._doc_order is None:
            return z
        out = np.empty_like(z)
        out[self._doc_order] = z
        return out

    def word_topics(self) -> np.ndarray:
        """[V, K] word-topic counts from the table (a bounded-staleness
        cached view under ``MVTPU_STALENESS`` — logging/eval reads skip
        the per-call blocking fetch)."""
        if self._wt_view is not None:
            return self._wt_view.get()
        return self.word_topic.get()

    def top_words(self, topic: int, k: int = 10) -> np.ndarray:
        return np.argsort(-self.word_topics()[:, topic])[:k]

    def dump_model(self, uri: str, rows_per_fetch: int = 4096) -> None:
        """Write the word-topic model in the reference's sparse text
        format — one line per word, ``word_id topic:count ...`` with only
        the NONZERO entries (the lightlda model dump shape). Fetches go
        through :meth:`SparseMatrixTable.get_rows_sparse`, so only the
        nonzero entries ever cross device→host (a converged topic model
        is ~99% zeros per row)."""
        from multiverso_tpu.io import open_stream
        import contextlib
        # every process runs the (collective) fetches; only rank 0
        # writes — concurrent 'wb' on a shared filesystem would corrupt
        write = jax.process_index() == 0
        stream = open_stream(uri, "wb") if write \
            else contextlib.nullcontext()
        with stream:
            for lo in range(0, self.V, rows_per_fetch):
                ids = np.arange(lo, min(lo + rows_per_fetch, self.V))
                indptr, cols, vals = \
                    self.word_topic.get_rows_sparse(ids)
                if not write:
                    continue
                lines = []
                for i, w in enumerate(ids):
                    ent = " ".join(
                        f"{k}:{v}" for k, v in
                        zip(cols[indptr[i]:indptr[i + 1]],
                            vals[indptr[i]:indptr[i + 1]]))
                    lines.append(f"{w} {ent}".rstrip())
                stream.write(("\n".join(lines) + "\n").encode())

    def _export_sampler_state(self):
        """(manifest scalars, payload arrays) of the sampler state —
        z assignments + doc-topic counts in the layout-appropriate
        encoding. ONE copy of the export logic, shared by the legacy
        prefix :meth:`store` and the run-manager :meth:`run_state`.

        Multi-process ``stream_blocks`` note: COLLECTIVE (like table
        store) — the lazy z sync all-gathers owned lanes, so every
        process must call it in lockstep (an ``if rank == 0:`` guard
        deadlocks)."""
        if self._docblock:
            if self.config.local_corpus:
                # per-process shard: z alone is the sampler state (load
                # for streamed layouts never reads ndk) — a global-size
                # dense ndk per rank would defeat the 1/P host scaling
                dense = np.zeros((0, self.K), np.int16)
                z = self._z_host.reshape(-1)
            else:
                # z is indexed in the packed block layout; ndk exports
                # as the dense [D, K] logical counts (the in-memory
                # loader rebuilds its blocked counts from it)
                dense = np.zeros((self.num_docs + 1, self.K), np.int16)
                dense[:self.num_docs] = self.doc_topics()
                if self.config.stream_blocks:
                    self._sync_z_host()
                    z = self._z_host.reshape(-1)
                else:
                    z = _to_host(self._z).reshape(-1)
            layout = "docblock"
        else:
            dense = np.asarray(self._ndk).reshape(self.num_docs + 1,
                                                  self.K)
            z = np.asarray(self._z)
            layout = "stream"
        manifest = {"magic": "multiverso_tpu.lda_state.v1",
                    "num_tokens": self.num_tokens,
                    # torn-set detection: the state file is written LAST
                    # and records the table's step — a crash between the
                    # per-file-atomic writes is caught at load
                    "word_topic_step":
                        self.word_topic.default_option.step,
                    "perm_seed": self.config.seed,
                    "t_pad": int(z.shape[0]),
                    "layout": layout,
                    "calls_done": self._calls_done}
        if self._docblock:
            # z indexing depends on the exact packing: equal padded
            # lengths with different block geometry must not load
            manifest["block_tokens"] = self.config.block_tokens
            manifest["block_docs"] = self.config.block_docs
        if self.config.local_corpus:
            # per-process sampler-state shard (z and doc counts are
            # process-local under local_corpus); same process layout
            # required to resume
            manifest["layout"] = "docblock_local"
            manifest["processes"] = jax.process_count()
            # per-rank shard identity (ADVICE r3): the process-count and
            # num_tokens checks alone would accept a DIFFERENT doc-to-
            # process split (or device order) of equal sizes, silently
            # binding the loaded z to the wrong documents/blocks
            crc, ntok = self._local_shard_digest()
            manifest["shard_crc32"] = crc
            manifest["local_tokens"] = ntok
        return manifest, {"z": z, "ndk": dense}

    def store(self, uri_prefix: str) -> None:
        """Checkpoint tables AND sampler state (z, doc-topic counts):
        the three must stay consistent or resumed sweeps corrupt counts.
        Collectivity caveats: see :meth:`_export_sampler_state`."""
        from multiverso_tpu.tables.base import savez_stream
        self.word_topic.store(f"{uri_prefix}.word_topic.npz")
        self.summary.store(f"{uri_prefix}.summary.npz")
        manifest, payload = self._export_sampler_state()
        state_path = f"{uri_prefix}.state.npz"
        if self.config.local_corpus:
            state_path = (f"{uri_prefix}.state"
                          f".rank{jax.process_index()}.npz")
        # every rank writes (z is globally complete after the sync above,
        # so the shared-path payloads are identical; per-process targets
        # like mem:// need their own copy); shared-path safety comes from
        # the stream layer's atomic rename
        savez_stream(state_path, manifest, payload)
        self._last_store = (uri_prefix, self._calls_done)

    def _local_shard_digest(self):
        """(crc32, local token count) identifying THIS rank's corpus
        shard AND its packed layout: token words, doc-relative rows, and
        the device-order-derived owned lane offsets all feed the crc, so
        resuming with a different split/ordering of equal sizes is
        rejected instead of corrupting counts."""
        import zlib
        crc = zlib.crc32(self._tw_host.tobytes())
        crc = zlib.crc32(self._drel_host.tobytes(), crc)
        crc = zlib.crc32(np.ascontiguousarray(
            np.asarray(self._own_offs, np.int64)).tobytes(), crc)
        ntok = int((self._tw_host != self._scratch_word).sum())
        return int(crc), ntok

    def load(self, uri_prefix: str) -> None:
        from multiverso_tpu.tables.base import loadz_stream
        self.word_topic.load(f"{uri_prefix}.word_topic.npz")
        self.summary.load(f"{uri_prefix}.summary.npz")
        state_path = f"{uri_prefix}.state.npz"
        if self.config.local_corpus:
            state_path = (f"{uri_prefix}.state"
                          f".rank{jax.process_index()}.npz")
        manifest, data = loadz_stream(state_path,
                                      "multiverso_tpu.lda_state.v1")
        self._import_sampler_state(manifest, data)

    def _import_sampler_state(self, manifest, data) -> None:
        """Validate + install sampler state (z, doc counts) against the
        LIVE tables — ONE copy of the geometry/seed/layout/torn-set
        checks, shared by the legacy prefix :meth:`load` and the
        run-manager :meth:`restore_run_state`. ``data`` is dict-like
        with ``"z"``/``"ndk"`` arrays."""
        if self.config.local_corpus and \
                manifest.get("processes") != jax.process_count():
            raise ValueError(
                f"local_corpus checkpoint was written by "
                f"{manifest.get('processes')} processes, app has "
                f"{jax.process_count()}: z shards are per-process")
        if self.config.local_corpus and "shard_crc32" in manifest:
            crc, ntok = self._local_shard_digest()
            if (manifest["shard_crc32"], manifest["local_tokens"]) \
                    != (crc, ntok):
                raise ValueError(
                    f"local_corpus checkpoint rank shard mismatch "
                    f"(crc32 {manifest['shard_crc32']:#x}/"
                    f"{manifest['local_tokens']} tokens != this app's "
                    f"{crc:#x}/{ntok}): the doc-to-process split and "
                    "device order must match the checkpointing run — "
                    "loading z against a different shard silently "
                    "corrupts counts")
        if manifest["num_tokens"] != self.num_tokens:
            raise ValueError(
                f"checkpoint has {manifest['num_tokens']} tokens, app has "
                f"{self.num_tokens} — same corpus required to resume")
        if "word_topic_step" in manifest and \
                self.word_topic.default_option.step \
                != int(manifest["word_topic_step"]):
            raise ValueError(
                "lda checkpoint is torn: state was "
                f"written at word_topic step "
                f"{manifest['word_topic_step']} but the loaded table "
                f"is at step {self.word_topic.default_option.step} — a "
                "crash interrupted the multi-file store; use an older "
                "complete checkpoint")
        if manifest["perm_seed"] != self.config.seed:
            raise ValueError(
                f"checkpoint was written with seed "
                f"{manifest['perm_seed']}, app has seed "
                f"{self.config.seed}: z is indexed in the seed-derived "
                "stream permutation, so the seeds must match to resume")
        my_layout = "stream" if not self._docblock else \
            ("docblock_local" if self.config.local_corpus else "docblock")
        ck_layout = manifest.get("layout", "stream")
        if ck_layout != my_layout:
            raise ValueError(
                f"checkpoint z layout {ck_layout!r} != app layout "
                f"{my_layout!r}: z indexing is layout-specific")
        if self._docblock:
            want = (self.config.block_tokens, self.config.block_docs)
            got = (manifest.get("block_tokens"),
                   manifest.get("block_docs"))
            if got != want:
                raise ValueError(
                    f"checkpoint block geometry {got} != app {want}: "
                    "z packing must match to resume")
        # T_pad depends on batch_tokens * steps_per_call (and the block
        # packing under tiled): a geometry mismatch would yield a
        # wrong-length z whose out-of-range scatters silently corrupt
        # counts (JAX clamps/drops OOB indices)
        streamed = self.config.stream_blocks
        z_shape = self._z_host.shape if streamed else self._z.shape
        if len(data["z"]) != int(np.prod(z_shape)):
            raise ValueError(
                f"checkpoint z length {len(data['z'])} != app stream "
                f"length {int(np.prod(z_shape))}: batch/block "
                "geometry must match the checkpointing run to resume")
        if streamed:
            # host z is the sampler state; blocked doc counts are derived
            # from it per call, so the stored dense ndk is not needed
            self._z_host = np.asarray(data["z"]).reshape(z_shape) \
                .astype(np.int32)
            self._z_synced = True    # checkpoint z is globally complete
            self._count_calls_from(manifest.get("calls_done", 0))
            return
        # restore INTO the live arrays' own shardings (what the fused
        # superstep's donation aliasing was compiled against). The stored
        # z is the packed block order flat and the counts are dense
        # [D, K]: neither knows how the blocks lie over the chips
        self._z = jax.device_put(
            np.asarray(data["z"]).reshape(self._z.shape),
            self._z.sharding)
        dense = np.asarray(data["ndk"])
        ndk_sharding = self._ndk.sharding
        if self._docblock:
            blocked = np.zeros(self._ndk.shape,
                               np.dtype(self._ndk.dtype)).reshape(
                self._nb_pad * self._maxd, -1)
            valid = self._blk_of_doc >= 0
            rows = (self._blk_of_doc[valid] * self._maxd
                    + self._row_of_doc[valid])
            blocked[rows] = dense[:self.num_docs][valid].reshape(
                int(valid.sum()), -1)
            self._ndk = jax.device_put(
                blocked.reshape(self._ndk.shape), ndk_sharding)
        else:
            self._ndk = jax.device_put(
                dense.reshape(self._ndk.shape).astype(self._ndk.dtype),
                ndk_sharding)
        # resume the RNG sequence where the checkpoint left off; replaying
        # consumed fold_in keys would correlate sweeps across the resume
        self._count_calls_from(manifest.get("calls_done", 0))

    # -- fault tolerance (ft.checkpoint contract) --------------------------

    def run_state(self) -> dict:
        """Train-state for the run manager: the sampler state (z +
        doc-topic counts, via the shared export) plus the sweep cursor.
        The tables ride the manager's own table export. COLLECTIVE
        under multi-process ``stream_blocks`` (see
        :meth:`_export_sampler_state`)."""
        manifest, payload = self._export_sampler_state()
        # the scalars flatten into the app-state manifest; arrays into
        # the payload — restore_run_state reassembles both
        return {**manifest, **payload, "sweep_done": self._sweep_done}

    def restore_run_state(self, restored) -> None:
        self._import_sampler_state(restored.state, restored.arrays)
        self._sweep_done = int(restored.get("sweep_done", 0))
        self._resume_sweeps = self._sweep_done


def main(argv=None) -> None:
    """CLI mirroring the reference lightlda binary's flags."""
    from multiverso_tpu.utils import configure
    configure.define_string("input_file", "", "docs in word:count format", overwrite=True)
    configure.define_int("num_topics", 100, "topics", overwrite=True)
    configure.define_float("alpha", -1.0, "doc-topic prior (<0 -> 50/K)",
                           overwrite=True)
    configure.define_float("beta", 0.01, "word-topic prior", overwrite=True)
    configure.define_int("num_iterations", 10, "Gibbs sweeps", overwrite=True)
    configure.define_int("eval_every", 1,
                         "likelihood eval cadence in sweeps", overwrite=True)
    configure.define_int("batch_tokens", 4096, "tokens per scan step", overwrite=True)
    configure.define_string("output_file", "", "model checkpoint prefix", overwrite=True)
    configure.define_string("dump_file", "",
                            "sparse text model dump (word k:count ...)",
                            overwrite=True)
    configure.define_string("sampler", "gibbs",
                            "gibbs | tiled (the doc-blocked pallas "
                            "sampler; K%128==0)",
                            overwrite=True)
    configure.define_int("checkpoint_interval", 0,
                         "store -output_file every N sweeps (0 = only "
                         "at end)", overwrite=True)
    from multiverso_tpu.ft.checkpoint import define_run_flags, wire_app
    define_run_flags()
    core.init(argv)
    path = configure.get_flag("input_file")
    if not path:
        raise SystemExit("-input_file is required")
    tw, td, vocab = load_docs(path)
    a = configure.get_flag("alpha")
    cfg = LDAConfig(
        num_topics=configure.get_flag("num_topics"),
        alpha=None if a < 0 else a,
        beta=configure.get_flag("beta"),
        batch_tokens=configure.get_flag("batch_tokens"),
        num_iterations=configure.get_flag("num_iterations"),
        eval_every=configure.get_flag("eval_every"),
        sampler=configure.get_flag("sampler"),
        checkpoint_prefix=configure.get_flag("output_file"),
        checkpoint_interval=configure.get_flag("checkpoint_interval"),
    )
    app = LightLDA(tw, td, vocab, cfg)
    # fault tolerance: run-level checkpoint/resume, cadence in SWEEPS.
    # -run_dir routes the periodic trigger through the manager (atomic
    # generations + retention), replacing the bespoke prefix dump; the
    # legacy -checkpoint_interval value still sets the cadence.
    mgr = wire_app(app, [app.word_topic, app.summary],
                   every_default=cfg.checkpoint_interval or 1)
    # flight recorder: env-gated stall watchdog + device capture (the
    # per-sweep beat is in train)
    with telemetry.maybe_watchdog("lda"), telemetry.profile_window("lda"):
        app.train()
    if mgr is not None:
        mgr.close()     # drain pending background checkpoint writes
    telemetry.record_device_memory()
    out = configure.get_flag("output_file")
    # skip the end-of-train dump when the last periodic store already
    # wrote this exact state (a second full collective dump is pure
    # waste at scale)
    if out and getattr(app, "_last_store", ()) != (out, app._calls_done):
        app.store(out)
    dump = configure.get_flag("dump_file")
    if dump:
        app.dump_model(dump)
    core.barrier()


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
