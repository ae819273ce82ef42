#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip: drive each main path once through the entry points a user
calls, at the full width the repo's benchmarks use (lengths cut), check
what comes out by the repo's own means, and say which device did it.

    python chip_smoke.py            # on a host with a TPU

Shape (a chip belongs to ONE process at a time):

- this parent never initialises a JAX backend — it does not even import
  jax. Each trainer/table phase runs in a child (``--phase NAME``), one
  after another, and the child holds the chip for the length of its
  phase. In the two serving phases the chip-holding children are
  ``python -m multiverso_tpu.server`` processes and this parent is
  their jax-free wire client.
- every phase prints ONE JSON line: platform, device_kind, device
  count, wall seconds split into compile and run, persistent-cache
  hits/misses, peak device memory, and what it checked.
- any phase failing, or any phase finding ``platform != "tpu"``, makes
  the run exit non-zero with no result line. Nothing is caught and
  carried past.
- on success the LAST stdout line is
  ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
  with the device as jax reported it to the phases.

Phases: ``w2v`` (WordEmbedding.train: host generator -> prefetch ->
placement -> fused superstep), ``lda`` (LightLDA doc-blocked sampler —
the Mosaic-compiled hot loop), ``tables`` (MatrixTable / tiled
SparseMatrixTable / KVTable get/add/COO-add with duplicate ids, both
kernel engines against a numpy reference), ``attend`` (the latent
attention kernel, forward and backward at the language-model cell's
shape on packed documents, against the plain blocked form),
``w2v_scatter`` (the distinct-row writer against XLA's scatter-add: one
step of the word2vec cell's output side), ``server``
(one wire server; numpy bit-for-bit). With >= 4 devices the base phases run on
``data=4`` and five more follow: ``w2v@2x2``, ``lda@1x1`` (one chip of
the four) and ``lda@2x2``, whose sha256 of the sampler's state after two
sweeps must equal each other's and ``lda``'s, ``tables@2x2`` (sharded
engines under shard_map) and ``fleet`` (four
one-chip members answering the single server's requests); otherwise
that block reports ``"skipped: N device(s)"``.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else
in ``<checkout>/.jax_cache`` (``multiverso_tpu.core`` places it; this
script only counts its entries before and after). A second run in the
same checkout reports fewer compile seconds and no new entries.

``--rehearse-cpu`` is a debugging aid for a host WITHOUT a chip: tiny
sizes on four virtual CPU devices, Pallas kernels interpreted. It is
only ever asked for on the command line — never what happens because
no chip was found — every line it prints says so, and its last line is
not the result line above.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import traceback
import zlib

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.join(HERE, "multiverso_tpu")

# the contract allows 1200 s, compilation included; the parent stops
# starting work short of that and gives each child what is left
BUDGET_S = 1150.0
REHEARSAL_TAG = "cpu — tiny sizes, Pallas interpreted; NOT a chip result"

# widths: bench.py:79-93 (word2vec), benchmarks/measure_lda.py:56-61
# (LightLDA), benchmarks/table_kernels.py SIZES (tables), the wire
# benches' 1<<16 array. Lengths (tokens, calls, sweeps) are cut.
FULL = dict(
    w2v=dict(vocab=10_000, tokens=1_000_000, dim=100, window=5,
             negative=5, batch=4096, steps=512, more_calls=3, lr=0.01),
    lda=dict(vocab=50_000, topics=1024, docs=12_000, tokens=1_228_800,
             block_tokens=512, batch_tokens=307_200),
    tables=dict(rows=10_000, cols=100, row_n=2048, sp_rows=50_000,
                sp_cols=1024, nnz=8192, kv_capacity=1 << 16,
                kv_batch=4096, value_dim=8),
    server=dict(array=1 << 16, kv_capacity=1 << 16, kv_batch=1024,
                value_dim=4),
    # the language-model cell's attention (perf/configs/dsv2_lite_ep8.json)
    attend=dict(sequences=8, length=4096, heads=16, nope=128, rope=64,
                v=128, block=512, doc_median=512, repeats=3),
    # one linear-attention layer's recurrence of the hybrid's cell
    # (perf/configs/olmo_hybrid_7b_vp8.json): 96 and 192 are no multiple
    # of 128 lanes
    gdn_recur=dict(sequences=1, length=4096, heads=30, dk=96, dv=192,
                   chunk=64, doc_median=512, repeats=3),
    # one step of the word2vec cell's output side
    # (perf/configs/w2v_gnews300.json): 4,096 pairs x (1 + 5) rows
    w2v_scatter=dict(vocab=3_000_000, dim=300, lanes=24_576, zipf=1.05),
)
TINY = dict(
    w2v=dict(vocab=500, tokens=40_000, dim=16, window=3, negative=3,
             batch=64, steps=32, more_calls=2, lr=0.05),
    lda=dict(vocab=400, topics=128, docs=160, tokens=8192,
             block_tokens=256, batch_tokens=2048),
    tables=dict(rows=120, cols=20, row_n=48, sp_rows=90, sp_cols=256,
                nnz=64, kv_capacity=2048, kv_batch=48, value_dim=4),
    server=dict(array=1 << 10, kv_capacity=2048, kv_batch=64,
                value_dim=4),
    attend=dict(sequences=2, length=64, heads=2, nope=16, rope=8, v=16,
                block=16, doc_median=12, repeats=1),
    gdn_recur=dict(sequences=2, length=64, heads=3, dk=8, dv=16, chunk=16,
                   doc_median=12, repeats=1),
    w2v_scatter=dict(vocab=3000, dim=20, lanes=600, zipf=1.3),
)


# -- child side: one phase, one process, holds the chip --------------------


class _CompileClock:
    """Every second jax spends tracing, lowering and compiling (a
    persistent-cache read counts as the compile it replaces), and the
    cache's hit/miss counts — from jax's own monitoring events, so bare
    ``jax.jit`` callees count too."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _mesh_arg(spec: str):
    """``"2x2"`` -> a data=2 x model=2 mesh over the first four devices
    (``lda@1x1`` takes one chip of a four-chip host); no spec -> the
    runtime's default mesh over all of them."""
    if not spec:
        return {}
    import jax
    dp, mp = (int(x) for x in spec.split("x"))
    return dict(devices=jax.devices()[: dp * mp], data_parallel=dp,
                model_parallel=mp)


def _check_placement(name: str, arr, mesh, platform: str) -> dict:
    """One shard on EVERY device of the mesh, each the model-axis share
    of the array (tables are row-sharded over ``model`` and replicated
    over ``data``) — nothing piled on device 0."""
    from multiverso_tpu import core
    shards = {int(s.device.id): int(s.data.nbytes)
              for s in arr.addressable_shards}
    want = {int(d.id) for d in mesh.devices.flat}
    assert set(shards) == want, \
        f"{name}: shards on devices {sorted(shards)}, mesh is {sorted(want)}"
    assert all(s.device.platform == platform
               for s in arr.addressable_shards), f"{name}: off-{platform}"
    share = arr.nbytes // mesh.shape[core.MODEL_AXIS]
    assert set(shards.values()) == {share}, \
        f"{name}: shard bytes {shards}, expected {share} each"
    return {"devices": sorted(shards), "bytes_each": share}


def phase_w2v(cfg: dict, mesh, platform: str) -> dict:
    from multiverso_tpu import telemetry
    from multiverso_tpu.apps.word_embedding import W2VConfig, WordEmbedding
    from multiverso_tpu.data.corpus import (Corpus, backend,
                                            synthetic_text)

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "corpus.txt")
        synthetic_text(path, num_tokens=cfg["tokens"],
                       vocab_size=cfg["vocab"], seed=1)
        corpus = Corpus.from_file(path, min_count=1, subsample=1e-3)
    app = WordEmbedding(corpus, W2VConfig(
        embedding_dim=cfg["dim"], window=cfg["window"],
        negative=cfg["negative"], batch_size=cfg["batch"],
        steps_per_call=cfg["steps"], learning_rate=cfg["lr"], epochs=64,
        subsample=1e-3, seed=1), mesh=mesh, name="smoke_w2v")

    def compiles() -> float:
        counters = telemetry.registry().snapshot()["counters"]
        return sum(v for k, v in counters.items()
                   if k.startswith("profile.compiles")
                   and "superstep." in k)

    # the public path, twice: one superstep call (compiles), then a few
    # more that must reuse it
    app.train(total_steps=cfg["steps"])
    first_loss, after_first = app.loss_history[0], compiles()
    app.train(total_steps=cfg["more_calls"] * cfg["steps"])
    losses = [first_loss] + list(app.loss_history)
    assert app._step_no == (1 + cfg["more_calls"]) * cfg["steps"], \
        f"dispatched {app._step_no} steps (corpus exhausted?)"
    assert after_first >= 1 and compiles() == after_first, \
        f"superstep recompiled: {after_first} -> {compiles()}"
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < first_loss, losses
    emb = app.embeddings()
    assert emb.shape == (corpus.vocab_size, cfg["dim"]) \
        and np.isfinite(emb).all()
    return {
        "losses": [round(float(x), 4) for x in losses],
        "superstep_compiles": after_first,
        "pair_generator": "native" if type(backend()).__name__
        == "NativeData" else "python",
        "pairs_trained": app._step_no * cfg["batch"],
        "tables": {t.name: _check_placement(t.name, t.param, mesh,
                                            platform)
                   for t in (app.w_in, app.w_out)},
    }


def phase_lda(cfg: dict, mesh, platform: str) -> dict:
    import hashlib

    from multiverso_tpu import core, telemetry
    from multiverso_tpu.apps.lightlda import LDAConfig, LightLDA

    v, t, d = cfg["vocab"], cfg["tokens"], cfg["docs"]
    rng = np.random.default_rng(0)        # measure_lda's zipf-1.1 draw
    p = 1.0 / np.arange(1, v + 1) ** 1.1
    tw = rng.choice(v, t, p=p / p.sum()).astype(np.int32)
    td = np.sort(rng.integers(0, d, t)).astype(np.int32)
    app = LightLDA(tw, td, v, LDAConfig(
        num_topics=cfg["topics"], batch_tokens=cfg["batch_tokens"],
        steps_per_call=1, seed=1, sampler="tiled", stale_words=True,
        doc_blocked=True, block_tokens=cfg["block_tokens"]),
        mesh=mesh, name="smoke_lda")
    assert app._interpret == (platform == "cpu"), \
        f"sampler kernel interpret={app._interpret} on {platform}"
    app.sweep()
    app.sweep()
    nwk = app.word_topics()
    nk = np.asarray(app.summary.get())
    ndk = app.doc_topics()
    assert nwk.sum() == nk[:app.K].sum() == app.num_tokens == t, \
        (int(nwk.sum()), int(nk.sum()), app.num_tokens)
    assert np.array_equal(nk[:app.K], nwk.sum(0))
    assert np.array_equal(ndk.sum(1), np.bincount(td, minlength=d))
    assert min(nwk.min(), nk.min(), ndk.min()) >= 0
    ll = app.loglik()
    assert np.isfinite(ll), ll
    wt = app.word_topic
    mp = mesh.shape[core.MODEL_AXIS]
    chips = telemetry.gauge("lda.sample.chips").value
    assert chips == mesh.devices.size, \
        f"{chips} chips sample distinct blocks on a mesh of " \
        f"{mesh.devices.size}"

    def sha(a):
        return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()

    return {
        "kernel_interpret": app._interpret, "sweeps": 2,
        "tokens": int(app.num_tokens), "loglik": round(float(ll), 4),
        # what two sweeps left, in the caller's terms: the same bits on
        # every mesh (Smoke.same_lda_state holds the phases to it)
        "sha256": {"assignments": sha(app.assignments()),
                   "word_topic": sha(nwk), "doc_topics": sha(ndk),
                   "summary": sha(nk)},
        "sample_chips": int(chips),
        "count_invariants": "word-topic = summary = doc-topic = tokens",
        "packing_fill": round(float(app.packing_fill), 4),
        "tables": {wt.name: _check_placement(wt.name, wt.param, mesh,
                                             platform)},
        "mesh": f"{mesh.shape[core.DATA_AXIS]}x{mp}",
    }


def _tables_once(cfg: dict, mesh, mode: str) -> dict:
    """Every table op once under one engine mode; returns the final
    logical contents + the engine each kernel ran."""
    import jax
    from multiverso_tpu.tables import (KVTable, MatrixTable,
                                       SparseMatrixTable)
    os.environ["MVTPU_KERNELS"] = mode
    rng = np.random.default_rng(5)
    out: dict = {"engines": {}}

    def note(kernel):
        out["engines"][kernel.name[: -len(mode) - 1]] = \
            f"{kernel.engine}/{kernel.layout}"

    # MatrixTable (flat rows): duplicate-id scatter-add + gather
    m = MatrixTable(cfg["rows"], cfg["cols"], updater="default",
                    mesh=mesh, name=f"smoke_rows_{mode}")
    ids = rng.integers(0, cfg["rows"], cfg["row_n"])
    ids[::7] = ids[0]                                 # heavy duplicates
    deltas = rng.integers(-5, 6, (cfg["row_n"], cfg["cols"])) \
        .astype(np.float32)
    m.add_rows(ids, deltas)
    m.add_rows(ids[::-1], deltas)
    q = rng.integers(0, cfg["rows"], cfg["row_n"])
    out["rows"] = m.get()
    out["rows_get"] = m.get_rows(q)
    ref = np.zeros((cfg["rows"], cfg["cols"]), np.float32)
    np.add.at(ref, ids, deltas)
    np.add.at(ref, ids[::-1], deltas)
    out["rows_ref"], out["rows_get_ref"] = ref, ref[q]
    note(m._scatter_add)
    note(m._gather_rows)

    # SparseMatrixTable(tiled): COO add with duplicate (row, col),
    # row add, gather — LightLDA's word-topic store
    s = SparseMatrixTable(cfg["sp_rows"], cfg["sp_cols"], "int32",
                          updater="default", tiled=True, mesh=mesh,
                          name=f"smoke_coo_{mode}")
    rows = rng.integers(0, cfg["sp_rows"], cfg["nnz"])
    cols = rng.integers(0, cfg["sp_cols"], cfg["nnz"])
    rows[::5], cols[::5] = rows[0], cols[0]           # duplicate pairs
    vals = rng.integers(-4, 5, cfg["nnz"]).astype(np.int32)
    s.add_sparse(rows, cols, vals)
    rid = rng.integers(0, cfg["sp_rows"], 64)
    rdel = rng.integers(0, 7, (64, cfg["sp_cols"])).astype(np.int32)
    s.add_rows(rid, rdel)
    sq = np.concatenate([rows[:32], rid[:32]])
    out["coo_get"] = s.get_rows(sq)
    # the WHOLE table: rows no lane visited must keep their content
    # through the kernels' in-place aliased output
    out["coo"] = s.get()
    sref = np.zeros((cfg["sp_rows"], cfg["sp_cols"]), np.int32)
    np.add.at(sref, (rows, cols), vals)
    np.add.at(sref, rid, rdel)
    out["coo_ref"], out["coo_get_ref"] = sref, sref[sq]
    note(s._coo_scatter_add)
    note(s._scatter_add)
    note(s._gather_rows)

    # KVTable: scalar default + vector adagrad; insert, re-add (the
    # matched-slot path), lookup with missing keys
    keys = rng.choice(np.arange(1, 16 * cfg["kv_batch"], dtype=np.uint64),
                      cfg["kv_batch"], replace=False)
    probe = np.concatenate([keys[: cfg["kv_batch"] // 2],
                            keys[: cfg["kv_batch"] // 2] + np.uint64(10 ** 9)])
    for updater, vd in (("default", 0), ("adagrad", cfg["value_dim"])):
        kv = KVTable(cfg["kv_capacity"], value_dim=vd, updater=updater,
                     mesh=mesh, name=f"smoke_kv_{updater}_{mode}")
        shape = (len(keys), vd) if vd else (len(keys),)
        d1 = rng.integers(-4, 5, shape).astype(np.float32)
        d2 = rng.integers(-4, 5, shape).astype(np.float32)
        kv.add(keys, d1)
        kv.add(keys[::2], d2[::2])
        kv.wait()
        vals_, found = kv.get(probe)
        out[f"kv_{updater}"] = vals_
        out[f"kv_{updater}_found"] = found
        out[f"kv_{updater}_len"] = len(kv)
        out[f"kv_{updater}_keys"] = np.asarray(kv.keys)
        out[f"kv_{updater}_table"] = [np.asarray(kv.values)] + [
            np.asarray(leaf) for leaf in jax.tree.leaves(kv.state)]
        if updater == "default":
            expect = d1.copy()
            expect[::2] += d2[::2]
            half = cfg["kv_batch"] // 2
            out["kv_default_ref"] = np.concatenate(
                [expect[:half], np.zeros(half, np.float32)])
        note(kv._probe_update)
        note(kv._lookup)
    out["kv_found_ref"] = np.concatenate(
        [np.ones(cfg["kv_batch"] // 2, bool),
         np.zeros(cfg["kv_batch"] // 2, bool)])
    return out


def phase_tables(cfg: dict, mesh, platform: str) -> dict:
    import jax.numpy as jnp
    from multiverso_tpu import core, telemetry
    from multiverso_tpu.tables import MatrixTable, make_superstep
    from multiverso_tpu.tables import superstep as ss

    prev = os.environ.get("MVTPU_KERNELS")
    # `pallas` on a CPU rehearsal (interpreted), `auto` on the chip:
    # the selection a user gets is the one checked
    chosen = "auto" if platform != "cpu" else "pallas"
    try:
        x = _tables_once(cfg, mesh, "xla")
        a = _tables_once(cfg, mesh, chosen)

        # in-trace functional forms inside a fused superstep
        fused = {}
        rng = np.random.default_rng(9)
        n = cfg["row_n"]
        ids = rng.integers(0, cfg["rows"], n).astype(np.int32)
        ids[::3] = ids[0]
        deltas = rng.integers(-3, 4, (n, cfg["cols"])).astype(np.float32)
        cols = rng.integers(0, cfg["cols"], n).astype(np.int32)
        vals = rng.integers(-3, 4, n).astype(np.float32)
        for mode in ("xla", chosen):
            os.environ["MVTPU_KERNELS"] = mode
            t = MatrixTable(cfg["rows"], cfg["cols"], updater="default",
                            mesh=mesh, name=f"smoke_fused_{mode}")

            def body(params, states, locals_, options, i, d, c, v):
                (p,) = params
                g = ss.gather_rows(p, i)
                p = ss.row_scatter_add(p, i, g + d)
                p = ss.coo_scatter_add(p, i, c, v)
                return (p,), states, locals_, jnp.abs(g).sum()

            step = make_superstep([t], body, name=f"smoke_fused_{mode}")
            args = [core.place(z, mesh=mesh)
                    for z in (ids, deltas, cols, vals)]
            t.add_rows(ids, deltas, sync=True)
            step((), *args)
            step((), *args)
            t.wait()
            fused[mode] = t.get()
    finally:
        if prev is None:
            os.environ.pop("MVTPU_KERNELS", None)
        else:
            os.environ["MVTPU_KERNELS"] = prev

    exact = ["rows", "rows_get", "coo", "coo_get", "kv_default",
             "kv_default_found", "kv_default_len", "kv_default_keys",
             "kv_adagrad_found", "kv_adagrad_len", "kv_adagrad_keys"]
    for key in exact:                # integer-valued adds: bit-exact
        assert np.array_equal(x[key], a[key]), f"{key}: engines differ"
        ref = x.get(f"{key}_ref")
        if ref is not None:
            assert np.array_equal(x[key], ref), f"{key}: != numpy"
    for r in (x, a):
        assert np.array_equal(r["kv_default_found"], r["kv_found_ref"])
        assert np.array_equal(r["kv_adagrad_found"], r["kv_found_ref"])
    for lx, la in zip(x["kv_default_table"], a["kv_default_table"]):
        assert np.array_equal(lx, la), "kv_default table: engines differ"
    # adagrad divides by a sqrt: two lowerings may round differently
    rtol = 8 * float(np.finfo(np.float32).eps)
    np.testing.assert_allclose(a["kv_adagrad"], x["kv_adagrad"],
                               rtol=rtol, atol=0)
    for lx, la in zip(x["kv_adagrad_table"], a["kv_adagrad_table"]):
        np.testing.assert_allclose(la, lx, rtol=rtol, atol=0)
    assert np.array_equal(fused["xla"], fused[chosen]), \
        "fused superstep: engines differ"

    counters = telemetry.registry().snapshot()["counters"]
    errors = {k: v for k, v in counters.items()
              if k.startswith("kernels.fallbacks") and "reason=error" in k}
    assert not errors, errors
    return {
        "mode": chosen, "engines": a["engines"],
        "engines_reference": sorted(set(x["engines"].values())),
        "matches": "xla engine and numpy, bit-exact (integer-valued "
                   f"adds); adagrad rtol={rtol:.1e}",
        "shapes": {k: cfg[k] for k in sorted(cfg)},
        "fallbacks": {k: v for k, v in counters.items()
                      if k.startswith("kernels.fallbacks")},
        "mesh": f"{mesh.shape[core.DATA_AXIS]}x"
                f"{mesh.shape[core.MODEL_AXIS]}",
    }


def _packed_doc(rng, sequences, length, median):
    """``doc`` [sequences, length] of packed documents, lengths lognormal
    around ``median``."""
    import jax.numpy as jnp
    from multiverso_tpu.data.packing import pack_documents

    lengths = np.clip(np.rint(np.exp(rng.normal(
        np.log(median), 1.0, 64 * sequences))), 3, length).astype(int)
    return jnp.asarray(next(pack_documents(
        (np.ones(n, np.int32) for n in lengths), sequences, length))["doc"])


def _gap(a, b) -> float:
    f32 = lambda x: np.asarray(x, np.float32)
    return float(np.linalg.norm(f32(a) - f32(b))
                 / max(np.linalg.norm(f32(b)), 1e-30))


def _timed(fn, operands, repeats):
    """``fn``'s result and its milliseconds a call, after a first call
    that compiles."""
    import jax

    out = jax.block_until_ready(fn(*operands))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*operands)
    jax.block_until_ready(out)
    return out, round(1e3 * (time.perf_counter() - t0) / repeats, 3)


def _attend_blocked(q_nope, q_pe, k_nope, k_pe, v, doc, scale, block):
    """The plain blocked form the attention kernel replaced: a sequence
    at a time, ``block`` queries against the keys up to the block's end,
    scores, float32 softmax and weighted values in plain XLA; each block
    recomputed in the backward pass."""
    import jax
    import jax.numpy as jnp

    def one(q_nope, q_pe, k_nope, k_pe, v, doc, first):
        end = first + block
        scores = (jnp.einsum("qhd,khd->hqk", q_nope[first:end],
                             k_nope[:end],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("qhd,kd->hqk", q_pe[first:end], k_pe[:end],
                               preferred_element_type=jnp.float32)) * scale
        allowed = (first + jnp.arange(block)[:, None]
                   >= jnp.arange(end)[None, :]) \
            & (doc[first:end, None] == doc[None, :end])
        prob = jax.nn.softmax(jnp.where(allowed[None], scores, -1e30),
                              axis=-1).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", prob, v[:end],
                          preferred_element_type=jnp.float32
                          ).astype(v.dtype)

    one = jax.checkpoint(one, static_argnums=(6,))
    S = doc.shape[1]
    out = jax.lax.map(
        lambda a: jnp.concatenate([one(*a, f) for f in range(0, S, block)]),
        (q_nope, q_pe, k_nope, k_pe, v, doc))
    return out.reshape(out.shape[0], S, -1)


def phase_attend(cfg: dict, mesh, platform: str) -> dict:
    """The latent-attention kernel (Mosaic on the chip), forward and
    backward, at the language-model cell's shape on packed documents,
    against the plain blocked form in the same precision."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.ops import interpret_mode
    from multiverso_tpu.ops import latent_attention as mla

    B, S, H = cfg["sequences"], cfg["length"], cfg["heads"]
    rng = np.random.default_rng(11)
    doc = _packed_doc(rng, B, S, cfg["doc_median"])
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    operands = (draw(B, S, H, cfg["nope"]), draw(B, S, H, cfg["rope"]),
                draw(B, S, H, cfg["nope"]), draw(B, S, cfg["rope"]),
                draw(B, S, H, cfg["v"]))
    weight = draw(B, S, H * cfg["v"])
    scale = float(cfg["nope"] + cfg["rope"]) ** -0.5
    forms = {
        "kernel": lambda *a: mla.attend(
            *a, doc, scale=scale, block=cfg["block"],
            interpret=interpret_mode(mesh)),
        "blocked": lambda *a: _attend_blocked(*a, doc, scale,
                                              cfg["block"])}
    got, ms = {}, {}
    for name, form in forms.items():
        both = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum((form(*a) * weight).astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4)))
        forward = jax.jit(form)
        for label, fn in (("forward", forward), ("both", both)):
            got[name, label], ms[f"{name}_{label}_ms"] = _timed(
                fn, operands, cfg["repeats"])
    gaps = {"o": _gap(got["kernel", "forward"], got["blocked", "forward"])}
    for name, a, b in zip(("q_nope", "q_pe", "k_nope", "k_pe", "v"),
                          got["kernel", "both"][1],
                          got["blocked", "both"][1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        gaps[f"d_{name}"] = _gap(a, b)
    # bfloat16 operands: the two forms round the probabilities at
    # different points (before and after the division by their sum)
    assert all(g < 2e-2 for g in gaps.values()), gaps
    total, computed = (int(n) for n in mla.key_blocks(doc, cfg["block"]))
    return {"shape": {k: cfg[k] for k in sorted(cfg)},
            "matches": "the plain blocked form, bfloat16 operands: "
                       "relative gap of o and of the five gradients < 2e-2",
            "gaps": {k: round(g, 5) for k, g in gaps.items()},
            "key_blocks": total, "key_blocks_computed": computed, **ms}


def _recur_scan(qkv, g, beta, doc, shape):
    """The plain form the recurrence's kernels replaced: the chunk-local
    terms as ``recur`` makes them, then a ``lax.scan`` over the chunks
    with the state as its carry; the backward pass is the scan's own."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.ops import gated_delta as gdn

    w, u, attn, q_in, k_out, keep = gdn.chunk_terms(qkv, g, beta, doc, shape)
    dot = lambda eq, a, b: jnp.einsum(eq, a, b,
                                      preferred_element_type=jnp.float32)

    def chunk(state, xs):
        w, u, attn, q_in, k_out, keep = xs
        held = state.astype(w.dtype)
        newb = (u - dot("hck,hkv->hcv", w, held)).astype(w.dtype)
        o = dot("hck,hkv->hcv", q_in, held) + dot("hij,hjv->hiv", attn, newb)
        return keep * state + dot("hck,hcv->hkv", k_out, newb), o

    o = jax.lax.scan(chunk, jnp.zeros((w.shape[1], shape.dk, shape.dv),
                                      jnp.float32),
                     (w, u, attn, q_in, k_out, keep))[1]
    return gdn.from_chunks(o, qkv.shape[0])


def phase_gdn_recur(cfg: dict, mesh, platform: str) -> dict:
    """The gated delta rule's recurrence (``ops/gated_delta.py``
    ``recur``: the state from chunk to chunk in two Mosaic kernels on
    the chip), forward and backward at the hybrid cell's widths on
    packed documents, against the plain scan over chunks in the same
    precision and in float32."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.ops import gated_delta as gdn
    from multiverso_tpu.ops import interpret_mode

    B, S, H = cfg["sequences"], cfg["length"], cfg["heads"]
    shape = gdn.GatedDeltaShape(H, cfg["dk"], cfg["dv"], 4, True, 1e-6,
                                cfg["chunk"], "bfloat16")
    rng = np.random.default_rng(17)
    doc = _packed_doc(rng, B, S, cfg["doc_median"])
    operands = (
        jnp.asarray(rng.normal(size=(B, S, shape.conv_width)), jnp.float32),
        -jnp.asarray(rng.uniform(1e-3, 0.5, size=(B, S, H)), jnp.float32),
        jnp.asarray(rng.uniform(0.1, 1.9, size=(B, S, H)), jnp.float32))
    weight = jnp.asarray(rng.normal(size=(B, S, H, cfg["dv"])), jnp.float32)

    def float32(*a):
        with jax.default_matmul_precision("highest"):
            return _recur_scan(*a, doc, shape._replace(dtype="float32"))

    forms = {
        "kernel": lambda *a: gdn.recur(*a, doc, shape,
                                       interpret=interpret_mode(mesh)),
        "scan": lambda *a: _recur_scan(*a, doc, shape),
        "float32": float32}
    got, ms = {}, {}
    for name, form in forms.items():
        both = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(form(*a) * weight), argnums=(0, 1, 2)))
        for label, fn in (("forward", jax.jit(form)), ("both", both)):
            got[name, label], ms[f"{name}_{label}_ms"] = _timed(
                fn, operands, cfg["repeats"])
    gaps = {}
    for other in ("scan", "float32"):
        gaps[f"o_{other}"] = _gap(got["kernel", "forward"],
                                  got[other, "forward"])
        for name, a, b in zip(("qkv", "g", "beta"), got["kernel", "both"][1],
                              got[other, "both"][1]):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            gaps[f"d_{name}_{other}"] = _gap(a, b)
    assert all(g < 2e-2 for g in gaps.values()), gaps
    return {"shape": {k: cfg[k] for k in sorted(cfg)},
            "heads_a_grid_step": gdn._head_block(
                B * H, min(cfg["chunk"], S), cfg["dk"], cfg["dv"]),
            "documents": int(gdn.doc_starts(doc)),
            "matches": "the plain scan over chunks with bfloat16 operands "
                       "and with float32 ones at highest: relative gap of "
                       "o and of the three gradients < 2e-2",
            "gaps": {k: round(g, 5) for k, g in gaps.items()}, **ms}


def phase_w2v_scatter(cfg: dict, mesh, platform: str) -> dict:
    """The distinct-row writer (Mosaic on the chip) against XLA's
    ``.at[].add``: one step's lanes, ids by a zipf law, into a table held
    as the trainer holds it on one device."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.ops import distinct_rows, interpret_mode

    rng = np.random.default_rng(13)
    rows, cols = distinct_rows.aligned_shape(cfg["vocab"] + 1, cfg["dim"])
    ids_host = np.minimum(rng.zipf(cfg["zipf"], cfg["lanes"]) - 1,
                          cfg["vocab"] - 1).astype(np.int32)
    ids = jnp.asarray(ids_host)
    upd = jnp.asarray(rng.normal(size=(cfg["lanes"], cols)) * 1e-2,
                      jnp.float32)
    table = jax.random.normal(jax.random.PRNGKey(13), (rows, cols),
                              jnp.float32)
    want = jax.jit(lambda t: t.at[ids].add(upd))(table)
    got, distinct = jax.jit(lambda t: distinct_rows.add_rows(
        t, ids, lambda lanes: jnp.take(upd, lanes, axis=0),
        interpret=interpret_mode(mesh)), donate_argnums=0)(table)
    # the writer sorts and writes MAX_LANES lanes a kernel call
    step = distinct_rows.MAX_LANES
    written = sum(len(np.unique(ids_host[lo:lo + step]))
                  for lo in range(0, len(ids_host), step))
    assert int(distinct) == written, (int(distinct), written)
    # a row's duplicates are added in lane order, as the scatter adds
    # them: every row, touched or not, comes out with the same bits
    differing = int(jax.jit(lambda a, b: jnp.any(a != b, axis=1).sum())(
        got, want))
    assert differing == 0, differing
    return {"shape": {k: cfg[k] for k in sorted(cfg)},
            "table": [rows, cols],
            "distinct_rows": len(np.unique(ids_host)),
            "distinct_groups": len(np.unique(ids_host // 8)),
            "rows_written": written,
            "matches": ".at[].add in float32, bit for bit in every row"}


CHILD_PHASES = {"w2v": phase_w2v, "lda": phase_lda,
                "tables": phase_tables, "attend": phase_attend,
                "gdn_recur": phase_gdn_recur,
                "w2v_scatter": phase_w2v_scatter}


def run_child(phase: str, rehearse: bool) -> int:
    name, _, mesh_spec = phase.partition("@")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, HERE)
    t0 = time.perf_counter()
    import jax
    clock = _CompileClock()
    from multiverso_tpu import core
    mesh = core.init(**_mesh_arg(mesh_spec))
    dev = jax.devices()[0]
    line = {"phase": phase, "ok": False, "platform": dev.platform,
            "device_kind": dev.device_kind, "devices": len(jax.devices())}
    if rehearse:
        line["rehearsal"] = REHEARSAL_TAG
    elif dev.platform != "tpu":
        print(f"chip_smoke: phase {phase} found platform "
              f"{dev.platform!r}, not 'tpu' — no accelerator, no result",
              file=sys.stderr)
        return 3
    sizes = (TINY if rehearse else FULL)[name]
    line["checked"] = CHILD_PHASES[name](sizes, mesh, dev.platform)
    jax.effects_barrier()
    wall = time.perf_counter() - t0
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    line.update(
        ok=True, wall_s=round(wall, 2),
        compile_s=round(clock.seconds, 2),
        run_s=round(wall - clock.seconds, 2),
        cache_hits=clock.hits, cache_misses=clock.misses,
        peak_hbm_bytes=max((p for p in peaks if p), default=None))
    print(json.dumps(line), flush=True)
    return 0


# -- parent side: jax-free ---------------------------------------------------


def _load(name: str, *relpath: str):
    """File-path load of one jax-free client module (the package
    __init__ imports jax; this parent must not)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(PKG, *relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cache_dir() -> str:
    """Same rule as ``multiverso_tpu.core.compile_cache_dir`` (which the
    parent cannot import without importing jax)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(HERE, ".jax_cache")


def cache_entries() -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir())
                   if not n.endswith("-atime"))
    except OSError:
        return 0


class Smoke:
    def __init__(self, rehearse: bool) -> None:
        self.rehearse = rehearse
        self.t0 = time.monotonic()
        self.lines: list = []
        self.failed: list = []
        self.env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            [HERE, os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
        if rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"
        self.sizes = (TINY if rehearse else FULL)["server"]

    def left(self) -> float:
        return BUDGET_S - (time.monotonic() - self.t0)

    def emit(self, line: dict) -> None:
        if self.rehearse:
            line.setdefault("rehearsal", REHEARSAL_TAG)
        self.lines.append(line)
        print(json.dumps(line), flush=True)

    # -- trainer/table phases: a child holds the chip ----------------------

    def child(self, phase: str) -> None:
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
        if self.rehearse:
            cmd.append("--rehearse-cpu")
        proc = subprocess.Popen(cmd, env=self.env, cwd=HERE,
                                stdout=subprocess.PIPE, text=True)
        try:
            out, _ = proc.communicate(timeout=max(self.left(), 1.0))
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
        line = None
        for raw in out.splitlines():
            print(raw, flush=True)
            if raw.startswith("{"):
                try:
                    doc = json.loads(raw)
                except ValueError:
                    continue
                if doc.get("phase") == phase:
                    line = doc
        if proc.returncode != 0 or line is None or not line.get("ok"):
            self.failed.append(f"{phase} (rc={proc.returncode})")
            return
        self.lines.append(line)

    # -- serving phases: servers hold the chips, this parent is the
    # -- jax-free client ---------------------------------------------------

    def _requests(self, client, tag: str) -> dict:
        """The same requests for one server and for the fleet: a dense
        sgd array and a KV table, pipelined and sync adds, gets —
        compared with numpy bit-for-bit (integer-valued deltas and a
        power-of-two learning rate: every intermediate is exact in
        float32, so ANY correct lowering agrees to the bit)."""
        z = self.sizes
        rng = np.random.default_rng(zlib.crc32(tag.encode()))
        arr = client.create_array(f"{tag}_w", z["array"], updater="sgd")
        ref = np.zeros(z["array"], np.float32)
        opt = {"learning_rate": 0.5}
        for _ in range(6):                        # pipelined, unacked
            d = rng.integers(-8, 9, z["array"]).astype(np.float32)
            handle = arr.add(d, option=opt)
            ref -= np.float32(0.5) * d
        handle.wait()
        d = rng.integers(-8, 9, z["array"]).astype(np.float32)
        arr.add(d, option=opt, sync=True)
        ref -= np.float32(0.5) * d
        got = arr.get()
        assert got.dtype == np.float32 and got.tobytes() == ref.tobytes(), \
            f"{tag}: dense array differs from numpy"

        kv = client.create_kv(f"{tag}_kv", z["kv_capacity"],
                              value_dim=z["value_dim"])
        keys = rng.choice(np.arange(1, 1 << 20, dtype=np.uint64),
                          z["kv_batch"], replace=False)
        shape = (z["kv_batch"], z["value_dim"])
        d1 = rng.integers(-4, 5, shape).astype(np.float32)
        d2 = rng.integers(-4, 5, shape).astype(np.float32)
        kv.add(keys, d1)                          # pipelined
        kv.add(keys[::2], d2[::2], sync=True)     # matched-slot re-add
        expect = d1.copy()
        expect[::2] += d2[::2]
        probe = np.concatenate([keys, keys[:8] + np.uint64(1 << 30)])
        vals, found = kv.get(probe)
        assert found[:len(keys)].all() and not found[len(keys):].any(), \
            f"{tag}: kv found mask wrong"
        assert vals[:len(keys)].tobytes() == expect.tobytes(), \
            f"{tag}: kv values differ from numpy"
        assert not vals[len(keys):].any(), f"{tag}: missing keys not 0"
        return {"array": z["array"], "array_adds": 7,
                "kv_keys": int(z["kv_batch"]), "kv_adds": 2,
                "matches": "numpy, bit-for-bit"}

    def _check_status(self, status: dict) -> None:
        want = "cpu" if self.rehearse else "tpu"
        if status.get("platform") != want:
            raise AssertionError(
                f"server {status.get('name')!r} runs on "
                f"{status.get('platform')!r} "
                f"({status.get('device_kind')!r}), not {want!r}")

    def _wait_for(self, path: str, proc, what: str) -> None:
        deadline = time.monotonic() + min(300.0, max(self.left(), 1.0))
        while not os.path.exists(path):
            if proc.poll() is not None:
                raise RuntimeError(
                    f"{what} exited rc={proc.returncode} before ready")
            if time.monotonic() > deadline:
                raise TimeoutError(f"{what} not ready in time")
            time.sleep(0.05)

    def _server_cmd(self, *args: str) -> list:
        return [sys.executable, "-m", "multiverso_tpu.server", *args]

    def server(self) -> None:
        transport = _load("multiverso_tpu.client.transport",
                          "client", "transport.py")
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory() as tmp:
            ready = os.path.join(tmp, "ready")
            proc = subprocess.Popen(self._server_cmd(
                "--address", "unix:" + os.path.join(tmp, "smoke.sock"),
                "--name", "smoke", "--ready-file", ready),
                env=self.env, cwd=HERE)
            try:
                self._wait_for(ready, proc, "wire server")
                with open(ready) as f:
                    address = f.read().strip().split(",")[0]
                t_up = time.perf_counter() - t0
                with transport.connect(address, client="chip-smoke",
                                       quant=None) as client:
                    status = client.server_status()
                    self._check_status(status)
                    checked = self._requests(client, "one")
                    client.shutdown_server()
                rc = proc.wait(timeout=60)
                assert rc == 0, f"server exited rc={rc} after shutdown"
            finally:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
        checked["server_rc"] = 0
        if not self.rehearse:
            checked["one_too_many"] = self._refusal(
                len(status["devices"]) + 1)
        self.emit({"phase": "server", "ok": True,
                   "platform": status["platform"],
                   "device_kind": status["device_kind"],
                   "devices": len(status["devices"]),
                   "wall_s": round(time.perf_counter() - t0, 2),
                   "startup_s": round(t_up, 2), "checked": checked})

    def _refusal(self, n: int) -> str:
        """One member more than the host has chips is refused by the
        LAUNCHER, in words — not by libtpu in some child."""
        with tempfile.TemporaryDirectory() as tmp:
            proc = subprocess.run(self._server_cmd(
                "--fleet", str(n), "--address",
                "unix:" + os.path.join(tmp, "x.sock"), "--fleet-file",
                os.path.join(tmp, "x.json")), env=self.env, cwd=HERE,
                capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0 and "one process per chip" \
            in proc.stderr, (proc.returncode, proc.stderr[-400:])
        return f"--fleet {n} refused: " + proc.stderr.strip() \
            .splitlines()[-1][:160]

    def fleet(self, n: int) -> None:
        transport = _load("multiverso_tpu.client.transport",
                          "client", "transport.py")
        router = _load("multiverso_tpu.client.router",
                       "client", "router.py")
        t0 = time.perf_counter()
        members: list = []
        with tempfile.TemporaryDirectory() as tmp:
            fleet_file = os.path.join(tmp, "fleet.json")
            proc = subprocess.Popen(self._server_cmd(
                "--fleet", str(n), "--address",
                "unix:" + os.path.join(tmp, "fleet.sock"),
                "--name", "smokefleet", "--fleet-file", fleet_file),
                env=self.env, cwd=HERE)
            try:
                self._wait_for(fleet_file, proc, "fleet launcher")
                with open(fleet_file) as f:
                    members = json.load(f)["members"]
                t_up = time.perf_counter() - t0
                assert len(members) == n, members
                chips = [m.get("chip") for m in members]
                statuses = []
                for m in members:
                    with transport.connect(
                            m["addresses"][0], client="chip-smoke-probe",
                            quant=None) as c:
                        statuses.append(c.server_status())
                for st in statuses:
                    self._check_status(st)
                if not self.rehearse:
                    assert sorted(chips) == list(range(n)), \
                        f"members hold chips {chips}"
                    assert all(len(st["devices"]) == 1
                               for st in statuses), \
                        [st["devices"] for st in statuses]
                fc = router.connect_fleet_file(
                    fleet_file, client="chip-smoke", quant=None)
                checked = self._requests(fc, "fleet")
                fc.close()
                proc.send_signal(signal.SIGTERM)
                rc = proc.wait(timeout=90)
                assert rc == 0, f"fleet launcher exited rc={rc}"
            finally:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
                for m in members:
                    try:               # a killed launcher orphans members
                        os.kill(int(m["pid"]), signal.SIGKILL)
                    except (OSError, KeyError, TypeError, ValueError):
                        pass
        checked.update(members=n, chips=chips,
                       member_devices=[st["devices"] for st in statuses])
        self.emit({"phase": "fleet", "ok": True,
                   "platform": statuses[0]["platform"],
                   "device_kind": statuses[0]["device_kind"],
                   "devices": n,
                   "wall_s": round(time.perf_counter() - t0, 2),
                   "startup_s": round(t_up, 2), "checked": checked})

    def same_lda_state(self) -> None:
        """Which chip samples a block does not change what it samples:
        after two sweeps ``assignments()``, the word-topic table,
        ``doc_topics()`` and the summary of ``lda@2x2`` hash to what
        ``lda@1x1`` and ``lda`` (the default mesh) read."""
        shas = {ln["phase"]: ln["checked"]["sha256"] for ln in self.lines
                if ln.get("phase", "").partition("@")[0] == "lda"}
        for phase, sha in shas.items():
            print(f"chip_smoke: {phase} sha256 " + " ".join(
                f"{k}={v}" for k, v in sha.items()), flush=True)
        want = shas.get("lda@1x1")
        differ = [p for p, sha in shas.items() if sha != want]
        if want is None or "lda@2x2" not in shas or differ:
            self.failed.append(
                f"lda state differs between meshes: {differ or shas}")

    def guarded(self, name: str, fn, *args) -> None:
        """A serving phase's failure is recorded and printed — and makes
        the run fail — but the launcher's processes are already reaped
        (``finally`` above), so the next phase can still take the chip."""
        try:
            fn(*args)
        except Exception as exc:      # noqa: BLE001 — reported, fails run
            traceback.print_exc()
            self.failed.append(f"{name} ({type(exc).__name__}: {exc})")

    def run(self) -> int:
        before = cache_entries()
        print(f"chip_smoke: compile cache {cache_dir()} — "
              f"{before} entries before"
              + (f" [rehearsal: {REHEARSAL_TAG}]" if self.rehearse
                 else ""), flush=True)
        self.child("w2v")
        if not self.lines:
            # the first child decides whether there is an accelerator
            # at all; without one nothing below can pass either
            print("chip_smoke: FAILED — " + "; ".join(self.failed),
                  file=sys.stderr)
            return 1
        for phase in ("lda", "tables", "attend", "gdn_recur",
                      "w2v_scatter"):
            self.child(phase)
        self.guarded("server", self.server)
        count = int(self.lines[0]["devices"])
        if count >= 4:
            for phase in ("w2v@2x2", "lda@1x1", "lda@2x2", "tables@2x2"):
                self.child(phase)
            self.same_lda_state()
            self.guarded("fleet", self.fleet, 4)
        else:
            self.emit({"phase": "four_chips",
                       "skipped": f"{count} device(s)"})
        after = cache_entries()
        total_compile = sum(l.get("compile_s", 0.0) for l in self.lines)
        print(f"chip_smoke: compile cache {cache_dir()} — {after} "
              f"entries after ({after - before:+d}); compile "
              f"{total_compile:.1f}s of "
              f"{time.monotonic() - self.t0:.1f}s", flush=True)
        if self.failed:
            print("chip_smoke: FAILED — " + "; ".join(self.failed),
                  file=sys.stderr)
            return 1
        first = self.lines[0]
        result = {"ok": True, "device": {
            "platform": first["platform"], "kind": first["device_kind"],
            "count": count}}
        if self.rehearse:
            print(json.dumps({"rehearsal": REHEARSAL_TAG, **result}),
                  flush=True)
        else:
            print(json.dumps(result), flush=True)
        return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny sizes on virtual CPU devices; never "
                             "the default, never a chip result")
    parser.add_argument("--phase", default=None,
                        help="internal: run ONE phase in this process "
                             "(it will hold the chip)")
    args = parser.parse_args(argv)
    if not __debug__:
        print("chip_smoke: its checks are assert statements — run it "
              "without -O", file=sys.stderr)
        return 2
    if args.phase:
        return run_child(args.phase, args.rehearse_cpu)
    if not os.path.isdir(PKG):
        print(f"chip_smoke: no multiverso_tpu package next to "
              f"{os.path.abspath(__file__)} — nothing to run",
              file=sys.stderr)
        return 2
    return Smoke(args.rehearse_cpu).run()


if __name__ == "__main__":
    sys.exit(main())
