"""Bytes and operations the ALGORITHM of each timed path needs, from its
shapes alone — never what an implementation happens to move, so a share
reads the same work whatever implements it and cannot pass 100 %.

Each model takes the configuration's ``program`` sizes and the counts of
work done in the window (handed over by the driver) and returns
``{"bytes": ..., "flops": ...}``. Sources: ``benchmarks/roofline.py``
(``lda_utilization``, ``w2v_utilization``) and
``benchmarks/table_kernels.py`` (``kv_bytes_per_op_model``), copied.
"""


def lda_sampler(sizes: dict, work: dict) -> dict:
    """The doc-blocked sampler's calls alone, per token: one word-topic
    row of K counts at the 2 bytes the stale mirror holds them in, the
    token's assignment read and written (8), its packed stream entry (8),
    and its share of the block's doc-topic counts read and written
    (MAXD rows of K int16 in and out a block: 64*K/block_tokens).
    Operations per token and topic: the posterior's two adds and two
    multiplies and the CDF's add and compare."""
    k = float(sizes["num_topics"])
    tokens = float(work["tokens"])
    per_token = 2.0 * k + 8.0 + 8.0 + 64.0 * k / sizes["block_tokens"]
    return {"bytes": tokens * per_token, "flops": tokens * 6.0 * k}


def lda_sweep(sizes: dict, work: dict) -> dict:
    """Whole sweeps: the sampler's work plus, once a sweep, the rebuild
    of the int32 word-topic master from the assignments and the rewrite
    of its bf16 mirror (6 bytes an entry of [V, K])."""
    s = lda_sampler(sizes, work)
    rebuild = 6.0 * sizes["vocab_size"] * sizes["num_topics"] \
        * float(work["sweeps"])
    return {"bytes": s["bytes"] + rebuild, "flops": s["flops"]}


def w2v_pairs(sizes: dict, work: dict) -> dict:
    """Skip-gram with negative sampling, per (centre, context) pair:
    2 + negative embedding rows of 4*D bytes, each gathered and
    scatter-added back (read, then read-modify-write: 3 passes); forward
    logits, d_src and d_tgt are 2*(1+negative)*D operations each."""
    d = float(sizes["embedding_dim"])
    n = float(sizes["negative"])
    pairs = float(work["pairs"])
    return {"bytes": pairs * 3.0 * (2.0 + n) * 4.0 * d,
            "flops": pairs * 6.0 * (1.0 + n) * d}


def kv_probe_commit(sizes: dict, work: dict) -> dict:
    """KV lookups and updates, per key: a get reads the slot's key (8)
    and its value; an add reads key, value and updater state and writes
    value and state back. Hashing and comparing are a handful of integer
    operations a key, counted as 16."""
    vb = 4.0 * max(int(sizes.get("value_dim", 0)), 1)
    sb = float(sizes.get("state_bytes", 0))
    gets = float(work.get("get_keys", 0))
    adds = float(work.get("add_keys", 0))
    return {"bytes": gets * (8.0 + vb) + adds * (8.0 + 2 * vb + 2 * sb),
            "flops": 16.0 * (gets + adds)}


MODELS = {f.__name__: f for f in
          (lda_sampler, lda_sweep, w2v_pairs, kv_probe_commit)}
