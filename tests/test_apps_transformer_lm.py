"""The language-model trainer (``apps/transformer_lm.py``) against the
plain float32 reference (``perf/reference/dsv2.py``) on seeded weights
at tiny widths, its expert layer's share of a layer, document packing,
and the spans, scopes and counters it records."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from multiverso_tpu import core, telemetry                     # noqa: E402
from multiverso_tpu.apps.transformer_lm import (               # noqa: E402
    LMConfig, TransformerLM, named_parameters, table_layout, table_shapes)
from multiverso_tpu.data.packing import pack_documents, real_tokens  # noqa: E402
from multiverso_tpu.ops import latent_attention as mla         # noqa: E402
from multiverso_tpu.ops import moe                             # noqa: E402
from perf.reference import dsv2 as ref                         # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 64,
        "type": "yarn"}
# the catalog's config of DeepSeek-V2-Lite (model-configs guide, row 12)
PUBLISHED = {
    "first_k_dense_replace": 1, "hidden_size": 2048,
    "intermediate_size": 10944, "kv_lora_rank": 512,
    "moe_intermediate_size": 1408, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "routed_scaling_factor": 1, "scoring_func": "softmax",
    "v_head_dim": 128, "vocab_size": 102400,
    "rope_scaling": dict(YARN, original_max_position_embeddings=4096)}


def tiny(**over) -> LMConfig:
    base = dict(num_hidden_layers=3, n_routed_experts=2, ep_size=4,
                ep_rank=1, num_experts_per_tok=3, rope_scaling=YARN,
                sequences=4, sequence_length=64, attention_block=16,
                expert_chunk_rows=32, mlp_chunks=2, head_chunks=2,
                seed=2147483659, init_std=0.05, learning_rate=1e-2,
                warmup_steps=4, compute_dtype="float32")
    return LMConfig(**dict(base, **over))


def reference_config(c: LMConfig) -> dict:
    """The reference counts ALL the router's outputs as its experts."""
    return dict(dataclasses.asdict(c), n_routed_experts=c.router_width)


def documents(c: LMConfig, n=200, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, c.vocab_size, rng.integers(3, 50))
            for _ in range(n)]


def start_tables(c: LMConfig) -> dict:
    return {n: np.ones(s, np.float32) if n == "norms" else np.asarray(
        ref.init_normal(c.seed, i, s, c.init_std))
        for i, (n, s) in enumerate(table_shapes(c).items())}


def as_tables(c: LMConfig, by_role: dict) -> dict:
    """Tensors by published role laid back into the tables' shapes."""
    tables = {n: np.zeros(s, np.float32)
              for n, s in table_shapes(c).items()}
    views = named_parameters(c, tables)
    for role, view in views.items():
        view[...] = np.asarray(by_role[role])
    return tables


def gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


@pytest.fixture(scope="module")
def mesh():
    return core.init(devices=jax.devices()[:1], data_parallel=1,
                     model_parallel=1)


@pytest.fixture(scope="module")
def trained(mesh):
    """A float32 trainer after three steps, beside the reference's three
    steps from the same start on the same packed batches."""
    c = tiny()
    docs = documents(c)
    app = TransformerLM(c, docs, mesh=mesh)
    start = {n: np.asarray(t.raw())[:s[0]] for (n, t), s in
             zip(app.tables.items(), table_shapes(c).values())}
    batches = list(pack_documents(docs, c.sequences, c.sequence_length))
    first = app.gradients(batches[0])
    # the registry is the process's: what THIS call counted is a growth
    before = telemetry.snapshot()["counters"]
    app.train(total_steps=3)
    counted = {k: v - before.get(k, 0)
               for k, v in telemetry.snapshot()["counters"].items()}
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    m, v = ref.zeros_like(p), ref.zeros_like(p)
    steps = []
    for s in range(3):
        ce, balance, g, aux = ref.loss_and_grads(p, batches[s],
                                                 reference_config(c))
        steps.append((ce, balance, {k: np.asarray(x)
                                    for k, x in g.items()}, aux))
        # linear warm-up over four steps, written out
        p, m, v = ref.adam_step(p, m, v, g, s,
                                lr=c.learning_rate * (s + 1) / 4,
                                b1=c.beta1, b2=c.beta2, eps=c.adam_eps)
    return {"config": c, "app": app, "start": start, "first": first,
            "batches": batches, "steps": steps, "counters": counted,
            "final": {k: np.asarray(x) for k, x in p.items()}}


def test_start_values_are_the_reference_s(trained):
    c = trained["config"]
    want = start_tables(c)
    for name, got in trained["start"].items():
        # one float32 rounding apart at most (a fused multiply)
        np.testing.assert_allclose(got, want[name], rtol=1e-5, atol=1e-8)


def test_the_embedding_rows_may_start_from_a_width_of_their_own(mesh):
    """``embed_init_std`` scales the embedding's draw alone; the other
    tables keep ``init_std`` and the same draw."""
    c = tiny(embed_init_std=1.0, num_hidden_layers=2)
    app = TransformerLM(c, mesh=mesh)
    for index, (name, shape) in enumerate(table_shapes(c).items()):
        got = np.asarray(app.tables[name].raw())[:shape[0]]
        want = np.ones(shape, np.float32) if name == "norms" else \
            np.asarray(ref.init_normal(c.seed, index, shape,
                                       1.0 if name == "embed" else 0.05))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-8)
    assert np.std(np.asarray(app.tables["embed"].raw())[:c.vocab_size]) \
        == pytest.approx(1.0, rel=0.05)


def test_losses_of_three_steps_match_the_reference(trained):
    for (ce, balance), (want_ce, want_balance, _, _) in zip(
            trained["app"].loss_history, trained["steps"]):
        assert ce == pytest.approx(want_ce, rel=2e-6)
        assert balance == pytest.approx(want_balance, rel=2e-6)


def test_every_table_s_gradient_matches_the_reference(trained):
    c = trained["config"]
    aux, grads = trained["first"]
    want = as_tables(c, trained["steps"][0][2])
    assert set(grads) == set(want)
    for name in want:
        got = np.asarray(grads[name])[:want[name].shape[0]]
        assert gap(got, want[name]) < 2e-5, name
    # the step's own aux: norms of the same gradients, and the probes
    step = jax.device_get(trained["app"].aux_tail[0])
    for name, norm in zip(want, step["grad_norms"]):
        assert norm == pytest.approx(np.linalg.norm(want[name]), rel=1e-5)
    assert gap(step["probe_embed"], want["embed"][:256]) < 2e-5
    assert gap(step["probe_expert"], want["l1.experts"][0]) < 2e-5


def test_tables_after_three_adam_steps_match_the_reference(trained):
    c = trained["config"]
    start = named_parameters(c, trained["start"])
    got = named_parameters(c, {
        n: np.asarray(t.raw()) for n, t in trained["app"].tables.items()})
    for role, want in trained["final"].items():
        # Adam's first steps are near +-lr an entry: the change is what
        # is compared, not the table (which the start dominates)
        assert gap(got[role] - start[role], want - start[role]) < 1e-3, role
    assert all(t.default_option.step == 3
               for t in trained["app"].tables.values())


def test_routing_is_exact_and_nothing_is_dropped(trained):
    c = trained["config"]
    step = jax.device_get(trained["app"].aux_tail[0])
    want = trained["steps"][0][3]
    np.testing.assert_array_equal(step["counts"], want["counts"])
    np.testing.assert_array_equal(np.sort(step["chosen"], -1),
                                  np.sort(want["chosen"], -1))
    held = step["counts"][:, c.first_expert:
                          c.first_expert + c.n_routed_experts]
    assert step["moe"].tolist() == [int(held.sum()), 0]
    # a recount from the routing the step returned, padding left out
    real = (trained["batches"][0]["doc"] > 0).reshape(-1)
    for layer, chosen in enumerate(step["chosen"]):
        recount = np.bincount(chosen[real].reshape(-1),
                              minlength=c.router_width)
        np.testing.assert_array_equal(recount, step["counts"][layer])


def test_the_reference_s_layer_at_a_time_gradient_is_the_objective_s(trained):
    """The reference goes a sequence and a layer at a time and chains
    the layers' vector-Jacobian products by hand; one ``jax.grad`` over
    the whole step's objective gives the same loss and gradients."""
    c = trained["config"]
    cfg = reference_config(c)
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    tokens, doc, pos = (jnp.asarray(trained["batches"][0][k])
                        for k in ("tokens", "doc", "pos"))
    B = tokens.shape[0]
    n_pred = jnp.sum((doc[:, 1:] == doc[:, :-1]) & (doc[:, :-1] > 0))

    def objective(p):
        total = 0.0
        for b in range(B):
            x = p["embed"][tokens[b]]
            for i in range(c.num_hidden_layers):
                layer = {k[len(f"l{i}."):]: v for k, v in p.items()
                         if k.startswith(f"l{i}.")}
                (x, balance), _ = ref.layer(layer, x, doc[b], pos[b], cfg)
                total += balance / B
            total += ref.head_loss(x, p["final_norm"], p["head"],
                                   tokens[b], doc[b], cfg) / n_pred
        return total

    want_ce, want_balance, got, _ = trained["steps"][0]
    with jax.default_matmul_precision("highest"):
        loss, want = jax.value_and_grad(objective)(p)
    assert float(loss) == pytest.approx(want_ce + want_balance, rel=1e-6)
    assert set(got) == set(want)
    for role, g in want.items():        # another order of the same sums
        np.testing.assert_allclose(got[role], g, rtol=1e-4, atol=1e-7)


def test_bfloat16_products_stay_near_the_reference(mesh, trained):
    c = dataclasses.replace(trained["config"], compute_dtype="bfloat16")
    app = TransformerLM(c, mesh=mesh)
    aux, grads = app.gradients(trained["batches"][0])
    want_ce, want_balance, want, _ = trained["steps"][0]
    assert float(aux["ce"]) == pytest.approx(want_ce, rel=1e-3)
    assert float(aux["balance"]) == pytest.approx(want_balance, rel=1e-2)
    want = as_tables(c, want)
    for name in want:
        got = np.asarray(grads[name])[:want[name].shape[0]]
        assert 1e-4 < gap(got, want[name]) < 0.15, name   # a flipped choice
        # moves a whole token between experts


def test_changing_one_document_leaves_another_s_states_bit_equal(trained):
    app, batch = trained["app"], trained["batches"][1]
    other = {k: v.copy() for k, v in batch.items()}
    changed = (batch["doc"] == 2)
    other["tokens"][changed] = (other["tokens"][changed] + 7) \
        % trained["config"].vocab_size
    a = np.asarray(app.hidden_states(batch))
    b = np.asarray(app.hidden_states(other))
    untouched = (batch["doc"] > 0) & ~changed
    assert changed.any() and untouched.any()
    assert np.array_equal(a[untouched], b[untouched])
    assert not np.array_equal(a[changed], b[changed])


def plain_attention(q_nope, q_pe, k_nope, k_pe, v, doc, scale):
    """The oracle: one [S, S] score matrix a head, masked causal and by
    document, float32 softmax, the operands' dtype in the products."""
    B, S = doc.shape
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe,
                           preferred_element_type=jnp.float32)) * scale
    t = jnp.arange(S)
    allowed = (t[:, None] >= t[None]) & (doc[:, :, None] == doc[:, None])
    prob = jax.nn.softmax(jnp.where(allowed[:, None], scores, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", prob.astype(v.dtype), v,
                      preferred_element_type=jnp.float32
                      ).astype(v.dtype).reshape(B, S, -1)


def attention_operands(B=2, S=64, H=4, nope=16, rope=8, vd=16, seed=3,
                       dtype=jnp.float32):
    """``q_nope, q_pe, k_nope, k_pe, v``: ``k_pe`` is every head's."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    return (draw(B, S, H, nope), draw(B, S, H, rope), draw(B, S, H, nope),
            draw(B, S, rope), draw(B, S, H, vd))


def doc_ids(*lengths, S=64):
    """One sequence's ``doc`` ids: documents of ``lengths`` from id 1,
    then padding (0)."""
    ids = np.repeat(np.arange(1, len(lengths) + 1), lengths)
    return np.concatenate([ids, np.zeros(S - len(ids), np.int64)])


# two sequences each: [four documents of 16, one of 64]; a boundary on
# the edge of a block of 8 / 16 / 32 and one off every edge; a padded
# tail that starts inside a block, and a sequence that is all padding
DOCS = {
    "four_and_one": np.stack([doc_ids(16, 16, 16, 16), doc_ids(64)]),
    "boundaries_on_and_off_a_block_edge": np.stack(
        [doc_ids(32, 32), doc_ids(21, 30, 13)]),
    "padding_slots": np.stack([doc_ids(20, 21, 9), doc_ids()]),
    "one_document_fills_the_sequence": np.stack([doc_ids(64), doc_ids(64)]),
}


@pytest.mark.parametrize("block", [8, 32, 64])
def test_blocked_attention_equals_the_unblocked_form(block):
    """The kernel (interpreted here) at query blocks 8, 32 and S against
    the plain [S, S] form."""
    operands = attention_operands()
    doc = jnp.asarray(DOCS["four_and_one"])
    got = mla.attend(*operands, doc, scale=0.2, block=block)
    np.testing.assert_allclose(got, plain_attention(*operands, doc, 0.2),
                               rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("docs", sorted(DOCS))
def test_attention_and_its_five_gradients_equal_the_plain_forms(docs):
    """Output and the gradient of every operand, written by hand in the
    kernel's backward pass, against ``jax.grad`` of the plain form."""
    operands = attention_operands(seed=5)
    doc = jnp.asarray(DOCS[docs])
    weight = attention_operands(seed=6)[0].reshape(2, 64, -1)

    def loss(attention):
        return lambda *a: jnp.sum(attention(*a) * weight)

    ours = lambda *a: mla.attend(*a, doc, scale=0.3, block=16)
    plain = lambda *a: plain_attention(*a, doc, 0.3)
    np.testing.assert_allclose(ours(*operands), plain(*operands),
                               rtol=1e-5, atol=2e-6)
    got = jax.grad(loss(ours), argnums=(0, 1, 2, 3, 4))(*operands)
    want = jax.grad(loss(plain), argnums=(0, 1, 2, 3, 4))(*operands)
    for name, g, w in zip(("q_nope", "q_pe", "k_nope", "k_pe", "v"),
                          got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5,
                                   err_msg=name)


def test_attention_at_the_published_head_dims_in_bfloat16():
    """Score depth 128 + 64 with the rotary key shared by the heads,
    value depth 128, bfloat16 operands: the kernel against the plain
    form in the same precision, forward and backward."""
    operands = attention_operands(B=1, S=64, H=2, nope=128, rope=64,
                                  vd=128, seed=7, dtype=jnp.bfloat16)
    doc = jnp.asarray(DOCS["boundaries_on_and_off_a_block_edge"][1:])
    scale = 192 ** -0.5
    ours = lambda *a: mla.attend(*a, doc, scale=scale, block=32)
    plain = lambda *a: plain_attention(*a, doc, scale)
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(ours(*operands)), f32(plain(*operands)),
                               rtol=2e-2, atol=2e-2)
    loss = lambda f: lambda *a: jnp.sum(f(*a).astype(jnp.float32))
    got = jax.grad(loss(ours), argnums=(0, 1, 2, 3, 4))(*operands)
    want = jax.grad(loss(plain), argnums=(0, 1, 2, 3, 4))(*operands)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16
        gap = np.linalg.norm(f32(g) - f32(w)) / np.linalg.norm(f32(w))
        assert gap < 2e-2, gap


@pytest.mark.parametrize("docs", sorted(DOCS))
def test_the_blocks_the_kernel_skips_are_a_count_of_the_doc_ids(docs):
    """A block pair is computed iff some query of it may see some key —
    or the two blocks' ranges of ids overlap, where ids are not sorted
    (a padded tail that starts inside a block)."""
    doc, block = DOCS[docs], 8
    n = doc.shape[1] // block
    t = np.arange(doc.shape[1])
    allowed = (t[:, None] >= t[None]) & (doc[:, :, None] == doc[:, None])
    some = allowed.reshape(len(doc), n, block, n, block).any((2, 4))
    lo = doc.reshape(len(doc), n, block).min(-1)
    hi = doc.reshape(len(doc), n, block).max(-1)
    overlap = (lo[:, None] <= hi[:, :, None]) & (hi[:, None] >= lo[:, :, None])
    under = np.tril(np.ones((n, n), bool))
    plan = np.asarray(mla.block_plan(jnp.asarray(doc), block)) > 0
    assert np.array_equal(plan, under & overlap)
    assert not (some & ~plan).any()         # nothing allowed is skipped
    # sorted ids: exactly the pairs that hold an allowed (query, key)
    tidy = (np.diff(doc, axis=1) >= 0).all(1)
    assert np.array_equal(plan[tidy], some[tidy])
    total, computed = np.asarray(mla.key_blocks(jnp.asarray(doc), block))
    assert total == len(doc) * under.sum()
    assert computed == (under & overlap).sum()
    if docs == "four_and_one":
        # 4 documents of 2 blocks: 3 pairs each; one of 8 blocks: 36
        assert (total, computed) == (72, 12 + 36)


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide 4: the routed parts of all ``ep_size`` shares plus the
    shared experts counted once are the uncut reference layer."""
    c = tiny(ep_size=4, n_routed_experts=2)
    E, D, F = c.router_width, c.hidden_size, c.moe_intermediate_size
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 32, D)), jnp.float32)
    p = {"ffn_norm": jnp.ones(D),
         "router": jnp.asarray(rng.normal(size=(D, E)), jnp.float32)}
    for part in ("gate", "up", "down"):
        p[f"exp_{part}"] = jnp.asarray(
            rng.normal(size=(E, D, F)) * 0.1, jnp.float32)
        p[f"shared_{part}"] = jnp.asarray(
            rng.normal(size=(D, 2 * F)) * 0.1, jnp.float32)
    uncut = dict(reference_config(c), ep_size=1, ep_rank=0)
    real = jnp.ones((2, 32))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.expert_layer(p, x[b], real[b], uncut)[0]
                          for b in range(2)])
        shared = jnp.stack([ref.swiglu(
            ref.rms_norm(x[b], p["ffn_norm"], c.rms_norm_eps),
            p["shared_gate"], p["shared_up"], p["shared_down"])
            for b in range(2)])
    h = mla.rms_norm(x, p["ffn_norm"], c.rms_norm_eps)
    routing = moe.route(h, p["router"], real,
                        top_k=c.num_experts_per_tok, norm_topk_prob=False,
                        scaling=1.0, alpha=c.aux_loss_alpha)
    total, rows = shared, 0
    for rank in range(c.ep_size):
        first = rank * c.n_routed_experts
        mine = slice(first, first + c.n_routed_experts)
        plan = moe.plan(routing.top_e, real, first=first,
                        held=c.n_routed_experts, chunk_rows=32)
        w = jnp.stack([p["exp_gate"][mine], p["exp_up"][mine],
                       p["exp_down"][mine]], axis=1)
        y, done = moe.routed_experts(
            h.reshape(-1, D), w, jnp.take(routing.top_s.reshape(-1),
                                          plan.row_src),
            plan, 32, jnp.dtype("float32"))
        total = total + y.reshape(x.shape)
        rows += int(done)
    assert rows == 2 * 32 * c.num_experts_per_tok   # every assignment once
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("chunk_rows", [16, 64, 4096])
def test_a_skewed_router_drops_no_token(chunk_rows):
    """Most tokens choose ONE expert held here: every assignment goes
    through, the counts are exact and the result is the dense one."""
    rng = np.random.default_rng(7)
    T, D, F, E, held, k = 96, 16, 8, 8, 2, 2
    h = jnp.asarray(rng.normal(size=(1, T, D)) + 1.0, jnp.float32)
    router = rng.normal(size=(D, E)) * 0.01
    router[:, 2] += 1.0                      # expert 2: held, rank 1
    real = jnp.asarray((np.arange(T) % 7 != 0).astype(np.float32))[None]
    routing = moe.route(h, jnp.asarray(router, jnp.float32), real, top_k=k,
                        norm_topk_prob=False, scaling=1.0, alpha=0.001)
    counts = np.asarray(routing.counts)
    assert counts[2] == int(real.sum()) and counts.sum() == k * counts[2]
    plan = moe.plan(routing.top_e, real, first=2, held=held,
                    chunk_rows=chunk_rows)
    w = jnp.asarray(rng.normal(size=(held, 3, D, F)) * 0.3, jnp.float32)

    def layer(h, w, top_s):
        return moe.routed_experts(
            h.reshape(T, D), w, jnp.take(top_s.reshape(-1), plan.row_src),
            plan, chunk_rows, jnp.dtype("float32"))

    def dense(h, w, top_s):
        y = jnp.zeros((T, D))
        for e in range(held):
            weight = jnp.sum(jnp.where(
                (routing.top_e == 2 + e) & (real.reshape(T, 1) > 0),
                top_s, 0.0), 1)
            y += weight[:, None] * ref.swiglu(
                h.reshape(T, D), w[e, 0], w[e, 1], w[e, 2])
        return y

    (y, rows) = layer(h, w, routing.top_s)
    assert int(rows) == counts[2:4].sum()
    np.testing.assert_allclose(y, dense(h, w, routing.top_s), rtol=1e-5,
                               atol=1e-6)
    pick = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(layer(*a)[0] * pick), (0, 1, 2))(
        h, w, routing.top_s)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * pick), (0, 1, 2))(
        h, w, routing.top_s)
    for g, wnt in zip(got, want):
        np.testing.assert_allclose(g, wnt, rtol=2e-4, atol=2e-5)


def test_packing_keeps_documents_whole_and_restarts_positions():
    rng = np.random.default_rng(11)
    docs = [rng.integers(1, 100, n) for n in
            (30, 50, 10, 64, 5, 40, 20, 90, 33, 7, 64, 1, 12)]
    docs += [np.arange(1, 65)] * 4            # so that the last steps fill
    steps = list(pack_documents(docs, 2, 64, open_sequences=2))
    seen = []
    for step in steps:
        assert set(step) == {"tokens", "doc", "pos"}
        for b in range(2):
            doc, pos, tok = step["doc"][b], step["pos"][b], step["tokens"][b]
            ids = [d for d in np.unique(doc) if d > 0]
            assert ids == list(range(1, len(ids) + 1))
            for d in ids:
                where = np.flatnonzero(doc == d)
                assert np.array_equal(where, np.arange(where[0],
                                                       where[-1] + 1))
                assert np.array_equal(pos[where], np.arange(len(where)))
                seen.append(tok[where])
            pad = doc == 0
            assert not tok[pad].any() and not pos[pad].any()
            assert not pad[:np.count_nonzero(~pad)].any()    # a tail
        assert real_tokens(step) == np.count_nonzero(step["doc"])
    # first fit, in arrival order: 30 and 50 open the two sequences, 10
    # joins the first, 64 fits neither and closes the fuller one (the
    # 50) to take its place; 5 joins the first, 40 fits neither and
    # closes the 64: the first step
    assert [len(s) for s in seen[:2]] == [50, 64]
    cut = [d[:64] for d in docs]
    arrived = sorted(map(bytes, (np.asarray(d, np.int32) for d in cut)))
    packed = sorted(map(bytes, seen))
    assert all(p in arrived for p in packed)


def test_more_open_sequences_leave_less_padding():
    """First fit over a pool: the fullest sequence closes when a
    document fits none, so a wider pool pads less; every document
    arrives once whatever the pool."""
    rng = np.random.default_rng(3)
    lens = np.clip(np.rint(np.exp(rng.normal(np.log(40), 1.0, 3000))),
                   2, 256).astype(int)
    docs = [np.full(n, i + 1, np.int32) for i, n in enumerate(lens)]
    pads = {}
    for pool in (4, 16, None):                  # None: 4 x sequences
        steps = list(pack_documents(docs, 4, 256, open_sequences=pool))
        slots = len(steps) * 4 * 256
        pads[pool] = 1 - sum(map(real_tokens, steps)) / slots
        seen = [t for step in steps for row in step["tokens"]
                for t in np.unique(row[row > 0])]
        assert len(seen) == len(set(seen))      # no document twice
    assert pads[16] == pads[None] < pads[4] < 0.1
    assert pads[16] < 0.02


def test_the_learning_rate_warms_up_linearly_then_stays():
    c = tiny(learning_rate=4.2e-4, warmup_steps=2000)
    assert c.learning_rate_at(0) == pytest.approx(2.1e-7)
    assert c.learning_rate_at(999) == pytest.approx(2.1e-4)
    assert c.learning_rate_at(1999) == c.learning_rate_at(10 ** 6) == 4.2e-4
    assert tiny(warmup_steps=1).learning_rate_at(0) == 1e-2    # no warm-up
    with pytest.raises(ValueError, match="warmup_steps"):
        tiny(warmup_steps=0).check()


def test_the_published_config_has_the_issue_s_parameter_counts():
    counts = ref.parameter_counts(PUBLISHED)
    assert counts["attention"] == 13_763_072           # 13.76M
    assert counts["shared_experts"] == 17_301_504      # 17.30M
    assert counts["routed_expert"] == 8_650_752        # 8.65M
    assert counts["dense_layer"] == 81_003_008         # 81.0M
    assert counts["outside_experts"] == 31_195_648     # 31.2M a layer
    # the same from the program's tables, as the chip's share holds them
    held = dict(PUBLISHED, num_hidden_layers=6, n_routed_experts=8,
                vocab_size=12800, ep_size=8, vocab_shard=8)
    c = LMConfig.from_dict(held)
    c.check()
    shapes = table_shapes(c)
    size = lambda n: int(np.prod(shapes[n]))
    assert size("l1.attn") + size("l1.kv_b") + c.kv_lora_rank \
        == counts["attention"]
    assert size("l1.shared") == counts["shared_experts"]
    assert size("l1.experts") == 8 * counts["routed_expert"]
    assert size("l1.router") == counts["router"] == 2048 * 64
    assert size("l0.mlp") + counts["attention"] == counts["dense_layer"]
    assert size("embed") + size("head") == 2 * 12800 * 2048
    assert len(shapes) == 31
    layout = table_layout(c)
    assert list(layout) == list(shapes)
    assert sum(map(len, layout.values())) == 3 + 6 * 7 + 3 + 5 * 7


def test_spans_scopes_and_counters_of_a_training_call(trained):
    snap = telemetry.snapshot()
    spans = {k for k in snap["histograms"] if k.startswith("span.seconds")}
    for name in ("lm.wait_data", "lm.place", "lm.superstep", "lm.fence",
                 "lm.setup.init_tables", "lm.docs.produce"):
        assert f"span.seconds{{name={name}}}" in spans, name
    counters = trained["counters"]
    batches = trained["batches"][:3]
    assert counters["lm.tokens"] == sum(real_tokens(b) for b in batches)
    assert counters["lm.pad_tokens"] == sum(b["doc"].size for b in batches) \
        - counters["lm.tokens"]
    assert counters["moe.tokens_dropped"] == 0
    assert counters["moe.tokens_routed"] > 0
    # block pairs of every layer's attention: 4 sequences of 4 blocks
    c = trained["config"]
    plans = [np.asarray(mla.block_plan(jnp.asarray(b["doc"]), 16))
             for b in batches]
    assert counters["lm.attend.key_blocks"] \
        == c.num_hidden_layers * len(batches) * 4 * 10
    assert counters["lm.attend.key_blocks_computed"] \
        == c.num_hidden_layers * sum(p.sum() for p in plans)
    assert 0 < counters["lm.attend.key_blocks_computed"] \
        < counters["lm.attend.key_blocks"]
    assert snap["gauges"]["moe.expert_load_max_over_mean"] >= 1.0
    held = telemetry.op_scopes()["superstep.lm_superstep"]
    assert held["module"] == "jit_run"
    named = set(held["scopes"].values())
    assert {"lm.embed_gather", "lm.embed_scatter", "lm.mla.project",
            "lm.mla.attend", "lm.dense_mlp", "lm.moe.route",
            "lm.moe.permute", "lm.moe.experts", "lm.moe.shared",
            "lm.head_loss", "lm.adam"} <= named


def test_the_step_names_what_lies_between_its_phases(trained):
    """The expert loops' accumulators and cast, the feed-forward's norm
    and a block's glue carry scopes of their own (PR 36)."""
    held = telemetry.op_scopes()["superstep.lm_superstep"]
    named = set(held["scopes"].values())
    assert {"lm.moe.accumulate", "lm.block_norm", "lm.residual"} <= named
    # the map says which names are not an op's own: fusions named by
    # their body (1 or more scopes in it) or by their operands (0)
    assert set(held) == {"module", "scopes", "inferred"}
    for name, n in held["inferred"].items():
        assert held["scopes"][name] != "unscoped" and n >= 0


def test_what_is_not_built_says_so(mesh):
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        tiny(q_lora_rank=8).check()
    with pytest.raises(NotImplementedError, match="latent"):
        tiny(kv_lora_rank=None).check()
    with pytest.raises(ValueError, match="ep_rank"):
        tiny(ep_rank=4).check()
    # published keys that change the model and are built one way only:
    # from_dict would drop them and train another model under the name
    for key, value, says in (
            ("attention_bias", True, "attention_bias"),
            ("hidden_act", "gelu", "hidden_act"),
            ("scoring_func", "sigmoid", "scoring_func"),
            ("model_type", "lfm2_moe", "latent attention in a"),
            ("model_type", "llama", "model_type"),
            ("layer_types", ["full_attention", "sliding_attention"] * 14,
             "layer_types entry"),
            ("model_type", "olmo_hybrid", "latent attention in a")):
        published = dict(PUBLISHED, **{key: value})
        if key == "layer_types":
            published["model_type"] = "olmo_hybrid"
        with pytest.raises(NotImplementedError, match=says):
            LMConfig.from_dict(published).check()
    LMConfig.from_dict(PUBLISHED).check()
    # a config that names no experts and no latent rank has neither
    bare = {k: v for k, v in PUBLISHED.items()
            if k not in ("n_routed_experts", "kv_lora_rank")}
    assert LMConfig.from_dict(bare).n_routed_experts == 0
    with pytest.raises(NotImplementedError, match="names no mixer"):
        LMConfig.from_dict(bare).check()
    with pytest.raises(ValueError, match="no documents"):
        TransformerLM(tiny(num_hidden_layers=1), mesh=mesh).train(1)
