"""The hybrid decoder — gated delta-rule linear attention beside full
attention, the Olmo family's reordered norms — through
``apps/transformer_lm.py`` against the plain float32 reference
(``perf/reference/olmo_hybrid.py``) on seeded weights at tiny widths:
the chunked recurrence and its gradients against the token-by-token
form, the short convolution at a document boundary, the rotary-less
attention kernels, the trainer's losses, gradients and Adam steps, the
vocabulary's share, the published parameter counts, and the spans,
scopes and counters of a training call."""

import dataclasses
import functools
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
    FULL, LINEAR, LMConfig, TransformerLM, named_parameters, norm_offsets,
    table_layout, table_shapes)
from multiverso_tpu.data.packing import pack_documents, real_tokens  # noqa: E402
from multiverso_tpu.ops import gated_delta as gdn              # noqa: E402
from multiverso_tpu.ops import latent_attention as mla         # noqa: E402
from perf.reference import olmo_hybrid as ref                  # noqa: E402

# the catalog's config of Olmo-Hybrid-7B (model-configs guide, row 5)
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def tiny(**over) -> LMConfig:
    base = dict(
        model_type="olmo_hybrid", layer_types=[LINEAR] * 3 + [FULL],
        num_hidden_layers=4, hidden_size=48, intermediate_size=96,
        num_attention_heads=3, num_key_value_heads=3,
        linear_num_key_heads=3, linear_num_value_heads=3,
        linear_key_head_dim=12, linear_value_head_dim=24,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None}, n_routed_experts=0,
        kv_lora_rank=None, vocab_size=256, sequences=4, sequence_length=64,
        attention_block=16, gdn_chunk=16, mlp_chunks=2, head_chunks=2,
        seed=2147483659, init_std=0.02, learning_rate=1e-2,
        warmup_steps=4, compute_dtype="float32")
    return LMConfig(**dict(base, **over))


def documents(c: LMConfig, n=200, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, c.vocab_size, rng.integers(1, 50))
            for _ in range(n)]


def start_tables(c: LMConfig) -> dict:
    out = {}
    for i, (n, s) in enumerate(table_shapes(c).items()):
        out[n] = np.ones(s, np.float32) if n == "norms" else np.asarray(
            ref.init_decay(c.seed, i, s[1]) if n.endswith(".gdn_decay")
            else ref.init_normal(c.seed, i, s, c.init_std))
    return out


def as_tables(c: LMConfig, by_role: dict) -> dict:
    """Tensors by published role laid back into the tables' shapes."""
    tables = {n: np.zeros(s, np.float32)
              for n, s in table_shapes(c).items()}
    for role, view in named_parameters(c, tables).items():
        view[...] = np.asarray(by_role[role])
    return tables


def gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def doc_ids(*lengths, S=64):
    ids = np.repeat(np.arange(1, len(lengths) + 1), lengths)
    return np.concatenate([ids, np.zeros(S - len(ids), np.int64)])


# two sequences each: one document; many; a boundary inside a chunk (of
# 8, 16 and 64) and one on a chunk's edge; a one-token document, first
# in its sequence and in its middle, and a padded tail
DOCS = {
    "one_document": np.stack([doc_ids(64), doc_ids(64)]),
    "many_documents": np.stack([doc_ids(16, 16, 16, 16),
                                doc_ids(5, 9, 13, 7, 11, 19)]),
    "a_boundary_inside_a_chunk": np.stack([doc_ids(32, 32),
                                           doc_ids(21, 30, 13)]),
    "a_one_token_document": np.stack([doc_ids(1, 40, 23),
                                      doc_ids(20, 1, 1, 9)]),
}


@pytest.fixture(scope="module")
def mesh():
    return core.init(devices=jax.devices()[:1], data_parallel=1,
                     model_parallel=1)


# -- the recurrence ------------------------------------------------------------

def recurrence_operands(B=2, S=64, H=3, dk=8, dv=16, seed=0):
    rng = np.random.default_rng(seed)
    qkv = jnp.asarray(rng.normal(size=(B, S, H * (2 * dk + dv))),
                      jnp.float32)
    g = -jnp.asarray(rng.uniform(0.01, 1.5, size=(B, S, H)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 1.9, size=(B, S, H)), jnp.float32)
    return qkv, g, beta


def token_by_token(qkv, g, beta, doc, H, dk, dv):
    """The reference's scan over tokens, a sequence at a time."""
    S = qkv.shape[1]
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, -1, keepdims=True) + ref.L2_EPS)
    out = []
    for b in range(qkv.shape[0]):
        q = qkv[b, :, :H * dk].reshape(S, H, dk)
        k = qkv[b, :, H * dk:2 * H * dk].reshape(S, H, dk)
        v = qkv[b, :, 2 * H * dk:].reshape(S, H, dv)
        out.append(ref.delta_rule(unit(q) * dk ** -0.5, unit(k), v, g[b],
                                  beta[b], ref.starts(doc[b])))
    return jnp.stack(out)


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("docs", sorted(DOCS))
def test_the_chunked_recurrence_and_its_gradients_equal_the_token_form(
        docs, chunk):
    """Output and the gradient of every operand of ``recur`` — the
    chunk-local solve, the state across chunks, the restart at a
    document start wherever it falls in a chunk — against ``jax.grad``
    of the token-by-token scan."""
    H, dk, dv = 3, 8, 16
    shape = gdn.GatedDeltaShape(H, dk, dv, 4, True, 1e-6, chunk, "float32")
    doc = jnp.asarray(DOCS[docs], jnp.int32)
    operands = recurrence_operands()
    ours = lambda *a: gdn.recur(*a, doc, shape)
    plain = lambda *a: token_by_token(*a, doc, H, dk, dv)
    np.testing.assert_allclose(ours(*operands), plain(*operands),
                               rtol=2e-5, atol=2e-6)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 64, H, dv)), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * weight), (0, 1, 2))(
        *operands)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), (0, 1, 2))(
        *operands)
    for name, g, w in zip(("qkv", "g", "beta"), got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5,
                                   err_msg=name)


def test_a_large_decay_neither_overflows_nor_leaks():
    """A chunk's running log decay far below float32's exp range: the
    masked pairs' ``exp(G_i - G_j)`` (positive above the diagonal) must
    not reach the result as inf or nan."""
    H, dk, dv = 2, 8, 8
    shape = gdn.GatedDeltaShape(H, dk, dv, 4, True, 1e-6, 64, "float32")
    qkv, _, beta = recurrence_operands(H=H, dk=dk, dv=dv)
    g = jnp.full((2, 64, H), -3.0)          # G falls to -192 in a chunk
    doc = jnp.asarray(DOCS["a_boundary_inside_a_chunk"], jnp.int32)
    got = gdn.recur(qkv, g, beta, doc, shape)
    want = token_by_token(qkv, g, beta, doc, H, dk, dv)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    grads = jax.grad(lambda *a: jnp.sum(gdn.recur(*a, doc, shape)),
                     (0, 1, 2))(qkv, g, beta)
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)


def scan_over_chunks(w, u, attn, q_in, k_out, keep):
    """The plain form ``gdn.across_chunks``'s kernels replaced: a
    ``lax.scan`` over the chunks with the state as its carry, in the
    kernels' order and precisions; its gradients are the scan's own."""
    dtype = w.dtype
    dot = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def chunk(state, xs):
        w, u, attn, q_in, k_out, keep = xs
        held = state.astype(dtype)
        newb = (u - dot("hck,hkv->hcv", w, held)).astype(dtype)
        o = dot("hck,hkv->hcv", q_in, held) \
            + dot("hij,hjv->hiv", attn, newb)
        return keep * state + dot("hck,hcv->hkv", k_out, newb), o

    start = jnp.zeros((w.shape[1], w.shape[3], u.shape[3]), jnp.float32)
    return jax.lax.scan(chunk, start, (w, u, attn, q_in, k_out, keep))[1]


TERMS = ("w", "u", "attn", "q_in", "k_out", "keep")


def assert_the_kernels_equal_the_scan(terms, seed=5):
    """Output and the cotangent of each of the six operands."""
    got, got_vjp = jax.vjp(
        lambda *a: gdn.across_chunks(*a, True), *terms)
    want, want_vjp = jax.vjp(scan_over_chunks, *terms)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    weight = jnp.asarray(np.random.default_rng(seed).normal(
        size=want.shape), jnp.float32)
    for name, g, w in zip(TERMS, got_vjp(weight), want_vjp(weight)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("docs", sorted(DOCS))
def test_the_kernels_across_chunks_equal_the_scan_they_replaced(docs,
                                                                chunk):
    """The forward and the backward kernel (interpreted here) against
    ``jax.vjp`` of the plain scan, on the six operands ``recur`` makes
    of every document layout."""
    shape = gdn.GatedDeltaShape(3, 8, 16, 4, True, 1e-6, chunk, "float32")
    assert_the_kernels_equal_the_scan(gdn.chunk_terms(
        *recurrence_operands(), jnp.asarray(DOCS[docs], jnp.int32), shape))


def test_a_head_count_the_kernels_block_does_not_divide(monkeypatch):
    """A grid step takes the most heads that fit its budget AND divide
    the head count; with ten heads (two sequences of five) two a step,
    one block holds the last head of one sequence beside the first of
    the next."""
    published = (64, 96, 192)       # chunk, d_k, d_v: 22 heads fit
    assert gdn._head_block(30, *published) == 15
    assert gdn._head_block(62, *published) == 2
    assert gdn._head_block(7, *published) == 7
    assert gdn._head_block(23, *published) == 1
    H, dk, dv, C = 5, 8, 16, 16
    monkeypatch.setattr(gdn, "_head_block", lambda *a: 2)
    shape = gdn.GatedDeltaShape(H, dk, dv, 4, True, 1e-6, C, "float32")
    assert_the_kernels_equal_the_scan(gdn.chunk_terms(
        *recurrence_operands(H=H, dk=dk, dv=dv),
        jnp.asarray(DOCS["many_documents"], jnp.int32), shape))


def test_the_recurrence_at_the_published_widths_in_bfloat16():
    """30 heads of 96 x 192, chunks of 64, bfloat16 operands: output and
    the three gradients of ``recur`` against the plain scan over
    float32 operands."""
    H, dk, dv, S = 30, 96, 192, 256
    shape = gdn.GatedDeltaShape(H, dk, dv, 4, True, 1e-6, 64, "bfloat16")
    doc = jnp.asarray(np.tile(DOCS["a_boundary_inside_a_chunk"][1:], 4)
                      + 3 * np.repeat(np.arange(4), 64), jnp.int32)
    operands = recurrence_operands(B=1, S=S, H=H, dk=dk, dv=dv, seed=7)

    def plain(*a):
        return gdn.from_chunks(scan_over_chunks(*gdn.chunk_terms(
            *a, doc, shape._replace(dtype="float32"))), 1)

    ours = lambda *a: gdn.recur(*a, doc, shape)
    weight = jnp.asarray(np.random.default_rng(8).normal(
        size=(1, S, H, dv)), jnp.float32)

    def output_and_gradients(f):
        def loss(*a):
            o = f(*a)
            return jnp.sum(o * weight), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True))(*operands)
        return (o,) + grads

    for name, g, w in zip(("o", "qkv", "g", "beta"),
                          output_and_gradients(ours),
                          output_and_gradients(plain)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert gap(g, w) < 2e-2, name


@pytest.mark.parametrize("docs", sorted(DOCS))
def test_the_short_convolution_stops_at_a_document_boundary(docs):
    doc = DOCS[docs]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 64, 10)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(4, 10)), jnp.float32)
    got = gdn.short_conv(x, taps, jnp.asarray(doc, jnp.int32))
    want = jnp.stack([jax.nn.silu(ref.short_conv(
        x[b], taps, jnp.asarray(doc[b]))) for b in range(2)])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # a document's first token sees itself alone: the last tap
    first = np.flatnonzero(np.diff(doc[1], prepend=-1) != 0)
    np.testing.assert_allclose(
        got[1, first], jax.nn.silu(x[1, first] * taps[3]), rtol=1e-6,
        atol=1e-6)
    # and the reference's control does reach across
    across = jax.nn.silu(ref.short_conv(x[1], taps, jnp.asarray(doc[1]),
                                        across=True))
    if len(first) > 1:
        assert not np.allclose(across[first[1:]], want[1, first[1:]])


# -- attention without rotary operands -------------------------------------------

def plain_attention(q, k, v, doc, scale):
    B, S = doc.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    t = jnp.arange(S)
    allowed = (t[:, None] >= t[None]) & (doc[:, :, None] == doc[:, None])
    prob = jax.nn.softmax(jnp.where(allowed[:, None], scores, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", prob.astype(v.dtype), v,
                      preferred_element_type=jnp.float32
                      ).astype(v.dtype).reshape(B, S, -1)


def attention_operands(B=2, S=64, H=3, d=16, seed=3, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, S, H, d)), dtype)
                 for _ in range(3))


@pytest.mark.parametrize("docs", sorted(DOCS))
def test_attention_without_rotary_operands_and_its_gradients(docs):
    """The kernels' static variant with per-head keys only (interpreted
    here) against ``jax.grad`` of the plain [S, S] form."""
    operands = attention_operands()
    doc = jnp.asarray(DOCS[docs], jnp.int32)
    weight = attention_operands(seed=4)[0].reshape(2, 64, -1)
    ours = lambda *a: mla.attend_heads(*a, doc, scale=0.25, block=16)
    plain = lambda *a: plain_attention(*a, doc, 0.25)
    np.testing.assert_allclose(ours(*operands), plain(*operands),
                               rtol=1e-5, atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * weight), (0, 1, 2))(
        *operands)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), (0, 1, 2))(
        *operands)
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5,
                                   err_msg=name)


def test_attention_at_the_published_head_dim_in_bfloat16():
    """Keys and values of depth 128, bfloat16 operands, forward and
    backward against the plain form in the same precision."""
    operands = attention_operands(B=1, H=2, d=128, seed=7,
                                  dtype=jnp.bfloat16)
    doc = jnp.asarray(DOCS["a_boundary_inside_a_chunk"][1:], jnp.int32)
    scale = 128 ** -0.5
    ours = lambda *a: mla.attend_heads(*a, doc, scale=scale, block=32)
    plain = lambda *a: plain_attention(*a, doc, scale)
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(ours(*operands)), f32(plain(*operands)),
                               rtol=2e-2, atol=2e-2)
    loss = lambda f: lambda *a: jnp.sum(f(*a).astype(jnp.float32))
    got = jax.grad(loss(ours), (0, 1, 2))(*operands)
    want = jax.grad(loss(plain), (0, 1, 2))(*operands)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16
        assert gap(f32(g), f32(w)) < 2e-2


# -- the trainer against the reference --------------------------------------------

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
    cfg = dataclasses.asdict(c)
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    m, v = ref.host_zeros_like(p), ref.host_zeros_like(p)
    steps = []
    for s in range(3):
        ce, g = ref.loss_and_grads(p, batches[s], cfg)
        steps.append((ce, {k: np.asarray(x) for k, x in g.items()}))
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
    assert set(trained["start"]) == set(want)
    for name, got in trained["start"].items():
        np.testing.assert_allclose(got, want[name], rtol=1e-5, atol=1e-8)
    decay = want["l0.gdn_decay"]
    assert (np.exp(decay[0]) <= 16).all()               # a_log = log U(0, 16)
    dt = np.log1p(np.exp(decay[1]))                     # softplus(dt_bias)
    assert (dt >= 0.001 * 0.999).all() and (dt <= 0.1 * 1.001).all()


def test_losses_of_three_steps_match_the_reference(trained):
    assert len(trained["app"].loss_history) == 3
    for (ce, balance), (want_ce, _) in zip(trained["app"].loss_history,
                                          trained["steps"]):
        assert ce == pytest.approx(want_ce, rel=2e-6)
        assert balance == 0.0


def test_every_table_s_gradient_matches_the_reference(trained):
    c = trained["config"]
    aux, grads = trained["first"]
    want = as_tables(c, trained["steps"][0][1])
    assert set(grads) == set(want)
    for name in want:
        got = np.asarray(grads[name])[:want[name].shape[0]]
        assert gap(got, want[name]) < 5e-5, name
    step = jax.device_get(trained["app"].aux_tail[0])
    for name, norm in zip(want, step["grad_norms"]):
        assert norm == pytest.approx(np.linalg.norm(want[name]), rel=2e-5)
    assert gap(step["probe_embed"], want["embed"][:256]) < 5e-5
    # layer 0's key projection of the recurrence, entry by entry
    assert gap(step["probe_gdn_k"],
               trained["steps"][0][1]["l0.w_k"]) < 5e-5


def test_tables_after_three_adam_steps_match_the_reference(trained):
    c = trained["config"]
    start = named_parameters(c, trained["start"])
    got = named_parameters(c, {
        n: np.asarray(t.raw()) for n, t in trained["app"].tables.items()})
    for role, want in trained["final"].items():
        assert gap(got[role] - start[role], want - start[role]) < 2e-3, role
    assert all(t.default_option.step == 3
               for t in trained["app"].tables.values())


def test_the_reference_s_layer_at_a_time_gradient_is_the_objective_s(
        trained):
    """One ``jax.grad`` over the whole step's objective gives the loss
    and gradients the reference chains by hand."""
    c = trained["config"]
    cfg = dataclasses.asdict(c)
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    tokens, doc = (jnp.asarray(trained["batches"][0][k])
                   for k in ("tokens", "doc"))
    n_pred = jnp.sum((doc[:, 1:] == doc[:, :-1]) & (doc[:, :-1] > 0))

    def objective(p):
        total = 0.0
        for b in range(tokens.shape[0]):
            x = p["embed"][tokens[b]]
            for i in range(c.num_hidden_layers):
                x = ref.layer(ref.layer_tensors(p, i), x, doc[b], cfg)
            total += ref.head_loss(x, p["final_norm"], p["head"],
                                   tokens[b], doc[b], cfg) / n_pred
        return total

    want_ce, got = trained["steps"][0]
    with jax.default_matmul_precision("highest"):
        loss, want = jax.value_and_grad(objective)(p)
    assert float(loss) == pytest.approx(want_ce, rel=1e-6)
    assert set(got) == set(want)
    for role, g in want.items():
        np.testing.assert_allclose(got[role], g, rtol=2e-4, atol=2e-7,
                                   err_msg=role)


def test_bfloat16_products_stay_near_the_reference(mesh, trained):
    c = dataclasses.replace(trained["config"], compute_dtype="bfloat16")
    app = TransformerLM(c, mesh=mesh)
    aux, grads = app.gradients(trained["batches"][0])
    want_ce, want = trained["steps"][0]
    assert float(aux["ce"]) == pytest.approx(want_ce, rel=2e-3)
    want = as_tables(c, want)
    for name in want:
        got = np.asarray(grads[name])[:want[name].shape[0]]
        assert 1e-4 < gap(got, want[name]) < 0.1, name


def test_changing_one_document_leaves_another_s_states_bit_equal(trained):
    """State, taps and scores all stop at a document's boundary."""
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


@pytest.mark.parametrize("variant", ["carried_state", "conv_across",
                                     "no_decay", "beta_1", "no_doc_mask",
                                     "state_bfloat16"])
def test_a_control_of_the_reference_is_not_the_reference(
        trained, variant, monkeypatch):
    """Each deliberately wrong reference moves the first step's loss or
    gradients well past what separates program and reference."""
    c = trained["config"]
    # four blocks a sequence: the state is rounded where it crosses them
    monkeypatch.setattr(ref, "STATE_BLOCK", 16)
    ref._programs.cache_clear()
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    ce, grads = ref.loss_and_grads(p, trained["batches"][0],
                                   dataclasses.asdict(c), variant)
    want_ce, want = trained["steps"][0]
    ref._programs.cache_clear()
    moved = max(abs(ce - want_ce) / want_ce,
                max(gap(grads[k], want[k]) for k in want))
    assert moved > (1e-4 if variant == "state_bfloat16" else 1e-2), moved


def test_the_eight_vocabulary_slices_logits_are_the_uncut_model_s(mesh):
    """Guide 4: each of the ``vocab_shard`` chips holds an eighth of the
    head's rows and computes its slice of the logits from the same
    residual; side by side they are the uncut reference's."""
    shards, rows = 8, 32
    c = tiny(vocab_size=rows, vocab_shard=shards, num_hidden_layers=2,
             layer_types=[LINEAR, FULL], sequences=2, mlp_chunks=1,
             head_chunks=1)
    rng = np.random.default_rng(9)
    uncut_head = rng.normal(size=(shards * rows, c.hidden_size)
                            ).astype(np.float32) * 0.1
    docs = documents(c, n=40, seed=4)
    batch = next(iter(pack_documents(docs, c.sequences, c.sequence_length)))
    app = TransformerLM(c, mesh=mesh)
    x = app.hidden_states(batch)
    final_norm = named_parameters(c, {"norms": np.asarray(
        app.tables["norms"].raw())})["final_norm"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.head_logits(x[b], jnp.asarray(final_norm),
                                          jnp.asarray(uncut_head),
                                          dataclasses.asdict(c))
                          for b in range(c.sequences)])
    got = []
    for r in range(shards):
        app.tables["head"].put_raw(jnp.asarray(
            uncut_head[r * rows:(r + 1) * rows]))
        got.append(np.asarray(app.logits(batch)))
    np.testing.assert_allclose(np.concatenate(got, -1), want, rtol=2e-5,
                               atol=2e-6)


def test_the_published_config_has_the_issue_s_parameter_counts():
    counts = ref.parameter_counts(PUBLISHED)
    assert counts["linear_attention"] == 88_750_332        # 88.75M
    assert counts["feed_forward"] == 126_812_160           # 126.81M
    assert counts["full_attention"] == 58_990_080          # 58.99M
    assert counts["model"] == pytest.approx(7.43e9, rel=1e-3)
    # the same from the program's tables, as the chip's share holds them
    held = dict(PUBLISHED, num_hidden_layers=4, vocab_size=12544,
                vocab_shard=8, sequences=2, sequence_length=4096)
    c = LMConfig.from_dict(held)
    c.check()
    assert c.n_routed_experts == 0 and c.kv_lora_rank is None
    assert [c.mixer(i) for i in range(4)] == [LINEAR] * 3 + [FULL]
    shapes = table_shapes(c)
    size = lambda n: int(np.prod(shapes[n]))
    assert size("l0.gdn_in") + size("l0.gdn_conv") + size("l0.gdn_decay") \
        + size("l0.gdn_out") + 192 == counts["linear_attention"]
    assert size("l3.attn") + 2 * 3840 == counts["full_attention"]
    assert size("l0.mlp") == size("l3.mlp") == counts["feed_forward"]
    assert size("embed") + size("head") == 2 * 12544 * 3840
    assert shapes["norms"] == (3 * 3 + 4 + 1, 3840)
    assert norm_offsets(c) == [0, 3, 6, 9, 13]
    assert len(shapes) == 3 + 3 * 5 + 2
    total = sum(map(size, shapes))
    assert total == pytest.approx(928.9e6, rel=1e-3)       # the cut
    layout = table_layout(c)
    assert list(layout) == list(shapes)
    # embed, head, final norm; a linear layer's 16 roles, the full
    # layer's 11
    assert sum(map(len, layout.values())) == 3 + 3 * 16 + 11
    roles = named_parameters(c, {n: np.zeros(s, np.float32)
                                 for n, s in shapes.items()
                                 if n.startswith("l0.gdn")
                                 or n == "l3.attn"})
    assert roles["l0.w_k"].shape == (3840, 2880)
    assert roles["l0.w_v"].shape == roles["l0.w_g"].shape == (3840, 5760)
    assert roles["l0.w_a"].shape == roles["l0.w_b"].shape == (3840, 30)
    assert roles["l0.conv"].shape == (4, 11520)
    assert roles["l3.w_o"].shape == (3840, 3840)


def test_spans_scopes_and_counters_of_a_training_call(trained):
    snap = telemetry.snapshot()
    spans = {k for k in snap["histograms"] if k.startswith("span.seconds")}
    for name in ("lm.wait_data", "lm.place", "lm.superstep", "lm.fence",
                 "lm.setup.init_tables", "lm.docs.produce"):
        assert f"span.seconds{{name={name}}}" in spans, name
    counters = trained["counters"]
    c, batches = trained["config"], trained["batches"][:3]
    # (sequence, linear layer, chunk) triples: 4 sequences of 4 chunks
    assert counters["lm.gdn.chunks"] == 3 * len(batches) * 4 * 4
    documents_trained = sum(
        len(np.unique(row[row > 0])) for b in batches for row in b["doc"])
    assert counters["lm.gdn.doc_starts"] == 3 * documents_trained
    # the attention counters count the ONE layer that attends
    plans = [np.asarray(mla.block_plan(jnp.asarray(b["doc"]), 16))
             for b in batches]
    assert counters["lm.attend.key_blocks"] == len(batches) * 4 * 10
    assert counters["lm.attend.key_blocks_computed"] \
        == sum(p.sum() for p in plans)
    held = telemetry.op_scopes()["superstep.lm_superstep"]
    assert held["module"] == "jit_run"
    named = set(held["scopes"].values())
    assert {"lm.embed_gather", "lm.embed_scatter", "lm.gdn.project",
            "lm.gdn.conv", "lm.gdn.recur", "lm.gdn.gate_out",
            "lm.attn.project", "lm.attn.attend", "lm.dense_mlp",
            "lm.head_loss", "lm.adam"} <= named


def test_the_step_names_what_lies_between_its_phases(trained):
    """A block's glue carries a scope of its own beside the reordered
    norms' (PR 36); this model has no expert layer."""
    held = telemetry.op_scopes()["superstep.lm_superstep"]
    named = set(held["scopes"].values())
    assert {"lm.block_norm", "lm.residual"} <= named
    # the map says which names are not an op's own: fusions named by
    # their body (1 or more scopes in it) or by their operands (0)
    assert set(held) == {"module", "scopes", "inferred"}
    for name, n in held["inferred"].items():
        assert held["scopes"][name] != "unscoped" and n >= 0


def test_the_packer_s_pool_is_the_configuration_s(mesh):
    """``open_sequences`` reaches ``pack_documents``; left out, the pool
    is 4 x sequences as before."""
    c = tiny(sequences=2, mlp_chunks=1, head_chunks=1)
    docs = documents(c, n=300, seed=6)
    first = lambda app: next(iter(app._batches()))
    default = first(TransformerLM(c, docs, mesh=mesh))
    want = next(iter(pack_documents(docs, 2, 64)))
    assert all(np.array_equal(default[k], want[k]) for k in want)
    wide = first(TransformerLM(dataclasses.replace(c, open_sequences=32),
                               docs, mesh=mesh))
    want = next(iter(pack_documents(docs, 2, 64, open_sequences=32)))
    assert all(np.array_equal(wide[k], want[k]) for k in want)
    assert real_tokens(wide) >= real_tokens(default)


@pytest.mark.parametrize("change, error, says", [
    (dict(attention_bias=True), NotImplementedError, "attention_bias"),
    (dict(hidden_act="gelu"), NotImplementedError, "hidden_act"),
    (dict(kv_lora_rank=16), NotImplementedError, "kv_lora_rank"),
    (dict(layer_types=[LINEAR, "conv", LINEAR, FULL]), NotImplementedError,
     "layer_types entry"),
    (dict(model_type="olmo3"), NotImplementedError, "model_type"),
    (dict(layer_types=[LINEAR, "sliding_attention", LINEAR, FULL]),
     NotImplementedError, "layer_types entry"),
    (dict(layer_types=[LINEAR, FULL]), ValueError, "names 2 of 4"),
    (dict(num_key_value_heads=1), NotImplementedError, "grouped"),
    (dict(rope_parameters={"rope_theta": 500000.0}), NotImplementedError,
     "rotary"),
    (dict(linear_num_value_heads=6), NotImplementedError, "value heads"),
    (dict(q_lora_rank=8), NotImplementedError, "q_lora_rank"),
    (dict(scoring_func="sigmoid"), NotImplementedError, "scoring_func"),
    (dict(model_type="deepseek_v2"), NotImplementedError, "layer_types in"),
    (dict(layer_types=None), NotImplementedError, "names no mixer"),
    (dict(gdn_chunk=48), ValueError, "gdn_chunk"),
    (dict(mlp_chunks=3), ValueError, "mlp_chunks"),
])
def test_what_the_hybrid_does_not_build_says_so(change, error, says):
    with pytest.raises(error, match=says):
        tiny(**change).check()
    tiny().check()
