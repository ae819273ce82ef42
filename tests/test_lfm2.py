"""The LFM2-MoE decoder — gated short convolutions beside grouped-query
rotary attention in a pre-norm block, a sigmoid router whose choice a
bias table steers, one tied vocabulary table — through
``apps/transformer_lm.py`` against the plain float32 reference
(``perf/reference/lfm2.py``) on seeded weights at tiny widths: the conv
operator and its gradients at a document boundary, the grouped kernels,
the router, the trainer's losses, gradients, Adam steps and bias steps,
the experts' and the vocabulary's shares, the published parameter
counts, and the spans, scopes and counters of a training call."""

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
    CONV, FULL, LMConfig, TransformerLM, is_bias, named_parameters,
    norm_offsets, table_layout, table_shapes)
from multiverso_tpu.data.packing import pack_documents         # noqa: E402
from multiverso_tpu.ops import latent_attention as mla         # noqa: E402
from multiverso_tpu.ops import moe                             # noqa: E402
from multiverso_tpu.ops import short_conv as sconv             # noqa: E402
from multiverso_tpu.telemetry import profiling                 # noqa: E402
from perf.reference import lfm2 as ref                         # noqa: E402

# the catalog's config of LFM2-8B-A1B (model-configs guide, row 34)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV,
                    CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV,
                    FULL, CONV, CONV, FULL, CONV, CONV],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
# the issue's cut: published layers 1-5, one expert-parallel rank of four
CUT = dict(PUBLISHED, num_hidden_layers=5, num_dense_layers=1,
           layer_types=PUBLISHED["layer_types"][1:6], num_experts=8,
           ep_size=4, ep_rank=0, vocab_size=16384, vocab_shard=4,
           tie_word_embeddings=True)


def tiny(**over) -> LMConfig:
    """The published keys under their published names, at tiny widths:
    2 experts held of 8 router outputs, 4 query heads on 2 key-value
    heads of 16 dims."""
    base = dict(
        model_type="lfm2_moe", layer_types=[CONV, FULL, CONV, CONV],
        num_hidden_layers=4, num_dense_layers=1, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_experts=2,
        ep_size=4, ep_rank=1, num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=1, use_expert_bias=True,
        num_attention_heads=4, num_key_value_heads=2, norm_eps=1e-5,
        conv_L_cache=3, conv_bias=False, rope_theta=1000000,
        tie_word_embeddings=True, vocab_size=256, sequences=4,
        sequence_length=64, attention_block=16, expert_chunk_rows=32,
        mlp_chunks=2, head_chunks=2, seed=2147483659, init_std=0.02,
        learning_rate=1e-2, warmup_steps=4, expert_bias_rate=1e-3,
        compute_dtype="float32")
    return LMConfig.from_dict(dict(base, **over))


def ref_config(c: LMConfig) -> dict:
    """The reference's own keys (the published names; ``num_experts``
    counts the router's outputs) from the program's configuration."""
    return {"hidden_size": c.hidden_size,
            "num_hidden_layers": c.num_hidden_layers,
            "layer_types": c.layer_types[:c.num_hidden_layers],
            "num_dense_layers": c.first_k_dense_replace,
            "intermediate_size": c.intermediate_size,
            "moe_intermediate_size": c.moe_intermediate_size,
            "num_experts": c.router_width, "ep_size": c.ep_size,
            "ep_rank": c.ep_rank,
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": c.norm_topk_prob,
            "routed_scaling_factor": c.routed_scaling_factor,
            "num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.kv_heads, "norm_eps": c.rms_norm_eps,
            "conv_L_cache": c.conv_L_cache, "rope_theta": c.rope_theta,
            "vocab_size": c.vocab_size}


def documents(c: LMConfig, n=200, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, c.vocab_size, rng.integers(1, 50))
            for _ in range(n)]


def start_tables(c: LMConfig) -> dict:
    out = {}
    for i, (n, s) in enumerate(table_shapes(c).items()):
        out[n] = np.ones(s, np.float32) if n == "norms" \
            else np.zeros(s, np.float32) if is_bias(n) \
            else np.asarray(ref.init_normal(c.seed, i, s, c.init_std))
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


# two sequences each: one document; many; a boundary inside a block (of
# 16) and one on a block's edge; a one-token document, first in its
# sequence and in its middle, and a padded tail
DOCS = {
    "one_document": np.stack([doc_ids(64), doc_ids(64)]),
    "many_documents": np.stack([doc_ids(16, 16, 16, 16),
                                doc_ids(5, 9, 13, 7, 11, 19)]),
    "a_boundary_inside_a_block": np.stack([doc_ids(32, 32),
                                           doc_ids(21, 30, 13)]),
    "a_one_token_document": np.stack([doc_ids(1, 40, 23),
                                      doc_ids(20, 1, 1, 9)]),
}


def positions(doc):
    """Positions restarting at every document of ``doc`` [B, S]."""
    pos = np.zeros_like(doc)
    for b, row in enumerate(doc):
        for t in range(1, len(row)):
            pos[b, t] = pos[b, t - 1] + 1 if row[t] == row[t - 1] else 0
    return pos


@pytest.fixture(scope="module")
def mesh():
    return core.init(devices=jax.devices()[:1], data_parallel=1,
                     model_parallel=1)


# -- the gated short convolution ---------------------------------------------------

def conv_operands(D=10, taps=3, seed=2):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return {"u": f32(2, 64, D), "conv_in": f32(D, 3 * D) * 0.3,
            "conv_taps": f32(taps, D), "conv_out": f32(D, D) * 0.3}


def our_conv(o, doc):
    return sconv.project_out(
        sconv.mix(sconv.project_in(o["u"], o["conv_in"], jnp.float32),
                  o["conv_taps"], doc), o["conv_out"], jnp.float32)


def plain_conv(o, doc, variant=None):
    return jnp.stack([ref.short_conv(o, o["u"][b], doc[b], variant)
                      for b in range(doc.shape[0])])


@pytest.mark.parametrize("docs", sorted(DOCS))
def test_the_conv_operator_and_its_gradients_at_a_document_boundary(docs):
    doc = jnp.asarray(DOCS[docs], jnp.int32)
    o = conv_operands()
    weight = jnp.asarray(np.random.default_rng(5).normal(
        size=(2, 64, 10)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, want = our_conv(o, doc), plain_conv(o, doc)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        g = jax.grad(lambda o: jnp.sum(our_conv(o, doc) * weight))(o)
        w = jax.grad(lambda o: jnp.sum(plain_conv(o, doc) * weight))(o)
    for name in o:
        np.testing.assert_allclose(g[name], w[name], rtol=1e-4, atol=1e-5,
                                   err_msg=name)
    # a document's first token sees itself alone: the last tap, and no
    # activation anywhere
    first = np.flatnonzero(np.diff(DOCS[docs][1], prepend=-1) != 0)
    bcx = sconv.project_in(o["u"], o["conv_in"], jnp.float32)
    b, c, x = np.split(np.asarray(bcx), 3, axis=-1)
    mixed = np.asarray(sconv.mix(bcx, o["conv_taps"], doc))
    np.testing.assert_allclose(
        mixed[1, first], (c * b * x)[1, first] * np.asarray(
            o["conv_taps"])[2], rtol=1e-5, atol=1e-6)
    # and the reference's controls do differ: taps across, a silu inside
    if len(first) > 1:
        across = plain_conv(o, doc, "conv_across")
        assert not np.allclose(across[1, first[1:]], want[1, first[1:]])
    assert not np.allclose(plain_conv(o, doc, "conv_silu"), want)


# -- the grouped kernels -----------------------------------------------------------

def plain_grouped(q, k, v, doc, scale):
    """[S, S] attention with query head h on key-value head h // group."""
    B, S, H, _ = q.shape
    group = H // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    t = jnp.arange(S)
    allowed = (t[:, None] >= t[None]) & (doc[:, :, None] == doc[:, None])
    prob = jax.nn.softmax(jnp.where(allowed[:, None], scores, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", prob.astype(v.dtype), v,
                      preferred_element_type=jnp.float32
                      ).astype(v.dtype).reshape(B, S, -1)


def grouped_operands(B=2, S=64, H=4, G=2, d=16, seed=3, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, S, n, d)), dtype)
                 for n in (H, G, G))


@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("docs", ["many_documents",
                                  "a_boundary_inside_a_block"])
def test_the_grouped_kernels_and_their_gradients(docs, group):
    """Query head h reads key-value head h // group (interpreted here);
    the keys' and values' gradients are the group's sums: against
    ``jax.grad`` of the plain [S, S] form."""
    operands = grouped_operands(G=4 // group)
    doc = jnp.asarray(DOCS[docs], jnp.int32)
    weight = grouped_operands(seed=4)[0].reshape(2, 64, -1)
    ours = lambda *a: mla.attend_heads(*a, doc, scale=0.25, block=16)
    plain = lambda *a: plain_grouped(*a, doc, 0.25)
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


def test_the_grouped_kernels_at_the_published_head_dim_in_bfloat16():
    """8 query heads on 2 key-value heads of depth 64 (padded to whole
    lanes), bfloat16 operands, forward and backward against the plain
    form in the same precision."""
    operands = grouped_operands(B=1, H=8, G=2, d=64, seed=7,
                                dtype=jnp.bfloat16)
    doc = jnp.asarray(DOCS["a_boundary_inside_a_block"][1:], jnp.int32)
    ours = lambda *a: mla.attend_heads(*a, doc, scale=0.125, block=32)
    plain = lambda *a: plain_grouped(*a, doc, 0.125)
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(ours(*operands)), f32(plain(*operands)),
                               rtol=2e-2, atol=2e-2)
    loss = lambda f: lambda *a: jnp.sum(f(*a).astype(jnp.float32))
    got = jax.grad(loss(ours), (0, 1, 2))(*operands)
    want = jax.grad(loss(plain), (0, 1, 2))(*operands)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16
        assert gap(f32(g), f32(w)) < 2e-2


def test_the_grouped_projection_is_the_reference_s(mesh):
    """QK-norm a head with one weight vector, then the rotary embedding
    over the whole head at positions that restart: ``project_grouped``
    and the kernels against the reference's attention operator."""
    c = tiny()
    cfg = ref_config(c)
    rng = np.random.default_rng(11)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    D, d, G = 64, 16, 2
    p = {"w_q": f32(D, D) * 0.2, "w_k": f32(D, G * d) * 0.2,
         "w_v": f32(D, G * d) * 0.2, "w_o": f32(D, D) * 0.2,
         "q_norm": 1 + 0.1 * f32(d), "k_norm": 1 + 0.1 * f32(d)}
    doc = DOCS["many_documents"]
    pos = positions(doc)
    u = f32(2, 64, D)
    with jax.default_matmul_precision("highest"):
        q, k, v = mla.project_grouped(
            u, jnp.asarray(pos), p["w_q"], p["w_k"], p["w_v"],
            p["q_norm"], p["k_norm"], 4, G, 1e-5, 1e6, jnp.float32)
        got = mla.output_heads(mla.attend_heads(
            q, k, v, jnp.asarray(doc, jnp.int32), scale=0.25, block=16),
            p["w_o"])
        want = jnp.stack([ref.full_attention(
            p, u[b], jnp.asarray(doc[b]), jnp.asarray(pos[b]), cfg)
            for b in range(2)])
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        for variant in ("no_rotary", "kv_head_mod", "qk_norm_all",
                        "no_doc_mask"):
            wrong = ref.full_attention(p, u[1], jnp.asarray(doc[1]),
                                       jnp.asarray(pos[1]), cfg, variant)
            assert gap(wrong, want[1]) > 1e-2, variant


# -- the router ------------------------------------------------------------------------

def routed(h, w, bias, **kw):
    real = jnp.ones(h.shape[:2], jnp.float32)
    kw = dict(dict(top_k=2, norm_topk_prob=True, scaling=1.0, alpha=0.0,
                   score="sigmoid"), **kw)
    return moe.route(h, w, real, bias=bias, **kw)


def test_the_router_scores_by_a_sigmoid_and_normalises_the_chosen():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 6)), jnp.float32) * 0.3
    r = routed(h, w, jnp.zeros((6,)))
    s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", h, w,
                                  precision="highest")).reshape(16, 6)
    top_s, top_e = jax.lax.top_k(s, 2)
    assert np.array_equal(r.top_e, top_e)
    np.testing.assert_allclose(
        r.top_s, top_s / (top_s.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(r.top_s.sum(-1), 1.0, rtol=1e-5)
    assert float(r.balance) == 0.0          # no balance loss at alpha 0
    assert int(r.counts.sum()) == 16 * 2
    # without the normalisation the weights are the scores themselves
    loose = routed(h, w, jnp.zeros((6,)), norm_topk_prob=False)
    np.testing.assert_allclose(loose.top_s, top_s, rtol=1e-6)


def test_the_bias_steers_the_choice_and_never_the_weights():
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(1, 32, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 6)), jnp.float32) * 0.3
    plain = routed(h, w, jnp.zeros((6,)))
    # a bias larger than any score's spread sends every token to expert 5
    bias = jnp.zeros((6,)).at[5].set(2.0)
    pushed = routed(h, w, bias)
    assert np.all(np.any(np.asarray(pushed.top_e) == 5, axis=-1))
    assert not np.all(np.any(np.asarray(plain.top_e) == 5, axis=-1))
    assert int(pushed.counts[5]) == 32
    # ... and the weights are the scores alone, normalised: no 2.0 in them
    s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", h, w,
                                  precision="highest")).reshape(32, 6)
    chosen = jnp.take_along_axis(s, pushed.top_e, -1)
    np.testing.assert_allclose(
        pushed.top_s, chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # the bias takes no gradient; the router's weights do
    loss = lambda w, b: jnp.sum(routed(h, w, b).top_s[:, 0])
    d_w, d_b = jax.grad(loss, (0, 1))(w, bias)
    assert float(jnp.abs(d_b).max()) == 0.0 and float(jnp.abs(d_w).max()) > 0
    # a tie goes to the lower index, as the reference's top_k has it
    tie = routed(jnp.zeros((1, 4, 16)), w, jnp.zeros((6,)))
    assert np.array_equal(tie.top_e, np.tile([0, 1], (4, 1)))
    cfg = {"num_experts_per_tok": 2, "norm_topk_prob": True}
    _, want_e = ref.route({"router": w, "expert_bias": bias}, h[0], cfg)
    assert np.array_equal(pushed.top_e, want_e)


def test_the_bias_s_delta_is_the_sign_of_the_load_s_excess():
    counts = jnp.asarray([10, 0, 4, 4, 2, 4, 4, 4])     # mean 4
    np.testing.assert_array_equal(moe.bias_delta(counts),
                                  [1, -1, 0, 0, -1, 0, 0, 0])


# -- the trainer against the reference --------------------------------------------

def reference_steps(c, batches, steps=3):
    """The reference's steps from the program's start: losses, deltas,
    and the tensors after the last."""
    cfg = ref_config(c)
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    m, v = ref.host_zeros_like(p), ref.host_zeros_like(p)
    out = []
    for s in range(steps):
        ce, g, aux = ref.loss_and_grads(p, batches[s], cfg)
        out.append((ce, {k: np.asarray(x) for k, x in g.items()}, aux))
        # linear warm-up over four steps, written out
        p, m, v = ref.adam_step(p, m, v, g, s,
                                lr=c.learning_rate * (s + 1) / 4,
                                b1=c.beta1, b2=c.beta2, eps=c.adam_eps)
        p = ref.bias_step(p, g, c.expert_bias_rate)
    return out, {k: np.asarray(x) for k, x in p.items()}


@pytest.fixture(scope="module")
def trained(mesh):
    """A float32 trainer after three steps, beside the reference's three
    steps from the same start on the same packed batches."""
    # another module's steps of the same ``fn`` (the first model's, with
    # shared experts) would merge into this module's map of scopes when
    # a worker ran them first: read this module's own compiles
    profiling._OP_SCOPES.pop("superstep.lm_superstep", None)
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
    steps, final = reference_steps(c, batches)
    return {"config": c, "app": app, "start": start, "first": first,
            "batches": batches, "steps": steps, "counters": counted,
            "final": final}


def test_start_values_are_the_reference_s(trained):
    c = trained["config"]
    want = start_tables(c)
    assert set(trained["start"]) == set(want) and "head" not in want
    for name, got in trained["start"].items():
        np.testing.assert_allclose(got, want[name], rtol=1e-5, atol=1e-8)
    assert all((want[n] == 0).all() for n in want if is_bias(n))
    assert sum(map(is_bias, want)) == 3


def test_losses_of_three_steps_match_the_reference(trained):
    assert len(trained["app"].loss_history) == 3
    for (ce, balance), (want_ce, _, _) in zip(trained["app"].loss_history,
                                             trained["steps"]):
        assert ce == pytest.approx(want_ce, rel=2e-6)
        assert balance == 0.0


def test_every_table_s_delta_matches_the_reference(trained):
    c = trained["config"]
    aux, grads = trained["first"]
    want = as_tables(c, trained["steps"][0][1])
    assert set(grads) == set(want)
    for name in want:
        got = np.asarray(grads[name])[:want[name].shape[0]]
        if is_bias(name):       # a sign rule: exact
            assert np.array_equal(got, want[name]), name
            assert set(np.unique(got)) <= {-1.0, 0.0, 1.0}
        else:
            assert gap(got, want[name]) < 5e-5, name
    step = jax.device_get(trained["app"].aux_tail[0])
    for name, norm in zip(want, step["grad_norms"]):
        assert norm == pytest.approx(np.linalg.norm(want[name]), rel=2e-5)
    assert gap(step["probe_embed"], want["embed"][:256]) < 5e-5
    # the first short convolution behind experts, entry by entry
    assert gap(step["probe_conv_in"],
               trained["steps"][0][1]["l2.conv_in"]) < 5e-5
    # the experts each real token chose, and their exact counts
    real = (trained["batches"][0]["doc"] > 0).reshape(-1)
    assert np.array_equal(
        np.sort(step["chosen"], -1)[:, real],
        np.sort(trained["steps"][0][2]["chosen"], -1)[:, real])
    assert np.array_equal(step["counts"], trained["steps"][0][2]["counts"])


def test_tables_and_bias_tables_after_three_steps_match_the_reference(
        trained):
    c = trained["config"]
    start = named_parameters(c, trained["start"])
    got = named_parameters(c, {
        n: np.asarray(t.raw()) for n, t in trained["app"].tables.items()})
    for role, want in trained["final"].items():
        if is_bias(role):
            # three steps of +-1e-3 (or 0) an entry, by the same rule
            np.testing.assert_allclose(got[role], want, rtol=0, atol=1e-9)
            assert np.abs(got[role]).max() <= 3e-3 + 1e-9
            assert np.abs(got[role]).max() > 0
        else:
            assert gap(got[role] - start[role],
                       want - start[role]) < 2e-3, role
    app = trained["app"]
    assert all(t.default_option.step == 3 for t in app.tables.values())
    # every table steps through its own updater: adam, and sgd at gamma
    for name, table in app.tables.items():
        assert table.updater.name == ("sgd" if is_bias(name) else "adam")
        if is_bias(name):
            assert table.default_option.learning_rate == c.expert_bias_rate


def test_the_tied_table_takes_one_adam_step_on_the_sum_of_its_gradients(
        trained, mesh):
    """``embed`` is gathered from AND multiplied by: its delta is the
    head's dense gradient plus the tokens' scatter-added rows, and Adam
    steps once on the sum — not twice, and not on either alone."""
    c, batch = trained["config"], trained["batches"][0]
    _, want, _ = trained["steps"][0]
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    _, rows_only, _ = ref.loss_and_grads(p, batch, ref_config(c), "untied")
    rows_only = np.asarray(rows_only["embed"])
    head_only = want["embed"] - rows_only
    # rows no token of the step names still get the head's gradient
    absent = np.setdiff1d(np.arange(c.vocab_size), batch["tokens"])
    assert len(absent) and (rows_only[absent] == 0).all()
    assert (np.abs(head_only[absent]).sum(-1) > 0).all()
    got = np.asarray(trained["first"][1]["embed"])[:c.vocab_size]
    assert gap(got, want["embed"]) < 5e-5
    assert gap(got, rows_only) > 0.1 and gap(got, head_only) > 0.1
    # one step from zero moments at rate lr / 4 moves an entry by
    # lr / 4 * sign(sum): two steps would move it twice as far
    one = TransformerLM(c, mesh=mesh)
    before = np.asarray(one.tables["embed"].raw())[:c.vocab_size]
    one._fused((), one._place(batch))
    moved = np.asarray(one.tables["embed"].raw())[:c.vocab_size] - before
    big = np.abs(want["embed"]) > 1e-4
    np.testing.assert_allclose(
        moved[big], -c.learning_rate * np.sign(want["embed"][big]),
        rtol=1e-3)
    assert one.tables["embed"].default_option.step == 1


def test_the_reference_s_layer_at_a_time_gradient_is_the_objective_s(
        trained):
    """One ``jax.grad`` over the whole step's objective gives the loss
    and gradients the reference chains by hand."""
    c = trained["config"]
    cfg = ref_config(c)
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    tokens, doc, pos = (jnp.asarray(trained["batches"][0][k])
                        for k in ("tokens", "doc", "pos"))
    n_pred = jnp.sum((doc[:, 1:] == doc[:, :-1]) & (doc[:, :-1] > 0))

    def objective(p):
        total = 0.0
        for b in range(tokens.shape[0]):
            x = p["embed"][tokens[b]]
            for i in range(c.num_hidden_layers):
                x, _ = ref.layer(ref.layer_tensors(p, i), x, doc[b],
                                 pos[b], cfg)
            total += ref.head_loss(x, p["final_norm"], p["embed"],
                                   tokens[b], doc[b], cfg) / n_pred
        return total

    want_ce, got, _ = trained["steps"][0]
    with jax.default_matmul_precision("highest"):
        loss, want = jax.value_and_grad(objective)(p)
    assert float(loss) == pytest.approx(want_ce, rel=1e-6)
    assert set(got) == set(want)
    for role, g in want.items():
        if is_bias(role):       # no derivative reaches a bias
            assert float(jnp.abs(g).max()) == 0.0
            continue
        np.testing.assert_allclose(got[role], g, rtol=2e-4, atol=2e-7,
                                   err_msg=role)


def test_bfloat16_products_stay_near_the_reference(mesh, trained):
    c = dataclasses.replace(trained["config"], compute_dtype="bfloat16")
    app = TransformerLM(c, mesh=mesh)
    aux, grads = app.gradients(trained["batches"][0])
    want_ce, want, _ = trained["steps"][0]
    assert float(aux["ce"]) == pytest.approx(want_ce, rel=2e-3)
    want = as_tables(c, want)
    for name in want:
        if not is_bias(name):
            got = np.asarray(grads[name])[:want[name].shape[0]]
            assert 1e-4 < gap(got, want[name]) < 0.15, name


def test_changing_one_document_leaves_the_others_bit_equal(trained):
    """Taps, rotary positions and scores all stop at a document's
    boundary."""
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


@pytest.mark.parametrize("variant", ref.VARIANTS + ("bias_frozen",))
def test_a_control_of_the_reference_is_not_the_reference(trained, variant):
    """Each deliberately wrong reference moves a step's loss, its
    deltas, its routing or the bias it leaves well past what separates
    program and reference. The biases start at 0.05 a draw here: a bias
    of 0 steers nothing."""
    c, batch = trained["config"], trained["batches"][0]
    cfg = ref_config(c)
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    rng = np.random.default_rng(3)
    for k in p:
        if is_bias(k):
            p[k] = jnp.asarray(rng.choice([-0.05, 0.05], p[k].shape),
                               jnp.float32)
    want_ce, want, want_aux = ref.loss_and_grads(p, batch, cfg)
    stepped = ref.bias_step(p, want, c.expert_bias_rate)
    if variant == "bias_frozen":
        moved = max(float(jnp.abs(stepped[k] - p[k]).max()) / 1e-3
                    for k in p if is_bias(k))
    else:
        ce, grads, aux = ref.loss_and_grads(p, batch, cfg, variant)
        routing = np.mean(np.any(np.sort(aux["chosen"], -1) != np.sort(
            want_aux["chosen"], -1), axis=-1))
        moved = max(abs(ce - want_ce) / want_ce, routing,
                    max(gap(grads[k], want[k]) for k in want
                        if not is_bias(k)))
    assert moved > 1e-2, moved


# -- the shares ----------------------------------------------------------------------

def test_the_four_expert_shares_add_up_to_the_uncut_expert_layer(mesh):
    """Guide 4: each of the ``ep_size`` chips routes over all the
    router's outputs, computes the experts it holds and leaves the rest
    out; the four parts of an expert layer's output add up to the uncut
    reference's."""
    c0 = tiny(num_hidden_layers=2, layer_types=[CONV, CONV],
              num_dense_layers=0, sequences=2, mlp_chunks=1, head_chunks=1)
    E, held, D, F = c0.router_width, c0.n_routed_experts, 64, 32
    rng = np.random.default_rng(13)
    experts = rng.normal(size=(E, 3, D, F)).astype(np.float32) * 0.1
    bias = rng.choice([-0.05, 0.0, 0.05], E).astype(np.float32)
    batch = next(iter(pack_documents(documents(c0, n=40, seed=4),
                                     c0.sequences, c0.sequence_length)))
    doc = jnp.asarray(batch["doc"])
    real = (doc > 0).astype(jnp.float32)
    parts = []
    for rank in range(c0.ep_size):
        c = dataclasses.replace(c0, ep_rank=rank)
        app = TransformerLM(c, mesh=mesh)
        app.tables["l0.experts"].put_raw(jnp.asarray(
            experts[rank * held:(rank + 1) * held]))
        app.tables["l0.expert_bias"].put_raw(jnp.asarray(bias))
        tables = app._raw()
        t, norms = app._layer_tables(tables, 0)
        x = jnp.take(tables["embed"], jnp.asarray(batch["tokens"]), axis=0)
        with jax.default_matmul_precision("highest"):
            (after, _), _ = app._layer(0, x, t, norms, doc,
                                       jnp.asarray(batch["pos"]), real)
            # what the layer adds behind its mixer is the experts' part
            u = mla.rms_norm(x, norms[0], c.rms_norm_eps)
            mixed = x + app._short_conv(u, doc, t["conv_in"],
                                        t["conv_taps"], t["conv_out"])
        parts.append(np.asarray(after - mixed))
        roles = named_parameters(c, {k: np.asarray(v)
                                     for k, v in tables.items()})
    # the uncut layer: all E experts on one chip, the reference's
    cfg = dict(ref_config(c0), ep_size=1, ep_rank=0)
    p = {"router": jnp.asarray(roles["l0.router"]),
         "expert_bias": jnp.asarray(bias),
         "exp_gate": jnp.asarray(experts[:, 0]),
         "exp_up": jnp.asarray(experts[:, 1]),
         "exp_down": jnp.asarray(experts[:, 2])}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.expert_layer(
            p, ref.rms_norm(mixed[b], jnp.asarray(roles["l0.ffn_norm"]),
                            c0.rms_norm_eps), real[b], cfg)[0]
            for b in range(c0.sequences)])
    assert all(np.abs(part).max() > 0 for part in parts)
    np.testing.assert_allclose(sum(parts), want, rtol=2e-5, atol=2e-6)


def test_the_four_vocabulary_slices_logits_are_the_uncut_model_s(mesh):
    """Each of the ``vocab_shard`` chips holds a quarter of the ONE tied
    table's rows and computes its slice of the logits from the same
    residual; side by side they are the uncut reference's."""
    shards, rows = 4, 64
    c = tiny(vocab_size=rows, vocab_shard=shards, num_hidden_layers=2,
             layer_types=[CONV, FULL], sequences=2, mlp_chunks=1,
             head_chunks=1)
    rng = np.random.default_rng(9)
    uncut = rng.normal(size=(shards * rows, c.hidden_size)
                       ).astype(np.float32) * 0.1
    batch = next(iter(pack_documents(documents(c, n=40, seed=4),
                                     c.sequences, c.sequence_length)))
    app = TransformerLM(c, mesh=mesh)
    x = app.hidden_states(batch)
    final_norm = named_parameters(c, {"norms": np.asarray(
        app.tables["norms"].raw())})["final_norm"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.head_logits(
            x[b], jnp.asarray(final_norm), jnp.asarray(uncut),
            ref_config(c)) for b in range(c.sequences)])
    got = []
    for r in range(shards):
        # the tied table's logical rows (a scratch row follows them)
        held = np.zeros((rows + 1, c.hidden_size), np.float32)
        held[:rows] = uncut[r * rows:(r + 1) * rows]
        app.tables["embed"].put_raw(jnp.asarray(held))
        # the residual is the one computed above: the slice is the HEAD's
        app.hidden_states = lambda _: x
        got.append(np.asarray(app.logits(batch)))
    np.testing.assert_allclose(np.concatenate(got, -1), want, rtol=2e-5,
                               atol=2e-6)


def test_the_published_config_has_the_issue_s_parameter_counts():
    counts = ref.parameter_counts(PUBLISHED)
    assert counts["conv"] == 16_783_360
    assert counts["attention"] == 10_485_888
    assert counts["dense"] == 44_040_192
    assert counts["expert"] == 11_010_048
    assert counts["router"] == 2048 * 32 + 32
    # 18 conv + 6 attention + 2 dense + 22 x 32 experts + ONE table
    assert counts["model"] == 8_339_930_560
    # (the reference's num_experts counts the router's outputs, the
    # program's the experts held)
    cut = ref.parameter_counts(dict(CUT, num_experts=32))
    assert cut["model"] == 507_820_288
    # the same from the program's tables, as the chip's share holds them
    c = LMConfig.from_dict(dict(CUT, sequences=4, sequence_length=4096))
    c.check()
    assert c.n_routed_experts == 8 and c.router_width == 32
    assert c.first_k_dense_replace == 1 and c.rms_norm_eps == 1e-5
    assert c.n_shared_experts == 0 and c.aux_loss_alpha == 0.0
    assert c.kv_lora_rank is None and c.kv_heads == 8 and c.head_dim == 64
    assert [c.mixer(i) for i in range(5)] == [CONV, FULL, CONV, CONV, CONV]
    assert [c.is_dense(i) for i in range(5)] == [True] + [False] * 4
    shapes = table_shapes(c)
    size = lambda n: int(np.prod(shapes[n]))
    assert "head" not in shapes
    assert not any(n.endswith(".shared") for n in shapes)
    assert shapes["l2.conv_in"] == (2048, 6144)
    assert shapes["l2.conv_taps"] == (3, 2048)
    assert shapes["l1.attn"] == (2048, 2048 + 512 + 512 + 2048)
    assert shapes["l1.expert_bias"] == (32,)
    assert shapes["l1.experts"] == (8, 3, 2048, 1792)
    assert size("l0.conv_in") + size("l0.conv_taps") + size("l0.conv_out") \
        == counts["conv"]
    assert size("l1.attn") + 2 * 64 == counts["attention"]
    assert size("l0.mlp") == counts["dense"]
    assert size("l1.router") + size("l1.expert_bias") == counts["router"]
    assert norm_offsets(c) == [0, 2, 6, 8, 10, 12]
    layout = table_layout(c)
    assert list(layout) == list(shapes)
    roles = named_parameters(c, {n: np.zeros(s, np.float32)
                                 for n, s in shapes.items()
                                 if n in ("norms", "l1.attn")})
    assert roles["l1.q_norm"].shape == roles["l1.k_norm"].shape == (64,)
    assert roles["l1.w_k"].shape == roles["l1.w_v"].shape == (2048, 512)
    assert roles["l1.w_o"].shape == (2048, 2048)
    # every tensor by role, counted once, is the issue's 507,820,288
    by_role = sum(int(np.prod(np.empty(s, np.int8)[index].shape))
                  for name, s in shapes.items()
                  for index in layout[name].values())
    assert by_role == cut["model"]


def test_spans_scopes_and_counters_of_a_training_call(trained):
    snap = telemetry.snapshot()
    spans = {k for k in snap["histograms"] if k.startswith("span.seconds")}
    for name in ("lm.wait_data", "lm.place", "lm.superstep", "lm.fence",
                 "lm.setup.init_tables", "lm.docs.produce"):
        assert f"span.seconds{{name={name}}}" in spans, name
    counters = trained["counters"]
    c, batches = trained["config"], trained["batches"][:3]
    documents_trained = sum(
        len(np.unique(row[row > 0])) for b in batches for row in b["doc"])
    # three conv layers: a document's first tokens read zeroed taps
    assert counters["lm.conv.doc_starts"] == 3 * documents_trained
    # the attention counters count the ONE layer that attends
    plans = [np.asarray(mla.block_plan(jnp.asarray(b["doc"]), 16))
             for b in batches]
    assert counters["lm.attend.key_blocks"] == len(batches) * 4 * 10
    assert counters["lm.attend.key_blocks_computed"] \
        == sum(p.sum() for p in plans)
    assert "lm.gdn.chunks" not in counters
    # three expert layers' biases stepped once a step
    assert counters["moe.bias_steps"] == 3 * 3
    assert 0 < snap["gauges"]["moe.expert_bias_max_abs"] <= 3e-3 + 1e-9
    real = sum(int((b["doc"] > 0).sum()) for b in batches)
    held = sum(int(s[2]["counts"][:, 2:4].sum()) for s in trained["steps"])
    assert counters["moe.tokens_routed"] == held <= real * 3 * 3
    assert counters["moe.tokens_dropped"] == 0
    assert snap["gauges"]["moe.expert_load_max_over_mean"] >= 1.0
    held = telemetry.op_scopes()["superstep.lm_superstep"]
    assert held["module"] == "jit_run"
    named = set(held["scopes"].values())
    assert {"lm.embed_gather", "lm.embed_scatter", "lm.block_norm",
            "lm.conv.project", "lm.conv.mix", "lm.attn.project",
            "lm.attn.attend", "lm.dense_mlp", "lm.moe.route",
            "lm.moe.permute", "lm.moe.experts", "lm.head_loss",
            "lm.adam"} <= named
    assert "lm.moe.shared" not in named


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


@pytest.mark.parametrize("change, error, says", [
    (dict(conv_bias=True), NotImplementedError, "conv_bias"),
    (dict(conv_L_cache=0), ValueError, "conv_L_cache"),
    (dict(attention_bias=True), NotImplementedError, "attention_bias"),
    (dict(hidden_act="gelu"), NotImplementedError, "hidden_act"),
    (dict(model_type="lfm2"), NotImplementedError, "model_type"),
    (dict(layer_types=[CONV, "sliding_attention", CONV, CONV]),
     NotImplementedError, "layer_types entry"),
    (dict(layer_types=[CONV, "linear_attention", CONV, CONV]),
     NotImplementedError, "layer_types entry"),
    (dict(layer_types=[CONV, FULL]), ValueError, "names 2 of 4"),
    (dict(layer_types=None), NotImplementedError, "names no mixer"),
    (dict(num_key_value_heads=3), ValueError, "num_key_value_heads"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), NotImplementedError,
     "rope_scaling"),
    (dict(rope_parameters={"rope_theta": None}), NotImplementedError,
     "rope_theta"),
    (dict(hidden_size=68), ValueError, "rotary"),
    (dict(scoring_func="softmax"), NotImplementedError, "scoring_func"),
    (dict(q_lora_rank=8), NotImplementedError, "q_lora_rank"),
    (dict(kv_lora_rank=16), NotImplementedError, "kv_lora_rank"),
    (dict(num_experts_per_tok=9), ValueError, "num_experts_per_tok"),
    (dict(mlp_chunks=3), ValueError, "mlp_chunks"),
])
def test_what_this_model_does_not_build_says_so(change, error, says):
    with pytest.raises(error, match=says):
        tiny(**change).check()
    tiny().check()
