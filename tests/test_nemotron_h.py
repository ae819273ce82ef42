"""The Nemotron-H decoder — Mamba-2 layers beside relu^2 expert layers
and grouped attention without QK-norm or rotary embedding, one part a
layer behind one pre-norm — through ``apps/transformer_lm.py`` against
the plain float32 reference (``perf/reference/nemotron_h.py``) on
seeded weights at tiny widths: the chunked SSD scan against the
token-by-token recurrence, the whole mixer, the relu^2 experts' written
out backward, the expert-parallel share, the pattern's parsing, the
trainer's losses, gradients and Adam steps, the SwiGLU paths as they
were, the published parameter counts, and the spans, scopes and
counters of a training call."""

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
from multiverso_tpu.apps import transformer_lm as lm           # noqa: E402
from multiverso_tpu.apps.transformer_lm import (               # noqa: E402
    EXPERTS, FULL, MLP, SSM, LMConfig, TransformerLM, is_bias,
    named_parameters, table_shapes)
from multiverso_tpu.data.packing import pack_documents         # noqa: E402
from multiverso_tpu.ops import gated_delta as gdn              # noqa: E402
from multiverso_tpu.ops import moe, ssd                        # noqa: E402
from multiverso_tpu.telemetry import profiling                 # noqa: E402
from perf.reference import nemotron_h as ref                   # noqa: E402

# the catalog's config of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
# the cut: published layers 0-8, one expert-parallel rank of 16
CUT = dict(PUBLISHED, num_hidden_layers=9, n_routed_experts=8, ep_size=16,
           ep_rank=0, vocab_size=16384, vocab_shard=8)


def tiny(**over) -> LMConfig:
    """The published keys under their published names, at tiny widths:
    4 ssm heads of 8 in 2 groups of a state of 16, 2 experts held of 8
    router outputs, 4 query heads on 2 key-value heads of 16 dims, every
    kind of layer once or twice."""
    base = dict(
        model_type="nemotron_h", hybrid_override_pattern="MEM*E-",
        num_hidden_layers=6, hidden_size=48, mamba_num_heads=4,
        mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
        chunk_size=16, use_conv_bias=True, mlp_hidden_act="relu2",
        intermediate_size=40, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=32, n_shared_experts=1,
        n_routed_experts=2, ep_size=4, ep_rank=1, num_experts_per_tok=3,
        norm_topk_prob=True, routed_scaling_factor=2.5,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        layer_norm_epsilon=1e-5, norm_eps=1e-5, rope_theta=10000,
        rescale_prenorm_residual=True, time_step_min=0.001,
        time_step_max=0.1, time_step_floor=1e-4, vocab_size=256,
        sequences=4, sequence_length=64, attention_block=16,
        expert_chunk_rows=32, mlp_chunks=2, head_chunks=2, ssm_chunks=2,
        attention_chunks=2, seed=2147483659, init_std=0.05,
        learning_rate=1e-2, warmup_steps=4, expert_bias_rate=1e-3,
        compute_dtype="float32")
    return LMConfig.from_dict(dict(base, **over))


def ref_config(c: LMConfig) -> dict:
    """The reference's own keys (the published names;
    ``n_routed_experts`` counts the router's outputs)."""
    return {"hidden_size": c.hidden_size,
            "num_hidden_layers": c.num_hidden_layers,
            "hybrid_override_pattern": c.hybrid_override_pattern,
            "mamba_num_heads": c.mamba_num_heads,
            "mamba_head_dim": c.mamba_head_dim,
            "ssm_state_size": c.ssm_state_size, "n_groups": c.n_groups,
            "conv_kernel": c.conv_kernel,
            "time_step_min": c.time_step_min,
            "time_step_max": c.time_step_max,
            "time_step_floor": c.time_step_floor,
            "intermediate_size": c.intermediate_size,
            "moe_intermediate_size": c.moe_intermediate_size,
            "moe_shared_expert_intermediate_size":
                c.moe_shared_expert_intermediate_size,
            "n_routed_experts": c.router_width, "ep_size": c.ep_size,
            "ep_rank": c.ep_rank,
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": c.norm_topk_prob,
            "routed_scaling_factor": c.routed_scaling_factor,
            "num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.kv_heads, "head_dim": c.head_dim,
            "norm_eps": c.rms_norm_eps, "vocab_size": c.vocab_size}


def documents(c: LMConfig, n=200, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, c.vocab_size, rng.integers(1, 50))
            for _ in range(n)]


def start_tables(c: LMConfig) -> dict:
    """Every table's start, drawn by the reference's own code."""
    out = {}
    for i, (n, s) in enumerate(table_shapes(c).items()):
        out[n] = np.ones(s, np.float32) if n == "norms" \
            or n.endswith(".ssm_norm") \
            else np.zeros(s, np.float32) if is_bias(n) \
            else np.asarray(ref.init_decay(c.seed, i, s[1], ref_config(c))) \
            if n.endswith(".ssm_decay") \
            else np.asarray(ref.init_conv(c.seed, i, s, c.conv_kernel)) \
            if n.endswith(".ssm_conv") \
            else np.asarray(ref.init_normal(c.seed, i, s,
                                            lm.start_std(c, n)))
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


# two sequences each, chunks of 16: one document; documents that start
# mid-chunk; a chunk (16-31) that holds three documents; boundaries on a
# chunk's edge, a one-token document and a padded tail
DOCS = {
    "one_document": np.stack([doc_ids(64), doc_ids(64)]),
    "starts_mid_chunk": np.stack([doc_ids(5, 30, 29),
                                  doc_ids(9, 13, 7, 11, 19)]),
    "three_in_a_chunk": np.stack([doc_ids(18, 4, 7, 35),
                                  doc_ids(20, 3, 3, 30)]),
    "edges_and_a_tail": np.stack([doc_ids(16, 32, 1, 7),
                                  doc_ids(1, 15, 16, 16)]),
}


@pytest.fixture(scope="module")
def mesh():
    return core.init(devices=jax.devices()[:1], data_parallel=1,
                     model_parallel=1)


# -- the chunked scan against the token-by-token recurrence ----------------------

SHAPE = ssd.SSDShape(heads=4, head_dim=8, state=16, groups=2, taps=4,
                     eps=1e-5, chunk=16, dtype="float32")


def scan_operands(seed=2, S=64, shape=SHAPE):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    H, P, N, G = shape.heads, shape.head_dim, shape.state, shape.groups
    # steps of the family's range and decay rates of its start: a
    # chunk's log decay runs to tens, its decay to 1e-9
    return {"x": f32(2, S, H * P), "bc": f32(2, S, 2 * G * N) * 0.5,
            "dt": jax.nn.softplus(f32(2, S, H) - 2.0),
            "a_log": jnp.log(jnp.asarray(rng.uniform(1, 16, H),
                                         jnp.float32))}


def plain_scan(o, doc, variant=None):
    """The reference's token-by-token recurrence, a sequence at a time."""
    H, P, N, G = SHAPE.heads, SHAPE.head_dim, SHAPE.state, SHAPE.groups
    out = []
    for b in range(doc.shape[0]):
        S = doc.shape[1]
        per_head = lambda v: jnp.repeat(v.reshape(S, G, N), H // G, axis=1)
        start = jnp.concatenate([jnp.ones((1,), bool),
                                 doc[b, 1:] != doc[b, :-1]])
        out.append(ref.recurrence(
            o["x"][b].reshape(S, H, P), per_head(o["bc"][b, :, :G * N]),
            per_head(o["bc"][b, :, G * N:]), o["dt"][b],
            -jnp.exp(o["a_log"]), start, variant))
    return jnp.stack(out)


def our_scan(o, doc):
    return ssd.scan(o["x"], o["bc"], o["dt"], o["a_log"], doc, SHAPE,
                    interpret=True)


@pytest.mark.parametrize("docs", sorted(DOCS))
def test_the_chunked_scan_is_the_token_recurrence(docs):
    """Forward and every operand's gradient, float32: what separates the
    two forms is the order of float32 sums (a chunk's products against a
    token's), 1e-6 of the output and 1e-5 of a gradient at most."""
    doc = jnp.asarray(DOCS[docs], jnp.int32)
    o = scan_operands()
    weight = jnp.asarray(np.random.default_rng(5).normal(
        size=(2, 64, 4, 8)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, want = our_scan(o, doc), plain_scan(o, doc)
        assert gap(got, want) < 1e-6
        g = jax.grad(lambda o: jnp.sum(our_scan(o, doc) * weight))(o)
        w = jax.grad(lambda o: jnp.sum(plain_scan(o, doc) * weight))(o)
    for name in o:
        assert gap(g[name], w[name]) < 1e-5, name
    # a document's first token holds its own write alone: the state
    # before it is gone
    first = np.flatnonzero(np.diff(DOCS[docs][1], prepend=-1) != 0)
    if len(first) > 1:
        carried = plain_scan(o, doc, "no_restart")
        assert gap(carried[1, first[1:]], want[1, first[1:]]) > 1e-2


def test_a_decay_summed_in_bfloat16_fails_the_scan_s_tolerance(monkeypatch):
    """The control the tolerance above must refuse: the chunk's running
    sums of the log decay rounded to bfloat16."""
    doc = jnp.asarray(DOCS["starts_mid_chunk"], jnp.int32)
    o = scan_operands()
    with jax.default_matmul_precision("highest"):
        want = plain_scan(o, doc)
        assert gap(our_scan(o, doc), want) < 1e-6
        summed = gdn._running_sum
        monkeypatch.setattr(gdn, "_running_sum", lambda g, start: summed(
            g, start).astype(jnp.bfloat16).astype(jnp.float32))
        assert gap(our_scan(o, doc), want) > 1e-3


def chunk_terms(x, bc, dt, a_log, doc, shape):
    """The chunked scan's own terms in plain XLA over every chunk at
    once, as the program formed them before its kernels did: each
    chunk's ``y_local`` [Nc, B, H, C, P] float32, and what crosses from
    chunk to chunk — ``q_in``, ``k_out`` [Nc, B, H, C, N], ``u``
    [Nc, B, H, C, P] and ``keep`` [Nc, B, H, 1, 1]."""
    H, P, N, G = shape.heads, shape.head_dim, shape.state, shape.groups
    dtype = shape.dtype
    Bs, S, _ = x.shape
    C = min(shape.chunk, S)
    Nc = S // C

    def chunks(a):      # [B, S, n, ...] -> [Nc, B, n, C, ...]
        a = a.reshape(Bs, Nc, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    xs = chunks(x.reshape(Bs, S, H, P))
    b = chunks(bc[..., :G * N].reshape(Bs, S, G, N))
    c = chunks(bc[..., G * N:].reshape(Bs, S, G, N))
    dt_c = chunks(dt)                                   # [Nc, B, H, C]
    in_chunks = lambda a: jnp.moveaxis(a.reshape(Bs, Nc, C), 1, 0)
    seg = in_chunks(gdn.segments(doc))                  # [Nc, B, C]
    entering = jnp.concatenate(
        [jnp.zeros_like(seg[:1, :, -1]), seg[:-1, :, -1]], 0)
    carried = (seg == entering[..., None])[:, :, None]  # [Nc, B, 1, C]
    same = (seg[..., :, None] == seg[..., None, :])[:, :, None]
    at = jnp.arange(C)
    upto = at[:, None] >= at[None]                      # j <= i
    Lsum = gdn._running_sum(dt_c * -jnp.exp(a_log)[:, None],
                            in_chunks(gdn.starts(doc))[:, :, None])
    decay = jnp.exp(jnp.where(same & upto,
                              Lsum[..., :, None] - Lsum[..., None, :],
                              gdn._GONE))
    from_state = jnp.where(carried, jnp.exp(Lsum), 0.0)
    to_end = decay[..., -1, :]
    per_head = lambda a: jnp.repeat(a, H // G, axis=2)
    cb = jnp.einsum("...in,...jn->...ij", c.astype(dtype), b.astype(dtype),
                    preferred_element_type=jnp.float32)  # [Nc, B, G, C, C]
    u = (dt_c[..., None] * xs).astype(dtype)
    attn = (per_head(cb) * decay).astype(dtype)
    y_local = jnp.einsum("...ij,...jp->...ip", attn, u,
                         preferred_element_type=jnp.float32)
    q_in = (per_head(c) * from_state[..., None]).astype(dtype)
    k_out = (per_head(b) * to_end[..., None]).astype(dtype)
    return y_local, q_in, k_out, u, from_state[..., -1, None, None]


def xla_scan(o, doc, shape):
    """The oracle: :func:`chunk_terms`, then a plain scan over the
    chunks with the state [B, H, N, P] float32 — a chunk puts out
    ``q_in S`` and leaves ``keep S + k_out^T u``."""
    y_local, *crossing = chunk_terms(o["x"], o["bc"], o["dt"], o["a_log"],
                                     doc, shape)
    Nc, Bs, H, C, P = y_local.shape

    def step(state, chunk):
        q_in, k_out, u, keep = chunk
        read = jnp.einsum("...in,...np->...ip", q_in,
                          state.astype(q_in.dtype),
                          preferred_element_type=jnp.float32)
        return keep * state + jnp.einsum(
            "...jn,...jp->...np", k_out, u,
            preferred_element_type=jnp.float32), read

    _, read = jax.lax.scan(
        step, jnp.zeros((Bs, H, shape.state, P), jnp.float32), crossing)
    y = y_local + read
    return jnp.moveaxis(jnp.moveaxis(y, 0, 2), 1, 3).reshape(
        Bs, Nc * C, H, P)


# bfloat16 products: the kernels round a cotangent before a product and
# fold the decay into the other operand than the XLA form, within a
# rounding of it (output 1.1e-3, gradients 3.8e-3 and a_log's 5.7e-3 at
# most); the decay's running sums rounded to bfloat16 read 5.8e-3,
# 5.7e-3 and 1.9e-2 at least
BF16_TOLERANCE = {"y": 3e-3, "x": 5e-3, "bc": 5e-3, "dt": 5e-3,
                  "a_log": 1e-2}


@pytest.mark.parametrize("docs, dtype, groups", [
    (d, t, 2) for d in sorted(DOCS) for t in ("float32", "bfloat16")]
    + [("three_in_a_chunk", "float32", 4)])
def test_the_scan_s_kernels_are_the_xla_chunk_form(docs, dtype, groups,
                                                    monkeypatch):
    """The scan's kernels against the XLA chunk form they replace, the
    output and every operand's gradient: float32 to the order of sums;
    bfloat16 within a tolerance the control (the decay summed in
    bfloat16) fails; and one head a group, a grid step's fewest."""
    shape = SHAPE._replace(dtype=dtype, groups=groups)
    doc = jnp.asarray(DOCS[docs], jnp.int32)
    o = scan_operands(shape=shape)
    weight = jnp.asarray(np.random.default_rng(5).normal(
        size=(2, 64, 4, 8)), jnp.float32)
    ours = lambda o: ssd.scan(o["x"], o["bc"], o["dt"], o["a_log"], doc,
                              shape, interpret=True)

    def gaps(want, want_grad):
        g = jax.grad(lambda o: jnp.sum(ours(o) * weight))(o)
        return dict(y=gap(ours(o), want),
                    **{k: gap(g[k], want_grad[k]) for k in o})

    with jax.default_matmul_precision("highest"):
        want = xla_scan(o, doc, shape)
        want_grad = jax.grad(lambda o: jnp.sum(xla_scan(o, doc, shape)
                                               * weight))(o)
        got = gaps(want, want_grad)
        if dtype == "bfloat16":
            summed = gdn._running_sum
            monkeypatch.setattr(gdn, "_running_sum", lambda g, start: summed(
                g, start).astype(jnp.bfloat16).astype(jnp.float32))
            control = gaps(want, want_grad)
    for name, value in got.items():
        if dtype == "float32":
            assert value < (1e-6 if name == "y" else 1e-5), name
        else:
            assert value < BF16_TOLERANCE[name] < control[name], name


def mixer_operands(seed=3, D=24):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    H, P, N, G = SHAPE.heads, SHAPE.head_dim, SHAPE.state, SHAPE.groups
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), H))
    decay = np.stack([np.log(rng.uniform(1, 16, H)),
                      dt + np.log(-np.expm1(-dt)), rng.normal(1, 0.1, H)])
    return {"u": f32(2, 64, D), "w_in": f32(D, SHAPE.in_width) * 0.3,
            "conv": f32(SHAPE.taps + 1, SHAPE.conv_width) * 0.5,
            "decay": jnp.asarray(decay, jnp.float32),
            "norm": 1 + 0.1 * f32(H * P), "w_out": f32(D, H * P) * 0.3}


def test_the_whole_mixer_and_its_gradients_are_the_reference_s():
    """Projection, filter with its bias, the scan, ``D x``, the gated
    norm over groups and the output projection, against the reference's
    M layer part by part from the same tensors."""
    doc = jnp.asarray(DOCS["three_in_a_chunk"], jnp.int32)
    o = mixer_operands()
    H, P, GN = SHAPE.heads, SHAPE.head_dim, SHAPE.groups * SHAPE.state
    cfg = {"mamba_num_heads": H, "mamba_head_dim": P,
           "ssm_state_size": SHAPE.state, "n_groups": SHAPE.groups,
           "norm_eps": 1e-5}
    weight = jnp.asarray(np.random.default_rng(6).normal(size=(2, 64, 24)),
                         jnp.float32)

    def ours(o):
        return ssd.mix(o["u"], doc, o["w_in"], o["conv"], o["decay"],
                       o["norm"], o["w_out"], SHAPE, interpret=True)

    def plain(o):
        cut = np.cumsum([H * P, H * P, GN, GN])
        w = jnp.split(o["w_in"], cut, axis=1)
        p = dict(zip(("w_z", "w_x", "w_B", "w_C", "w_dt"), w),
                 conv_w=o["conv"][:-1], conv_b=o["conv"][-1],
                 a_log=o["decay"][0], dt_bias=o["decay"][1],
                 d_skip=o["decay"][2], ssm_norm=o["norm"],
                 w_out=o["w_out"])
        return jnp.stack([ref.mamba(p, o["u"][b], doc[b], cfg)
                          for b in range(2)])

    with jax.default_matmul_precision("highest"):
        assert gap(ours(o), plain(o)) < 2e-6
        g = jax.grad(lambda o: jnp.sum(ours(o) * weight))(o)
        w = jax.grad(lambda o: jnp.sum(plain(o) * weight))(o)
    for name in o:
        assert gap(g[name], w[name]) < 2e-5, name


# -- the relu^2 experts ----------------------------------------------------------------

def plan_operands(seed=4, T=96, D=16, F=24, E=8, held=3, k=2, first=2):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(held, 2, D, F)) * 0.3, jnp.float32)
    top_e = jnp.asarray(np.stack([rng.choice(E, k, replace=False)
                                  for _ in range(T)]), jnp.int32)
    top_s = jnp.asarray(rng.uniform(0.1, 1, (T, k)), jnp.float32)
    real = jnp.asarray(rng.uniform(size=T) > 0.1, jnp.float32)
    plan = moe.plan(top_e, real, first=first, held=held, chunk_rows=32)
    return h, w, top_s, top_e, real, plan


def plain_relu2_experts(h, w, top_s, top_e, real, first):
    """Every held expert on every token, weight 0 where it was not
    chosen."""
    y = jnp.zeros_like(h)
    for e in range(w.shape[0]):
        took = (top_e == first + e) & (real[:, None] > 0)
        weight = jnp.sum(jnp.where(took, top_s, 0.0), 1)
        y = y + weight[:, None] * (jnp.square(jax.nn.relu(h @ w[e, 0]))
                                   @ w[e, 1].T)
    return y


def test_the_relu2_experts_backward_is_jax_grad():
    """The written-out backward (``d_u = 2 relu(u) d_a``) against
    ``jax.grad`` of the plain form: float32 products, several blocks of
    sorted rows, padding and experts held elsewhere left out."""
    h, w, top_s, top_e, real, plan = plan_operands()
    weight = jnp.asarray(np.random.default_rng(7).normal(size=h.shape),
                         jnp.float32)

    def ours(h, w, top_s):
        y, _ = moe.routed_experts(h, w, moe.row_weights(top_s,
                                                        plan.row_src),
                                  plan, 32, jnp.float32, "relu2")
        return jnp.sum(y * weight)

    def plain(h, w, top_s):
        return jnp.sum(plain_relu2_experts(h, w, top_s, top_e, real, 2)
                       * weight)

    with jax.default_matmul_precision("highest"):
        assert ours(h, w, top_s) == pytest.approx(float(plain(h, w, top_s)),
                                                  rel=1e-5)
        got = jax.grad(ours, (0, 1, 2))(h, w, top_s)
        want = jax.grad(plain, (0, 1, 2))(h, w, top_s)
    for name, g, v in zip(("h", "w", "top_s"), got, want):
        assert g.shape == v.shape
        assert gap(g, v) < 1e-5, name


def _parent_swiglu_rows(x, wb, sizes):
    """The SwiGLU expert as the parent wrote it, op for op."""
    g = moe._gmm(x, wb[:, 0], sizes)
    u = moe._gmm(x, wb[:, 1], sizes)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    return g, u, a, moe._gmm(a, wb[:, 2], sizes, transpose_rhs=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_swiglu_paths_are_the_parent_s_bit_for_bit(dtype, mesh):
    """A dsv2-shaped layer's SwiGLU experts — three parts, the default
    activation — give the parent's loop's rows and gradients bit for
    bit, and the dense and shared feed-forward the parent's product."""
    h, _, top_s, top_e, real, plan = plan_operands()
    w = jnp.asarray(np.random.default_rng(8).normal(size=(3, 3, 16, 24))
                    * 0.3, jnp.float32)
    rw = moe.row_weights(top_s, plan.row_src)
    d_y = jnp.asarray(np.random.default_rng(9).normal(size=h.shape),
                      jnp.float32)

    @jax.jit
    def parent(h, w, rw):
        """The parent's forward and backward loops, verbatim."""
        wb = moe._cast_held(w, dtype)

        def forward(c, carry):
            y, done = carry
            tok, r, valid, sizes = moe._block(c, 32, plan, rw)
            x = moe._gather_rows(h, tok).astype(dtype)
            out = _parent_swiglu_rows(x, wb, sizes)[3]
            return (moe._scatter_rows(y, tok, moe._held_rows(valid, out, r)),
                    done + jnp.sum(sizes))

        def backward(c, carry):
            d_h, d_w, d_rw = carry
            tok, r, valid, sizes = moe._block(c, 32, plan, rw)
            x = moe._gather_rows(h, tok).astype(dtype)
            g, u, a, out = _parent_swiglu_rows(x, wb, sizes)
            d_rows = moe._held_rows(valid, moe._gather_rows(d_y, tok))
            d_x, d_wc = moe._experts_backward(x, wb, sizes, g, u, a, r,
                                              d_rows)
            d_w, d_rw = moe._accumulate(d_w, d_rw, d_wc,
                                        moe._held_rows(valid, out), d_rows,
                                        c * 32)
            return (moe._scatter_rows(d_h, tok, moe._held_rows(valid, d_x)),
                    d_w, d_rw)

        n = moe._chunks(plan, 32)
        y, _ = jax.lax.fori_loop(0, n, forward,
                                 (jnp.zeros(h.shape, jnp.float32),
                                  jnp.zeros((), jnp.int32)))
        one = jnp.zeros(w.shape[:1] + w.shape[2:], jnp.float32)
        d_h, d_w, _ = jax.lax.fori_loop(
            0, n, backward, (jnp.zeros(h.shape, jnp.float32),
                             (one, one, one),
                             jnp.zeros(rw.shape, jnp.float32)))
        return y, d_h, moe._stack_gradients(d_w)

    @jax.jit
    def ours(h, w, rw):
        (y, _), vjp = jax.vjp(lambda h, w: moe.routed_experts(
            h, w, rw, plan, 32, dtype), h, w)
        d_h, d_w = vjp((d_y, jnp.zeros((), jnp.int32)))
        return y, d_h, d_w

    for got, want in zip(ours(h, w, rw), parent(h, w, rw)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    hb = h.reshape(2, 48, 16).astype(dtype)
    for group in (lm._dense_mlp_group, lm._shared_experts_group):
        assert np.array_equal(np.asarray(group(hb, w[0])),
                              np.asarray(lm._swiglu(hb, w[0])))
    c = LMConfig(num_hidden_layers=2, n_routed_experts=2)
    assert c.mlp_act == "silu" and table_shapes(c)["l1.experts"][1] == 3
    assert table_shapes(c)["l0.mlp"][0] == table_shapes(c)["l1.shared"][0] \
        == 3


# -- the expert-parallel share ----------------------------------------------------------

def test_the_four_expert_shares_and_one_shared_expert_add_up_to_the_uncut_layer(
        mesh):
    """Expert parallelism: each of the ``ep_size`` chips routes over all the
    router's outputs and computes the experts it holds; the four chips'
    routed parts plus the shared expert counted once add up to the
    uncut reference layer's output."""
    c0 = tiny(hybrid_override_pattern="EM", num_hidden_layers=2,
              sequences=2, mlp_chunks=1, head_chunks=1, ssm_chunks=1)
    E, held, D, F = c0.router_width, c0.n_routed_experts, 48, 24
    rng = np.random.default_rng(13)
    experts = rng.normal(size=(E, 2, D, F)).astype(np.float32) * 0.1
    bias = rng.choice([-0.05, 0.0, 0.05], E).astype(np.float32)
    batch = next(iter(pack_documents(documents(c0, n=40, seed=4),
                                     c0.sequences, c0.sequence_length)))
    doc = jnp.asarray(batch["doc"])
    real = (doc > 0).astype(jnp.float32)
    routed, shared = [], None
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
            u = lm.mla.rms_norm(x, norms[0], c.rms_norm_eps)
            shared = lm._shared_relu2_group(u, t["shared"])
        routed.append(np.asarray(after - x - shared))
        roles = named_parameters(c, {k: np.asarray(v)
                                     for k, v in tables.items()})
    cfg = dict(ref_config(c0), ep_size=1, ep_rank=0)
    p = {"router": jnp.asarray(roles["l0.router"]),
         "expert_bias": jnp.asarray(bias),
         "exp_up": jnp.asarray(experts[:, 0]),
         "exp_down": jnp.asarray(experts[:, 1]),
         "shared_up": jnp.asarray(roles["l0.shared_up"]),
         "shared_down": jnp.asarray(roles["l0.shared_down"]),
         "norm": jnp.asarray(roles["l0.norm"])}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.layer(p, x[b], doc[b], cfg)[0] - x[b]
                          for b in range(c0.sequences)])
    assert all(np.abs(part).max() > 0 for part in routed)
    np.testing.assert_allclose(sum(routed) + np.asarray(shared), want,
                               rtol=2e-5, atol=2e-6)


# -- the pattern -------------------------------------------------------------------------

@pytest.mark.parametrize("pattern, kinds", [
    ("MEM*E-", [SSM, EXPERTS, SSM, FULL, EXPERTS, MLP]),
    ("-*M", [MLP, FULL, SSM]),
    (PUBLISHED["hybrid_override_pattern"][:9],
     [SSM, EXPERTS, SSM, EXPERTS, SSM, FULL, EXPERTS, SSM, EXPERTS]),
])
def test_the_pattern_names_one_kind_a_layer(pattern, kinds):
    c = tiny(hybrid_override_pattern=pattern, num_hidden_layers=len(kinds))
    c.check()
    assert [c.mixer(i) for i in range(len(kinds))] == kinds
    assert [c.is_expert(i) for i in range(len(kinds))] == \
        [k == EXPERTS for k in kinds]
    assert [c.is_dense(i) for i in range(len(kinds))] == \
        [k == MLP for k in kinds]
    assert lm.norm_offsets(c) == list(range(len(kinds) + 1))
    shapes = table_shapes(c)
    for i, kind in enumerate(kinds):
        mine = {n.split(".", 1)[1] for n in shapes
                if n.startswith(f"l{i}.")}
        assert mine == {SSM: {"ssm_in", "ssm_conv", "ssm_decay",
                              "ssm_norm", "ssm_out"},
                        FULL: {"attn"}, MLP: {"mlp"},
                        EXPERTS: {"router", "expert_bias", "shared",
                                  "experts"}}[kind], i


@pytest.mark.parametrize("change, error, says", [
    (dict(hybrid_override_pattern="MEM*EX"), NotImplementedError,
     "letter 'X'"),
    (dict(hybrid_override_pattern="MEM*E=", num_hidden_layers=6),
     NotImplementedError, "letter '='"),
    (dict(hybrid_override_pattern="MEM"), ValueError, "names 3 of 6"),
    (dict(mlp_hidden_act="silu"), NotImplementedError, "mlp_hidden_act"),
    (dict(mamba_proj_bias=True), NotImplementedError, "mamba_proj_bias"),
    (dict(use_conv_bias=False), NotImplementedError, "use_conv_bias"),
    (dict(mamba_hidden_act="gelu"), NotImplementedError,
     "mamba_hidden_act"),
    (dict(residual_in_fp32=True), NotImplementedError, "residual_in_fp32"),
    (dict(n_group=8, topk_group=4), NotImplementedError, "group-limited"),
    (dict(n_groups=3), ValueError, "n_groups"),
    (dict(chunk_size=24), ValueError, "chunk_size"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), NotImplementedError,
     "rotary"),
    (dict(layer_types=["conv"] * 6), NotImplementedError, "one way"),
    (dict(num_dense_layers=1), NotImplementedError, "first_k_dense"),
    (dict(num_key_value_heads=3), ValueError, "num_key_value_heads"),
    (dict(scoring_func="softmax"), NotImplementedError, "scoring_func"),
    (dict(ssm_chunks=3), ValueError, "ssm_chunks"),
])
def test_what_this_model_does_not_build_says_so(change, error, says):
    with pytest.raises(error, match=says):
        tiny(**change).check()
    tiny().check()


def test_the_other_blocks_refuse_the_pattern_and_relu2():
    with pytest.raises(NotImplementedError, match="hybrid_override"):
        LMConfig(hybrid_override_pattern="MM").check()
    with pytest.raises(NotImplementedError, match="mlp_hidden_act"):
        LMConfig(mlp_hidden_act="relu2").check()
    c = tiny()
    assert c.use_expert_bias and c.scoring_func == "sigmoid"
    assert c.rms_norm_eps == 1e-5 and c.shared_width == 32


# -- the trainer against the reference ------------------------------------------------

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
    profiling._OP_SCOPES.pop("superstep.lm_superstep", None)
    c = tiny()
    docs = documents(c)
    app = TransformerLM(c, docs, mesh=mesh)
    start = {n: np.asarray(t.raw())[:s[0]] for (n, t), s in
             zip(app.tables.items(), table_shapes(c).values())}
    batches = list(pack_documents(docs, c.sequences, c.sequence_length))
    first = app.gradients(batches[0])
    before = telemetry.snapshot()["counters"]
    app.train(total_steps=3)
    counted = {k: v - before.get(k, 0)
               for k, v in telemetry.snapshot()["counters"].items()}
    steps, final = reference_steps(c, batches)
    return {"config": c, "app": app, "start": start, "first": first,
            "batches": batches, "steps": steps, "counters": counted,
            "final": final}


def test_start_values_are_the_reference_s(trained):
    """The reference draws the decay's start with its own code; the
    ssm's output projection starts 1 / sqrt(layers) as wide."""
    c = trained["config"]
    want = start_tables(c)
    assert set(trained["start"]) == set(want)
    for name, got in trained["start"].items():
        np.testing.assert_allclose(got, want[name], rtol=1e-5, atol=1e-8,
                                   err_msg=name)
    decay = want["l0.ssm_decay"]
    assert ((np.exp(decay[0]) >= 1) & (np.exp(decay[0]) <= 16)).all()
    dt = np.log1p(np.exp(decay[1]))
    assert ((dt >= 1e-3 - 1e-9) & (dt <= 0.1 + 1e-9)).all()
    assert (decay[2] == 1).all()
    assert lm.start_std(c, "l0.ssm_out") == pytest.approx(
        c.init_std / np.sqrt(6))


def test_losses_of_three_steps_match_the_reference(trained):
    """float32 on both sides: the loss agrees to the order of its sums."""
    assert len(trained["app"].loss_history) == 3
    for (ce, balance), (want_ce, _, _) in zip(trained["app"].loss_history,
                                             trained["steps"]):
        assert ce == pytest.approx(want_ce, rel=2e-6)
        assert balance == 0.0


def test_every_table_s_delta_matches_the_reference(trained):
    """Every table's gradient, entry by entry (5e-5 of its norm: the
    chunked scan's and the blocked attention's float32 sums), the bias's
    sign rule exactly, and the routing exactly."""
    c = trained["config"]
    aux, grads = trained["first"]
    want = as_tables(c, trained["steps"][0][1])
    assert set(grads) == set(want)
    for name in want:
        got = np.asarray(grads[name])[:want[name].shape[0]]
        if is_bias(name):
            assert np.array_equal(got, want[name]), name
        else:
            assert gap(got, want[name]) < 5e-5, name
            assert np.abs(want[name]).max() > 0, name
    step = jax.device_get(trained["app"].aux_tail[0])
    for name, norm in zip(want, step["grad_norms"]):
        assert norm == pytest.approx(np.linalg.norm(want[name]), rel=2e-5)
    assert gap(step["probe_embed"], want["embed"][:256]) < 5e-5
    real = (trained["batches"][0]["doc"] > 0).reshape(-1)
    assert np.array_equal(
        np.sort(step["chosen"], -1)[:, real],
        np.sort(trained["steps"][0][2]["chosen"], -1)[:, real])
    assert np.array_equal(step["counts"], trained["steps"][0][2]["counts"])


def test_tables_after_three_adam_steps_match_the_reference(trained):
    c = trained["config"]
    start = named_parameters(c, trained["start"])
    got = named_parameters(c, {
        n: np.asarray(t.raw()) for n, t in trained["app"].tables.items()})
    for role, want in trained["final"].items():
        if is_bias(role):
            np.testing.assert_allclose(got[role], want, rtol=0, atol=1e-9)
            assert 0 < np.abs(got[role]).max() <= 3e-3 + 1e-9
        else:
            assert gap(got[role] - start[role],
                       want - start[role]) < 2e-3, role
    app = trained["app"]
    assert all(t.default_option.step == 3 for t in app.tables.values())
    for name, table in app.tables.items():
        assert table.updater.name == ("sgd" if is_bias(name) else "adam")


def test_the_reference_s_layer_at_a_time_gradient_is_the_objective_s(
        trained):
    """One ``jax.grad`` over the whole step's objective gives the loss
    and gradients the reference chains by hand."""
    c = trained["config"]
    cfg = ref_config(c)
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
                x, _ = ref.layer(ref.layer_tensors(p, i), x, doc[b], cfg)
            total += ref.head_loss(x, p["final_norm"], p["head"],
                                   tokens[b], doc[b], cfg) / n_pred
        return total

    want_ce, got, _ = trained["steps"][0]
    with jax.default_matmul_precision("highest"):
        loss, want = jax.value_and_grad(objective)(p)
    assert float(loss) == pytest.approx(want_ce, rel=1e-6)
    for role, g in want.items():
        if is_bias(role):
            continue
        assert gap(got[role], g) < 1e-5, role


def test_bfloat16_products_stay_near_the_reference(mesh, trained):
    """The program as the cell runs it: bfloat16 operands, float32
    decay and state. Near the reference, and not equal to it; a
    router's gradient follows every choice bfloat16 flips, and is held
    to twice the others' bound."""
    c = dataclasses.replace(trained["config"], compute_dtype="bfloat16")
    app = TransformerLM(c, mesh=mesh)
    aux, grads = app.gradients(trained["batches"][0])
    want_ce, want, _ = trained["steps"][0]
    assert float(aux["ce"]) == pytest.approx(want_ce, rel=2e-3)
    want = as_tables(c, want)
    for name in want:
        if not is_bias(name):
            got = np.asarray(grads[name])[:want[name].shape[0]]
            bound = 0.3 if name.endswith(".router") else 0.15
            assert 1e-5 < gap(got, want[name]) < bound, name


def test_changing_one_document_leaves_the_others_bit_equal(trained):
    """The state, the taps and the scores all stop at a document's
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


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_a_control_of_the_reference_is_not_the_reference(trained, variant):
    c, batch = trained["config"], trained["batches"][0]
    cfg = ref_config(c)
    p = {k: jnp.asarray(v) for k, v in
         named_parameters(c, start_tables(c)).items()}
    want_ce, want, _ = ref.loss_and_grads(p, batch, cfg)
    ce, grads, _ = ref.loss_and_grads(p, batch, cfg, variant)
    moved = max(abs(ce - want_ce) / want_ce,
                max(gap(grads[k], want[k]) for k in want if not is_bias(k)))
    assert moved > 1e-3, moved


def test_the_published_config_has_its_parameter_counts():
    """The arithmetic from the config's keys: a Mamba-2 layer
    38,744,896 with its pre-norm, attention 23,399,040, an expert
    9,977,856, the shared one 19,955,712, the router 344,192; 31.58B
    published, 667.0M in the cut."""
    full = ref.parameter_counts(PUBLISHED)
    D = PUBLISHED["hidden_size"]
    assert full["ssm"] + D == 38_744_896
    assert full["attention"] + D == 23_399_040
    assert (full["expert"], full["shared"], full["router"]) == \
        (9_977_856, 19_955_712, 344_192)
    assert full["model"] == 31_577_940_288
    cut = ref.parameter_counts(dict(CUT, n_routed_experts=128))
    assert cut["model"] == pytest.approx(667.0e6, rel=1e-3)
    c = LMConfig.from_dict(dict(CUT, sequences=4, sequence_length=8192))
    c.check()
    assert [c.mixer(i) for i in range(9)] == [
        SSM, EXPERTS, SSM, EXPERTS, SSM, FULL, EXPERTS, SSM, EXPERTS]
    held = sum(int(np.prod(s)) for s in table_shapes(c).values())
    assert held == cut["model"]
    assert c.router_width == 128 and c.use_expert_bias


def test_spans_scopes_and_counters_of_a_training_call(trained):
    snap = telemetry.snapshot()
    spans = {k for k in snap["histograms"] if k.startswith("span.seconds")}
    for name in ("lm.wait_data", "lm.place", "lm.superstep", "lm.fence"):
        assert f"span.seconds{{name={name}}}" in spans, name
    counters = trained["counters"]
    batches = trained["batches"][:3]
    documents_trained = sum(
        len(np.unique(row[row > 0])) for b in batches for row in b["doc"])
    # two ssm layers: 4 chunks of 16 a sequence, a state restart a
    # document
    assert counters["lm.ssm.chunks"] == 3 * 2 * 4 * 4
    # a grid step of the scan holds one group's heads
    assert snap["gauges"]["lm.ssm.scan.heads_per_step"] == 2
    assert counters["lm.ssm.doc_starts"] == 2 * documents_trained
    assert counters["lm.attend.key_blocks"] == len(batches) * 4 * 10
    assert counters["moe.bias_steps"] == 3 * 2
    assert counters["moe.tokens_dropped"] == 0
    held = telemetry.op_scopes()["superstep.lm_superstep"]
    named = set(held["scopes"].values())
    assert {"lm.ssm.project", "lm.ssm.conv", "lm.ssm.scan",
            "lm.ssm.gate_out", "lm.attn.project", "lm.attn.attend",
            "lm.dense_mlp", "lm.moe.route", "lm.moe.permute",
            "lm.moe.experts", "lm.moe.shared", "lm.moe.accumulate",
            "lm.block_norm", "lm.head_loss", "lm.adam"} <= named
