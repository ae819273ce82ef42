"""Plain reference of the DeepSeek-V2 decoder as one chip of an
expert-parallel group trains it: loss, gradients and the Adam step, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
It imports nothing of the program; every tensor goes by its published
role in a flat dict (``PARAMETERS`` below).

Source: huggingface.co/deepseek-ai/DeepSeek-V2-Lite ``config.json`` and
``modeling_deepseek.py`` (DeepseekV2Attention, DeepseekV2MoE, MoEGate,
DeepseekV2YarnRotaryEmbedding). Departures from the published
description, each on purpose:

- **The share.** ``ep_size`` chips share each layer; this one holds
  experts ``ep_rank * held … (ep_rank + 1) * held - 1`` and rows
  ``0 … vocab_size - 1`` of the vocabulary (the slice IS the vocabulary:
  ids, logits and loss are over it). The router keeps all
  ``n_routed_experts`` outputs and ``num_experts_per_tok`` choices; what
  the absent experts would add to a token is left out and the partial
  result goes on to the next layer. The balance loss needs no expert's
  weights and is computed whole.
- **Rotary layout.** The published code de-interleaves the rotary dims
  of ``q_pe`` / ``k_pe`` (pairs ``(2i, 2i + 1)``) before the half-split
  ``rotate_half``. Here the rotary dims are taken as already
  half-split: pair ``i`` is ``(i, i + d/2)``. With random weights that
  is a fixed permutation of columns of ``w_q`` and ``w_kv_a``.
- **Positions** restart at 0 in every packed document, and a token
  attends only to earlier tokens of its own document.
- **The loss** is the mean cross-entropy over the tokens that have a
  successor in their document (the last token of a document and the
  padding predict nothing), plus the balance loss of every expert
  layer; padding is routed nowhere and counted nowhere.
- **Adam** as the program's ``updaters/updaters.py`` writes it:
  ``t = step + 1``, ``eps`` outside the root, NO weight decay; no
  gradient clipping (the published recipe has both).

``variant`` runs a control, a deliberately wrong reference that
``correct`` must tell from the right one: ``"bfloat16"`` (every tensor
and product in bfloat16; the caller may also keep the tables in
bfloat16 between steps, :func:`round_bfloat16`), ``"half_sequences"``
(the second half of the step's sequences left out), ``"no_routed"`` (the routed experts' term
left out), ``"capacity_1"`` (each held expert keeps at most the mean
load, overflow dropped), ``"no_doc_mask"`` (attention across document
boundaries). ``"unchanged"`` (the state left as it was) is the caller's:
it skips :func:`adam_step`.
"""

from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 1024      # queries a block of the attention (memory only)
VARIANTS = ("bfloat16", "half_sequences", "no_routed", "capacity_1",
            "no_doc_mask")

PARAMETERS = """
embed [vocab, hidden]; head [vocab, hidden] (logits = h . head^T);
final_norm [hidden]; for layer i: l{i}.attn_norm [hidden],
l{i}.w_q [hidden, heads * (nope + rope)], l{i}.w_kv_a [hidden, rank +
rope], l{i}.kv_norm [rank], l{i}.w_kv_b [rank, heads * (nope + v)],
l{i}.w_o [hidden, heads * v] (x += o . w_o^T), l{i}.ffn_norm [hidden];
dense layer: l{i}.w_gate, l{i}.w_up, l{i}.w_down, each [hidden, width]
(y = a . w_down^T); expert layer: l{i}.router [hidden, experts],
l{i}.shared_gate / _up / _down [hidden, shared * width],
l{i}.exp_gate / _up / _down [held, hidden, width].
"""


def init_normal(seed: int, index: int, shape, std: float):
    """Start values of the table with that index: normal(0, std) from
    the seed (a jax key; any whole number up to 2**63)."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return std * jax.random.normal(jax.random.fold_in(key, index),
                                   tuple(shape), jnp.float32)


# -- rotary embedding, YaRN ------------------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """Rotary frequencies [rope/2] under the config's ``rope_scaling``
    (plain ``theta ** (-2i/d)`` where it has none)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return extra.astype(np.float32)
    inter = extra / rs["factor"]
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rotary_magnitude(cfg: dict) -> float:
    rs = cfg.get("rope_scaling")
    if not rs:
        return 1.0
    return yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"])


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        scale *= m * m
    return scale


def rotate(x, positions, cfg: dict):
    """Half-split rotary on the last axis of ``x`` [..., S, (H,) d] by
    ``positions`` [..., S]."""
    ang = positions[..., None].astype(jnp.float32) \
        * jnp.asarray(yarn_inv_freq(cfg))
    mag = rotary_magnitude(cfg)
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    if x.ndim == ang.ndim + 1:                 # a head axis before d
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# -- the layers --------------------------------------------------------------

def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down.T


def latent_attention(p, x, doc, pos, cfg, mask_docs=True):
    """``x`` [S, hidden] of ONE sequence, ``p`` the layer's tensors
    (their names without the ``l{i}.``); returns the attention's
    addition to the residual."""
    H = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    S = x.shape[0]
    h = rms_norm(x, p["attn_norm"], cfg["rms_norm_eps"])
    q = (h @ p["w_q"]).reshape(S, H, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv_a = h @ p["w_kv_a"]
    c, k_pe = kv_a[:, :rank], kv_a[:, rank:]
    c = rms_norm(c, p["kv_norm"], cfg["rms_norm_eps"])
    kv = (c @ p["w_kv_b"]).reshape(S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = rotate(q_pe, pos, cfg)
    k_pe = rotate(k_pe, pos, cfg)               # one key for all heads
    scale = softmax_scale(cfg)

    def attend(q_nope, q_pe, q_doc, q_t):
        """A block of queries against every key (the whole [S, S] score
        matrix of 16 heads does not fit beside the tables)."""
        scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
                  + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * scale
        allowed = q_t[:, None] >= jnp.arange(S)[None, :]
        if mask_docs:
            allowed &= q_doc[:, None] == doc[None, :]
        scores = jnp.where(allowed[None], scores.astype(jnp.float32),
                           -1e30)
        prob = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        return jnp.einsum("hqk,khd->qhd", prob, v)

    # a block of queries at a time, each recomputed in the backward pass
    Q = min(S, QUERY_BLOCK)
    blocks = lambda a: a.reshape(S // Q, Q, *a.shape[1:])
    o = jax.lax.map(lambda b: jax.checkpoint(attend)(*b),
                    (blocks(q_nope), blocks(q_pe), blocks(doc),
                     blocks(jnp.arange(S)))).reshape(S, H * vd)
    return o @ p["w_o"].T


def route(p, h, real, cfg):
    """Scores over ALL the router's outputs in float32, the greedy
    top-k, and the sequence's balance loss (``h`` is one sequence)."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    if cfg.get("scoring_func", "softmax") != "softmax":
        raise NotImplementedError(cfg["scoring_func"])
    s = jax.nn.softmax(h.astype(jnp.float32)
                       @ p["router"].astype(jnp.float32), axis=-1)
    top_s, top_e = jax.lax.top_k(s, k)
    if cfg.get("norm_topk_prob"):
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    top_s = top_s * cfg.get("routed_scaling_factor", 1.0)
    chose = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(1) \
        * real[:, None]                                    # [S, E]
    n = jnp.maximum(real.sum(), 1.0)
    f = chose.sum(0) * (E / (k * n))
    P = (s * real[:, None]).sum(0) / n
    balance = cfg["aux_loss_alpha"] * jnp.sum(f * P)
    return top_s, top_e, chose, balance


def expert_layer(p, x, real, cfg, variant=None):
    """One sequence through the shared experts and the experts held
    here; returns (addition to the residual, balance loss, counts over
    all experts [E], chosen experts [S, k], assignments dropped)."""
    held = cfg["n_routed_experts"] // cfg["ep_size"]
    first = cfg["ep_rank"] * held
    h = rms_norm(x, p["ffn_norm"], cfg["rms_norm_eps"])
    top_s, top_e, chose, balance = route(p, h, real, cfg)
    y = swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    dropped = jnp.zeros((), jnp.float32)
    if variant != "no_routed":
        capacity = x.shape[0] * cfg["num_experts_per_tok"] \
            // cfg["n_routed_experts"]
        def one(carry, expert):
            y, dropped = carry
            e, gate, up, down = expert
            took = (top_e == first + e) & (real[:, None] > 0)   # [S, k]
            if variant == "capacity_1":
                keep = jnp.cumsum(took.any(1)) - 1 < capacity
                dropped += jnp.sum(took.any(1) & ~keep)
                took &= keep[:, None]
            # the expert on EVERY token of the sequence, weight 0 where
            # the token did not choose it: nothing to gather, no slots
            weight = jnp.sum(jnp.where(took, top_s, 0.0), 1)
            return (y + weight[:, None].astype(h.dtype)
                    * swiglu(h, gate, up, down), dropped), None

        (y, dropped), _ = jax.lax.scan(
            one, (y, dropped), (jnp.arange(held), p["exp_gate"],
                                p["exp_up"], p["exp_down"]))
    return y, balance, chose.sum(0), top_e, dropped


def layer(p, x, doc, pos, cfg, variant=None):
    """One decoder layer on one packed sequence: ``(residual after it,
    its balance loss), what it routed`` (zeros in a dense layer); a
    layer is dense iff it has ``w_gate``."""
    real = (doc > 0).astype(jnp.float32)
    if variant == "bfloat16":
        p = {k: v.astype(jnp.bfloat16) for k, v in p.items()}
    x = x + latent_attention(p, x, doc, pos, cfg,
                             mask_docs=variant != "no_doc_mask")
    if "w_gate" in p:
        h = rms_norm(x, p["ffn_norm"], cfg["rms_norm_eps"])
        x = x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
        return (x, jnp.zeros(())), (jnp.zeros((E,)), jnp.zeros(
            (x.shape[0], k), jnp.int32), jnp.zeros(()))
    y, balance, counts, top_e, dropped = expert_layer(p, x, real, cfg,
                                                      variant)
    return (x + y, balance.astype(jnp.float32)), (counts, top_e, dropped)


def head_loss(x, final_norm, head, tokens, doc, cfg):
    """Sum of the cross-entropy over the sequence's predicting tokens."""
    h = rms_norm(x, final_norm.astype(x.dtype), cfg["rms_norm_eps"])
    logits = (h @ head.astype(x.dtype).T).astype(jnp.float32)
    target = jnp.concatenate([tokens[1:], tokens[:1]])
    predicts = (jnp.concatenate([doc[1:] == doc[:-1],
                                 jnp.zeros((1,), bool)])
                & (doc > 0)).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, -1) \
        - jnp.take_along_axis(logits, target[:, None], 1)[:, 0]
    return jnp.sum(nll * predicts)


@lru_cache(maxsize=None)
def _programs(cfg_json: str, variant):
    """The jitted pieces a sequence goes through, one program a kind of
    layer (the layers of a kind share it): a layer forward, a layer's
    vector-Jacobian product from the residual that entered it (the layer
    is computed again: nothing of the first pass is kept but that
    residual), and the head's loss with its gradients."""
    cfg = json.loads(cfg_json)
    run = partial(layer, cfg=cfg, variant=variant)

    def pull(p, x, doc, pos, d_x, d_balance):
        _, vjp, _ = jax.vjp(lambda p, x: run(p, x, doc, pos), p, x,
                            has_aux=True)
        return vjp((d_x, d_balance))

    return (jax.jit(run), jax.jit(pull),
            jax.jit(jax.value_and_grad(partial(head_loss, cfg=cfg),
                                       argnums=(0, 1, 2))))


_add_trees = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                     donate_argnums=0)


def _layer_tensors(params: dict, i: int) -> dict:
    prefix = f"l{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def loss_and_grads(params: dict, batch: dict, cfg: dict, variant=None):
    """The step's loss and gradients, one sequence at a time (a
    gradient is a sum over sequences, so this is the whole step's) and,
    inside a sequence, one layer at a time: forward keeping the residual
    that enters each layer, then the chain rule from the head down.
    The objective is ``mean cross-entropy over the step's predicting
    tokens + mean over sequences of the layers' balance losses``.
    ``batch``: ``tokens``, ``doc``, ``pos``, each int32 [B, S]; ``doc``
    is 0 on padding. Returns ``(ce, balance, grads, aux)``: aux
    ``counts`` [expert layers, E], ``chosen`` [expert layers, B * S, k],
    ``dropped``."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(variant)
    tokens, doc, pos = (jnp.asarray(batch[k], jnp.int32)
                        for k in ("tokens", "doc", "pos"))
    if variant == "half_sequences":
        half = tokens.shape[0] // 2
        tokens, doc, pos = tokens[:half], doc[:half], pos[:half]
    B, L = tokens.shape[0], cfg["num_hidden_layers"]
    n_pred = jnp.sum((doc[:, 1:] == doc[:, :-1]) & (doc[:, :-1] > 0)
                     ).astype(jnp.float32)
    forward, pull, head = _programs(json.dumps(cfg, sort_keys=True),
                                    variant)
    dt = jnp.bfloat16 if variant == "bfloat16" else jnp.float32
    layers = [_layer_tensors(params, i) for i in range(L)]
    expert = [i for i in range(L) if "w_gate" not in layers[i]]
    grads = zeros_like(params)
    ce, balance, dropped, counts, chosen = 0.0, 0.0, 0.0, 0.0, []
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            x = params["embed"][tokens[b]].astype(dt)
            entering, routed = [], []
            for i in range(L):
                entering.append(x)
                (x, bal), r = forward(layers[i], x, doc[b], pos[b])
                balance += float(bal) / B
                routed.append(r)
            ce_sum, (d_x, d_norm, d_head) = head(
                x, params["final_norm"], params["head"], tokens[b], doc[b])
            ce += float(ce_sum) / float(n_pred)
            d_x = d_x / n_pred.astype(dt)
            g = {"final_norm": d_norm / n_pred, "head": d_head / n_pred}
            for i in reversed(range(L)):
                d_p, d_x = pull(layers[i], entering.pop(), doc[b], pos[b],
                                d_x, jnp.float32(1.0 / B))
                g.update({f"l{i}.{k}": v.astype(jnp.float32)
                          for k, v in d_p.items()})
            g["embed"] = jnp.zeros_like(params["embed"]).at[tokens[b]].add(
                d_x.astype(jnp.float32))
            grads = _add_trees(grads, g)
            counts = counts + np.stack([np.asarray(routed[i][0])
                                        for i in expert])
            chosen.append(np.stack([np.asarray(routed[i][1])
                                    for i in expert]))
            dropped += sum(float(routed[i][2]) for i in expert)
    return ce, balance, grads, {
        "counts": counts, "chosen": np.concatenate(chosen, axis=1),
        "dropped": dropped}


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(p, m, v, g, t, lr, b1, b2, eps):
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    mhat = m / (1.0 - b1 ** t)
    vhat = v / (1.0 - b2 ** t)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v


def adam_step(params, m, v, grads, step, *, lr, b1, b2, eps):
    """One Adam step on every tensor, in place (the arguments are
    donated): ``t = step + 1``, bias-corrected moments, ``eps`` outside
    the root, no weight decay."""
    t = jnp.float32(step + 1)
    out = {k: _adam(params[k], m[k], v[k], grads[k], t, lr, b1, b2, eps)
           for k in params}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def round_bfloat16(params):
    """Tables held in bfloat16: what the ``"bfloat16"`` control keeps of
    a step (a norm weight of 1 cannot take a step of 4e-4 there)."""
    return {k: v.astype(jnp.bfloat16).astype(jnp.float32)
            for k, v in params.items()}


def zeros_like(params):
    return {k: jnp.zeros_like(v) for k, v in params.items()}


def parameter_counts(cfg: dict) -> dict:
    """Parameters by kind from the config's keys alone: ``attention``
    and ``outside_experts`` a layer, one ``routed_expert``, the
    ``dense_layer`` whole, ``vocabulary`` (embedding and head)."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    attention = D * H * (nope + rope) + D * (rank + rope) + rank \
        + rank * H * (nope + vd) + H * vd * D
    F = cfg["moe_intermediate_size"]
    shared = 3 * D * F * cfg["n_shared_experts"]
    router = D * cfg["n_routed_experts"]
    return {"attention": attention, "shared_experts": shared,
            "router": router,
            "outside_experts": attention + shared + router,
            "routed_expert": 3 * D * F,
            "dense_layer": attention + 3 * D * cfg["intermediate_size"],
            "vocabulary": 2 * D * cfg["vocab_size"]}
