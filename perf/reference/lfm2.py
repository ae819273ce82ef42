"""Plain reference of the LFM2-MoE decoder as one chip of an
expert-parallel, vocabulary-parallel group of a pipeline stage trains
it: loss, gradients, the Adam step and the selection bias's own step, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
It imports nothing of the program; every tensor goes by its published
role in a flat dict (``PARAMETERS`` below).

Source: huggingface.co/LiquidAI/LFM2-8B-A1B ``config.json``
(``model_type`` ``lfm2_moe``), computed as the public ``transformers``
implementation of that model type computes it, written from knowledge
of that code and of the config's keys. With ``u = RMSNorm(x; w, eps)``
in float32, block ``i`` is PRE-norm:

    h  = x + Op_i(RMSNorm(x; mixer_norm))
    x' = h + FFN_i(RMSNorm(h; ffn_norm))

after the last layer one RMSNorm (``final_norm``, the family's
``embedding_norm``), then ``logits = RMSNorm(x) E^T`` with ``E`` the SAME
table the tokens' rows were gathered from (tied).

- ``conv`` (the gated short convolution): ``[B | C | X] = u W_in``
  (``conv_in`` [D, 3 D], no bias), ``z = B * X``,
  ``c_t = sum_{j < L} w_j * z_{t - (L - 1) + j}`` with ``L`` =
  ``conv_L_cache`` taps, one filter a channel (``conv_taps`` [L, D], no
  bias), causal; ``Op = (C * c) W_out`` (``conv_out`` [D, D]). NO
  activation inside the operator.
- ``full_attention``: ``q = u W_q`` as H heads of ``d`` = D / H,
  ``k = u W_k``, ``v = u W_v`` as G = ``num_key_value_heads`` heads of
  ``d``; ``q`` and ``k`` each through an RMSNorm over ONE head's ``d``
  dims with one weight vector of ``d`` (``q_norm``, ``k_norm``: the
  family's ``q_layernorm`` / ``k_layernorm``), then a rotary embedding
  over all ``d`` dims, base ``rope_theta``, half-split pairs
  ``(i, i + d / 2)``. Query head ``h`` reads key-value head
  ``h // (H / G)``. ``softmax(q k^T / sqrt(d))``, causal;
  ``Op = concat_h(o_h) W_o``.
- dense FFN (layer < ``num_dense_layers``): ``W_2 (silu(W_1 u) * W_3 u)``.
- expert FFN: ``s = sigmoid(u W_r)`` over all ``num_experts`` outputs;
  chosen: the ``num_experts_per_tok`` largest of ``s + b``, ``b`` the
  layer's ``expert_bias``; weights: ``s`` at the chosen (WITHOUT ``b``),
  divided by their sum + 1e-6 (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``FFN = sum_chosen weight_e * expert_e(u)``.
  No shared expert, no balance loss. ``b`` gets no gradient.
- the bias's rule (auxiliary-loss-free balancing, arXiv:2408.15664, at
  its published rate ``gamma`` = 1e-3): after each step, per expert
  layer, with ``c_e`` the step's real tokens that chose ``e`` over all
  the router's outputs: ``b_e += gamma * sign(mean(c) - c_e)``; ``b``
  starts at 0; step ``n`` routes with the ``b`` step ``n - 1`` left.
  :func:`loss_and_grads` returns ``sign(c_e - mean c)`` under the
  bias's name — the table's delta, no derivative — and
  :func:`bias_step` applies it.

Departures from a plain reading, each on purpose:

- **Packed documents.** A token attends only to earlier tokens of its
  own document; positions restart at 0 in every document; a convolution
  tap that would reach into an earlier document reads 0.
- **The share.** ``ep_size`` chips share each layer: this one holds
  experts ``ep_rank * held … (ep_rank + 1) * held - 1`` (``held`` =
  ``num_experts / ep_size``); the router keeps all ``num_experts``
  outputs and choices, and what the absent experts would add to a token
  is left out. The vocabulary rows held here ARE the vocabulary: ids,
  logits and loss are over the slice.
- **The loss** is the mean cross-entropy over the tokens that have a
  successor in their document; padding is routed nowhere and counted
  nowhere.
- **Adam** as the program's ``updaters/updaters.py`` writes it:
  ``t = step + 1``, ``eps`` outside the root, NO weight decay; no
  gradient clipping. The tied table takes ONE step on the sum of its
  two gradients (the head's and the gathered rows').

``variant`` runs a control, a deliberately wrong reference that
``correct`` must tell from the right one: ``"conv_across"`` (taps reach
into the previous document), ``"no_doc_mask"`` (attention across
documents), ``"no_rotary"``, ``"kv_head_mod"`` (query head ``h`` reads
key-value head ``h % G``), ``"qk_norm_all"`` (QK-norm over all heads'
dims), ``"conv_silu"`` (a ``silu`` on the taps' output), ``"softmax"``
(softmax scores), ``"bias_not_selecting"`` (the chosen are the top of
``s`` alone), ``"bias_in_weights"`` (weights from ``s + b``),
``"weights_not_normalised"``, ``"untied"`` (the embedding's step misses
the head's gradient), ``"bfloat16"`` (every tensor and product in
bfloat16; the caller may also keep the tables in bfloat16,
:func:`round_bfloat16`). ``"bias_frozen"`` (the bias never stepped) and
``"unchanged"`` (no state stepped) are the caller's: they skip
:func:`bias_step` / :func:`adam_step`.
"""

from __future__ import annotations

import json
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 1024      # queries a block of the attention (memory only)
VARIANTS = ("conv_across", "no_doc_mask", "no_rotary", "kv_head_mod",
            "qk_norm_all", "conv_silu", "softmax", "bias_not_selecting",
            "bias_in_weights", "weights_not_normalised", "untied",
            "bfloat16")

PARAMETERS = """
embed [vocab, hidden] (also the head: logits = h . embed^T);
final_norm [hidden]; every layer i: l{i}.mixer_norm, l{i}.ffn_norm
[hidden]. A conv layer: l{i}.conv_in [hidden, 3 hidden] (B | C | X),
l{i}.conv_taps [taps, hidden] (tap taps-1 weighs the token itself),
l{i}.conv_out [hidden, hidden] (x += y . conv_out). A full_attention
layer: l{i}.w_q [hidden, H d], l{i}.w_k, l{i}.w_v [hidden, G d],
l{i}.q_norm, l{i}.k_norm [d], l{i}.w_o [hidden, H d] (x += o . w_o^T).
A dense layer: l{i}.w_gate, l{i}.w_up, l{i}.w_down, each [hidden, width]
(y = a . w_down^T). An expert layer: l{i}.router [hidden, experts],
l{i}.expert_bias [experts], l{i}.exp_gate / _up / _down [held, hidden,
width].
"""


def _key(seed: int, index: int):
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, index)


def init_normal(seed: int, index: int, shape, std: float):
    """Start values of the table with that index: normal(0, std) from
    the seed (a jax key; any whole number up to 2**63)."""
    return std * jax.random.normal(_key(seed, index), tuple(shape),
                                   jnp.float32)


def is_bias(name: str) -> bool:
    return name.endswith("expert_bias")


# -- the layers --------------------------------------------------------------

def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down.T


def causal_taps(z, taps, doc, across=False):
    """``z`` [S, W] through the causal depthwise filter ``taps`` [L, W]:
    ``c_t = sum_j taps[j] z_{t - (L - 1 - j)}``, a term whose token lies
    in another document (or before the sequence) left out."""
    S, L = z.shape[0], taps.shape[0]
    t = jnp.arange(S)
    c = jnp.zeros_like(z)
    for j in range(L):
        src = t - (L - 1 - j)
        ok = src >= 0
        if not across:
            ok &= doc[jnp.maximum(src, 0)] == doc
        c = c + jnp.where(ok[:, None], z[jnp.maximum(src, 0)], 0) * taps[j]
    return c


def short_conv(p, u, doc, variant=None):
    """The gated short convolution on one sequence's normed input ``u``
    [S, hidden]."""
    b, c, x = jnp.split(u @ p["conv_in"], 3, axis=-1)
    y = causal_taps(b * x, p["conv_taps"], doc,
                    across=variant == "conv_across")
    if variant == "conv_silu":
        y = jax.nn.silu(y)
    return (c * y) @ p["conv_out"]


def rotate(x, pos, theta):
    """Rotary embedding over the last axis of ``x`` [S, heads, d],
    half-split pairs ``(i, i + d / 2)``, float32."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def full_attention(p, u, doc, pos, cfg, variant=None):
    """Grouped-query softmax attention with a QK-norm a head and a
    rotary embedding, on one sequence's normed input."""
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // H
    S, eps = u.shape[0], cfg["norm_eps"]
    q, k = u @ p["w_q"], u @ p["w_k"]
    if variant == "qk_norm_all":
        q = rms_norm(q, jnp.tile(p["q_norm"], H), eps).reshape(S, H, d)
        k = rms_norm(k, jnp.tile(p["k_norm"], G), eps).reshape(S, G, d)
    else:
        q = rms_norm(q.reshape(S, H, d), p["q_norm"], eps)
        k = rms_norm(k.reshape(S, G, d), p["k_norm"], eps)
    v = (u @ p["w_v"]).reshape(S, G, d)
    if variant != "no_rotary":
        theta = float(cfg["rope_theta"])
        q, k = rotate(q, pos, theta), rotate(k, pos, theta)
    heads = jnp.arange(H)
    of = heads % G if variant == "kv_head_mod" else heads // (H // G)
    k, v = k[:, of], v[:, of]           # a query head's key-value head

    def attend(q, q_doc, q_t):
        """A block of queries against every key."""
        scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
        allowed = q_t[:, None] >= jnp.arange(S)[None, :]
        if variant != "no_doc_mask":
            allowed &= q_doc[:, None] == doc[None, :]
        scores = jnp.where(allowed[None], scores.astype(jnp.float32),
                           -1e30)
        prob = jax.nn.softmax(scores, axis=-1).astype(u.dtype)
        return jnp.einsum("hqk,khd->qhd", prob, v)

    Q = min(S, QUERY_BLOCK)
    blocks = lambda a: a.reshape(S // Q, Q, *a.shape[1:])
    o = jax.lax.map(lambda b: jax.checkpoint(attend)(*b),
                    (blocks(q), blocks(doc), blocks(jnp.arange(S)))
                    ).reshape(S, H * d)
    return o @ p["w_o"].T


def route(p, h, cfg, variant=None):
    """Scores over ALL the router's outputs in float32, the chosen
    experts and their weights (``h`` is one sequence)."""
    k = cfg["num_experts_per_tok"]
    logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    s = jax.nn.softmax(logits, -1) if variant == "softmax" \
        else jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(p["expert_bias"].astype(jnp.float32))
    picking = s if variant == "bias_not_selecting" else s + bias
    top_e = jax.lax.top_k(picking, k)[1]
    weighing = s + bias if variant == "bias_in_weights" else s
    top_s = jnp.take_along_axis(weighing, top_e, -1)
    if cfg.get("norm_topk_prob") and variant != "weights_not_normalised":
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-6)
    return top_s * cfg.get("routed_scaling_factor", 1.0), top_e


def held_experts(cfg):
    held = cfg["num_experts"] // cfg.get("ep_size", 1)
    return cfg.get("ep_rank", 0) * held, held


def expert_layer(p, h, real, cfg, variant=None):
    """One sequence's normed input through the experts held here;
    returns (addition to the residual, counts of the real tokens over
    all experts [E], chosen experts [S, k])."""
    first, held = held_experts(cfg)
    top_s, top_e = route(p, h, cfg, variant)
    chose = jax.nn.one_hot(top_e, cfg["num_experts"],
                           dtype=jnp.float32).sum(1) * real[:, None]

    def one(y, expert):
        e, gate, up, down = expert
        took = (top_e == first + e) & (real[:, None] > 0)       # [S, k]
        # the expert on EVERY token of the sequence, weight 0 where the
        # token did not choose it: nothing to gather, no slots
        weight = jnp.sum(jnp.where(took, top_s, 0.0), 1)
        return y + weight[:, None].astype(h.dtype) \
            * swiglu(h, gate, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(held), p["exp_gate"], p["exp_up"], p["exp_down"]))
    return y, chose.sum(0), top_e


def layer(p, x, doc, pos, cfg, variant=None):
    """One decoder layer on one packed sequence: the residual after it,
    and what it routed (zeros in a dense layer). A layer is a conv layer
    iff it has ``conv_in``, dense iff it has ``w_gate``."""
    real = (doc > 0).astype(jnp.float32)
    if variant == "bfloat16":
        p = {k: v.astype(jnp.bfloat16) for k, v in p.items()}
    eps = cfg["norm_eps"]
    u = rms_norm(x, p["mixer_norm"], eps)
    if "conv_in" in p:
        x = x + short_conv(p, u, doc, variant)
    else:
        x = x + full_attention(p, u, doc, pos, cfg, variant)
    h = rms_norm(x, p["ffn_norm"], eps)
    if "w_gate" in p:
        E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), (
            jnp.zeros((E,)), jnp.zeros((x.shape[0], k), jnp.int32))
    y, counts, top_e = expert_layer(p, h, real, cfg, variant)
    return x + y, (counts, top_e)


def head_logits(x, final_norm, head, cfg):
    h = rms_norm(x, final_norm.astype(x.dtype), cfg["norm_eps"])
    return (h @ head.astype(x.dtype).T).astype(jnp.float32)


def head_loss(x, final_norm, head, tokens, doc, cfg):
    """Sum of the cross-entropy over the sequence's predicting tokens."""
    logits = head_logits(x, final_norm, head, cfg)
    target = jnp.concatenate([tokens[1:], tokens[:1]])
    predicts = (jnp.concatenate([doc[1:] == doc[:-1],
                                 jnp.zeros((1,), bool)])
                & (doc > 0)).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, -1) \
        - jnp.take_along_axis(logits, target[:, None], 1)[:, 0]
    return jnp.sum(nll * predicts)


@lru_cache(maxsize=None)
def _programs(cfg_json: str, variant):
    """The jitted pieces a sequence goes through (the layers of a kind
    share a program): a layer forward, a layer's vector-Jacobian product
    from the residual that entered it (the layer is computed again), and
    the head's loss with its gradients."""
    cfg = json.loads(cfg_json)
    run = partial(layer, cfg=cfg, variant=variant)

    def pull(p, x, doc, pos, d_x):
        _, vjp, _ = jax.vjp(lambda p, x: run(p, x, doc, pos), p, x,
                            has_aux=True)
        return vjp(d_x)

    return (jax.jit(run), jax.jit(pull),
            jax.jit(jax.value_and_grad(partial(head_loss, cfg=cfg),
                                       argnums=(0, 1, 2))))


_add_trees = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                     donate_argnums=0)


def layer_tensors(params: dict, i: int) -> dict:
    prefix = f"l{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def loss_and_grads(params: dict, batch: dict, cfg: dict, variant=None):
    """The step's loss and every table's delta, one sequence at a time (a
    gradient is a sum over sequences) and, inside a sequence, one layer
    at a time: forward keeping the residual that enters each layer, then
    the chain rule from the head down. The objective is the mean
    cross-entropy over the step's predicting tokens. ``batch``:
    ``tokens``, ``doc``, ``pos``, each int32 [B, S]; ``doc`` is 0 on
    padding. Returns ``(ce, grads, aux)``: ``grads["embed"]`` is the SUM
    of the head's gradient and the gathered rows' (one tied table),
    ``grads["l{i}.expert_bias"]`` the bias's delta ``sign(c - mean c)``
    of the step's counts; aux ``counts`` [expert layers, E], ``chosen``
    [expert layers, B * S, k]."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(variant)
    tokens, doc, pos = (jnp.asarray(batch[k], jnp.int32)
                        for k in ("tokens", "doc", "pos"))
    B, L = tokens.shape[0], cfg["num_hidden_layers"]
    n_pred = jnp.sum((doc[:, 1:] == doc[:, :-1]) & (doc[:, :-1] > 0)
                     ).astype(jnp.float32)
    forward, pull, head = _programs(json.dumps(cfg, sort_keys=True),
                                    variant)
    dt = jnp.bfloat16 if variant == "bfloat16" else jnp.float32
    layers = [layer_tensors(params, i) for i in range(L)]
    expert = [i for i in range(L) if "router" in layers[i]]
    grads = zeros_like(params)
    ce, counts, chosen = 0.0, 0.0, []
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            x = params["embed"][tokens[b]].astype(dt)
            entering, routed = [], []
            for i in range(L):
                entering.append(x)
                x, r = forward(layers[i], x, doc[b], pos[b])
                routed.append(r)
            ce_sum, (d_x, d_norm, d_head) = head(
                x, params["final_norm"], params["embed"], tokens[b], doc[b])
            ce += float(ce_sum) / float(n_pred)
            d_x = d_x / n_pred.astype(dt)
            g = {"final_norm": d_norm / n_pred}
            for i in reversed(range(L)):
                d_p, d_x = pull(layers[i], entering.pop(), doc[b], pos[b],
                                d_x)
                g.update({f"l{i}.{k}": v.astype(jnp.float32)
                          for k, v in d_p.items()})
            rows = jnp.zeros_like(params["embed"]).at[tokens[b]].add(
                d_x.astype(jnp.float32))
            # ONE tied table: the head's gradient and the rows' summed
            g["embed"] = rows if variant == "untied" \
                else rows + d_head.astype(jnp.float32) / n_pred
            grads = _add_trees(grads, g)
            counts = counts + np.stack([np.asarray(routed[i][0])
                                        for i in expert])
            chosen.append(np.stack([np.asarray(routed[i][1])
                                    for i in expert]))
    for n, i in enumerate(expert):
        c = jnp.asarray(counts[n], jnp.float32)
        grads[f"l{i}.expert_bias"] = jnp.sign(c - jnp.mean(c))
    return ce, grads, {"counts": counts,
                       "chosen": np.concatenate(chosen, axis=1)}


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(p, m, v, g, t, lr, b1, b2, eps):
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    mhat = m / (1.0 - b1 ** t)
    vhat = v / (1.0 - b2 ** t)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v


def adam_step(params, m, v, grads, step, *, lr, b1, b2, eps):
    """One Adam step on every tensor but the selection biases (which
    pass through as they are: :func:`bias_step` is theirs): ``t = step +
    1``, bias-corrected moments, ``eps`` outside the root, no weight
    decay. ``m`` and ``v`` may wait on the host (numpy): a tensor's
    moments are on the device only while its step runs, and come back
    as numpy."""
    t = jnp.float32(step + 1)
    new_p, new_m, new_v = {}, {}, {}
    for k in params:
        if is_bias(k):
            new_p[k], new_m[k], new_v[k] = params[k], m[k], v[k]
            continue
        new_p[k], mk, vk = _adam(params[k], jnp.asarray(m[k]),
                                 jnp.asarray(v[k]), grads[k], t, lr, b1,
                                 b2, eps)
        new_m[k], new_v[k] = np.asarray(mk), np.asarray(vk)
    return new_p, new_m, new_v


def bias_step(params, grads, rate):
    """The selection biases' own step: ``b -= rate * sign(c - mean c)``,
    i.e. ``b_e += rate * sign(mean c - c_e)``; every other tensor passes
    through."""
    return {k: p - jnp.float32(rate) * grads[k] if is_bias(k) else p
            for k, p in params.items()}


def round_bfloat16(params):
    """Tables held in bfloat16: what the ``"bfloat16"`` control keeps of
    a step (a norm weight of 1 cannot take a step of 3e-4 there)."""
    return {k: v.astype(jnp.bfloat16).astype(jnp.float32)
            for k, v in params.items()}


def zeros_like(params):
    return {k: jnp.zeros_like(v) for k, v in params.items()}


def host_zeros_like(params):
    return {k: np.zeros(v.shape, np.float32) for k, v in params.items()}


def parameter_counts(cfg: dict) -> dict:
    """Parameters by kind from the config's keys alone: the ``conv`` and
    the ``attention`` operator (its two QK-norm vectors included), a
    ``dense`` feed-forward, one ``expert``, a ``router`` (its bias
    included), the ``vocabulary`` (ONE table, tied), the ``norms`` (two
    a layer and the final one), and the ``model``; ``num_experts``
    counts the router's outputs, of which ``num_experts / ep_size`` are
    held."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    d = D // H
    conv = 3 * D * D + D * D + cfg["conv_L_cache"] * D
    attention = 2 * D * D + 2 * D * cfg["num_key_value_heads"] * d + 2 * d
    dense = 3 * D * cfg["intermediate_size"]
    expert = 3 * D * cfg["moe_intermediate_size"]
    router = D * cfg["num_experts"] + cfg["num_experts"]
    L = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:L]
    n_dense = min(cfg["num_dense_layers"], L)
    _, held = held_experts(cfg)
    vocabulary = D * cfg["vocab_size"]
    norms = (2 * L + 1) * D
    model = sum(conv if k == "conv" else attention for k in kinds) \
        + n_dense * dense + (L - n_dense) * (held * expert + router) \
        + vocabulary + norms
    return {"conv": conv, "attention": attention, "dense": dense,
            "expert": expert, "router": router, "vocabulary": vocabulary,
            "norms": norms, "model": model}
