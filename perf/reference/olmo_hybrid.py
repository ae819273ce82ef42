"""Plain reference of the Olmo-Hybrid decoder as the first chip of a
vocabulary-parallel pipeline stage trains it: loss, gradients and the
Adam step, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program; every tensor goes by its published role in a flat dict
(``PARAMETERS`` below).

Source: huggingface.co/allenai/Olmo-Hybrid-7B ``config.json``
(``model_type`` ``olmo_hybrid``); the linear-attention layer is the
public ``flash-linear-attention`` ``GatedDeltaNet`` (arXiv:2412.06464),
which the config's keys name (``linear_conv_kernel_dim``,
``linear_allow_neg_eigval``, key width 0.75 x hidden, value width twice
the key width). The equations, per token ``t`` and head:

- block: ``x += RMSNorm_a(Mixer(x))``, ``x += RMSNorm_f(SwiGLU(x))`` —
  the Olmo 2 / 3 family's reordered norm: mixer and feed-forward read
  the residual itself; a final RMSNorm before the head.
- ``linear_attention``: ``q~, k~, v~ = W_q x, W_k x, W_v x``, each
  channel through its own causal filter of ``linear_conv_kernel_dim``
  taps (no bias) and ``silu``; ``q <- q / |q| / sqrt(d_k)``,
  ``k <- k / |k|`` a head; ``b = 2 sigmoid(W_b x)``,
  ``g = -exp(A_log) softplus(W_a x + dt_bias)``, ``a = exp(g)``;
  ``S_t = a_t S_{t-1} + b_t k_t (v_t - (a_t S_{t-1})^T k_t)^T``,
  ``o_t = S_t^T q_t``; ``y = W_o (RMSNorm_dv(o) * silu(W_g x))``.
- ``full_attention``: ``q, k, v = W_q x, W_k x, W_v x``; ``q`` and ``k``
  through an RMSNorm over all heads' dims (the family's QK-norm); NO
  rotary embedding (``rope_parameters.rope_theta`` is null: positions
  come from the recurrent layers); softmax(q k^T / sqrt(head_dim)),
  causal; ``y = W_o o``.

Departures from a plain reading, each on purpose:

- **Packed documents.** A token attends only to earlier tokens of its
  own document; the recurrent state is 0 at a document's first token;
  a convolution tap that would reach into an earlier document reads 0.
- **The share.** The vocabulary held here IS the vocabulary: ids,
  logits and loss are over the slice (``vocab_shard`` chips share it).
- **The loss** is the mean cross-entropy over the tokens that have a
  successor in their document.
- **Adam** as the program's ``updaters/updaters.py`` writes it:
  ``t = step + 1``, ``eps`` outside the root, NO weight decay; no
  gradient clipping.

``variant`` runs a control, a deliberately wrong reference that
``correct`` must tell from the right one: ``"carried_state"`` (the
state runs on across document boundaries), ``"conv_across"`` (the taps
reach into the previous document), ``"no_decay"`` (``a = 1``),
``"beta_1"`` (``b`` not doubled), ``"no_doc_mask"`` (attention across
documents), ``"state_bfloat16"`` (the state rounded to bfloat16 every
``STATE_BLOCK`` tokens, as a chunked form that carries it in bfloat16
would), ``"bfloat16"`` (every tensor and product in bfloat16; the caller
may also keep the tables in bfloat16, :func:`round_bfloat16`).
``"unchanged"`` (the state left as it was) is the caller's: it skips
:func:`adam_step`.
"""

from __future__ import annotations

import json
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 1024      # queries a block of the attention (memory only)
STATE_BLOCK = 64        # tokens a checkpointed block of the recurrence
L2_EPS = 1e-6           # under the root of q's and k's unit norm
VARIANTS = ("carried_state", "conv_across", "no_decay", "beta_1",
            "no_doc_mask", "state_bfloat16", "bfloat16")

PARAMETERS = """
embed [vocab, hidden]; head [vocab, hidden] (logits = h . head^T);
final_norm [hidden]; every layer i: l{i}.mixer_norm [hidden],
l{i}.ffn_norm [hidden], l{i}.w_gate, l{i}.w_up, l{i}.w_down, each
[hidden, width] (y = a . w_down^T). A linear_attention layer: l{i}.w_q,
l{i}.w_k [hidden, H dk], l{i}.w_v, l{i}.w_g [hidden, H dv], l{i}.w_a,
l{i}.w_b [hidden, H], l{i}.conv [taps, H (2 dk + dv)] (over q | k | v;
tap taps-1 weighs the token itself), l{i}.a_log, l{i}.dt_bias [H],
l{i}.o_norm [dv], l{i}.w_o [hidden, H dv] (x += y . w_o^T). A
full_attention layer: l{i}.w_q, l{i}.w_k, l{i}.w_v [hidden, H d],
l{i}.q_norm, l{i}.k_norm [H d], l{i}.w_o [hidden, H d].
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


def init_decay(seed: int, index: int, heads: int):
    """Start of a linear-attention layer's ``[a_log; dt_bias]`` [2, H],
    the public layer's: ``a_log = log U(0, 16)`` (the draw kept off 0)
    and ``dt_bias`` the inverse softplus of ``exp(U(log 0.001,
    log 0.1))``."""
    key = _key(seed, index)
    a = jax.random.uniform(jax.random.fold_in(key, 0), (heads,),
                           jnp.float32, 0.0, 16.0)
    dt = jnp.exp(jax.random.uniform(jax.random.fold_in(key, 1), (heads,),
                                    jnp.float32, np.log(0.001),
                                    np.log(0.1)))
    return jnp.stack([jnp.log(jnp.maximum(a, 1e-4)),
                      dt + jnp.log(-jnp.expm1(-dt))])


# -- the layers --------------------------------------------------------------

def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down.T


def starts(doc):
    """Where a document starts in one sequence's ``doc`` ids [S]."""
    return jnp.concatenate([jnp.ones((1,), bool), doc[1:] != doc[:-1]])


def short_conv(x, taps, doc, across=False):
    """``x`` [S, W] through the causal depthwise filter ``taps`` [K, W]:
    ``y_t = sum_j taps[j] x_{t - (K - 1 - j)}``, a term whose token lies
    in another document (or before the sequence) left out."""
    S, K = x.shape[0], taps.shape[0]
    t = jnp.arange(S)
    y = jnp.zeros_like(x)
    for j in range(K):
        back = K - 1 - j
        src = t - back
        ok = src >= 0
        if not across:
            ok &= doc[jnp.maximum(src, 0)] == doc
        y = y + jnp.where(ok[:, None], x[jnp.maximum(src, 0)], 0) * taps[j]
    return y


def delta_rule(q, k, v, g, beta, start, variant=None):
    """The recurrence token by token: ``q, k`` [S, H, dk], ``v``
    [S, H, dv], ``g, beta`` [S, H], ``start`` [S]; returns ``o``
    [S, H, dv]. Checkpointed in blocks of ``STATE_BLOCK`` tokens so that
    its gradient keeps one state a block."""
    S, H, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q, k, v, g, beta, start = xs
        if variant != "carried_state":
            state = jnp.where(start, 0.0, state)
        if variant != "no_decay":
            state = jnp.exp(g)[:, None, None] * state
        write = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
        state = state + k[:, :, None] * write[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    @jax.checkpoint
    def block(state, xs):
        if variant == "state_bfloat16":
            state = state.astype(jnp.bfloat16).astype(state.dtype)
        return jax.lax.scan(token, state, xs)

    n = min(S, STATE_BLOCK)
    blocked = jax.tree.map(lambda a: a.reshape(S // n, n, *a.shape[1:]),
                           (q, k, v, g, beta, start))
    _, o = jax.lax.scan(block, jnp.zeros((H, dk, dv), q.dtype), blocked)
    return o.reshape(S, H, dv)


def linear_attention(p, x, doc, cfg, variant=None):
    """``x`` [S, hidden] of ONE sequence, ``p`` the layer's tensors;
    returns the mixer's output (before the block's norm)."""
    H, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    dv = cfg["linear_value_head_dim"]
    S = x.shape[0]
    qkv = jnp.concatenate([x @ p["w_q"], x @ p["w_k"], x @ p["w_v"]], -1)
    qkv = jax.nn.silu(short_conv(qkv, p["conv"], doc,
                                 across=variant == "conv_across"))
    q = qkv[:, :H * dk].reshape(S, H, dk).astype(jnp.float32)
    k = qkv[:, H * dk:2 * H * dk].reshape(S, H, dk).astype(jnp.float32)
    v = qkv[:, 2 * H * dk:].reshape(S, H, dv)
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) * dk ** -0.5, unit(k)
    beta = jax.nn.sigmoid((x @ p["w_b"]).astype(jnp.float32))
    if cfg.get("linear_allow_neg_eigval") and variant != "beta_1":
        beta = 2.0 * beta
    g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        (x @ p["w_a"]).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    o = delta_rule(q.astype(x.dtype), k.astype(x.dtype), v,
                   g.astype(x.dtype), beta.astype(x.dtype), starts(doc),
                   variant)
    o = rms_norm(o, p["o_norm"], cfg["rms_norm_eps"]).reshape(S, H * dv)
    return (o * jax.nn.silu(x @ p["w_g"])) @ p["w_o"].T


def full_attention(p, x, doc, cfg, mask_docs=True):
    """Softmax attention with per-head keys, QK-norm and no rotary
    embedding, on one sequence."""
    H = cfg["num_attention_heads"]
    if cfg.get("num_key_value_heads", H) != H:
        raise NotImplementedError("grouped key-value heads")
    d = cfg["hidden_size"] // H
    S = x.shape[0]
    eps = cfg["rms_norm_eps"]
    q = rms_norm(x @ p["w_q"], p["q_norm"], eps).reshape(S, H, d)
    k = rms_norm(x @ p["w_k"], p["k_norm"], eps).reshape(S, H, d)
    v = (x @ p["w_v"]).reshape(S, H, d)

    def attend(q, q_doc, q_t):
        """A block of queries against every key."""
        scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
        allowed = q_t[:, None] >= jnp.arange(S)[None, :]
        if mask_docs:
            allowed &= q_doc[:, None] == doc[None, :]
        scores = jnp.where(allowed[None], scores.astype(jnp.float32),
                           -1e30)
        prob = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        return jnp.einsum("hqk,khd->qhd", prob, v)

    Q = min(S, QUERY_BLOCK)
    blocks = lambda a: a.reshape(S // Q, Q, *a.shape[1:])
    o = jax.lax.map(lambda b: jax.checkpoint(attend)(*b),
                    (blocks(q), blocks(doc), blocks(jnp.arange(S)))
                    ).reshape(S, H * d)
    return o @ p["w_o"].T


def layer(p, x, doc, cfg, variant=None):
    """One decoder layer on one packed sequence; a layer is a
    linear-attention layer iff it has ``a_log``."""
    if variant == "bfloat16":
        p = {k: v.astype(jnp.bfloat16) for k, v in p.items()}
    eps = cfg["rms_norm_eps"]
    if "a_log" in p:
        y = linear_attention(p, x, doc, cfg, variant)
    else:
        y = full_attention(p, x, doc, cfg,
                           mask_docs=variant != "no_doc_mask")
    x = x + rms_norm(y, p["mixer_norm"], eps)
    return x + rms_norm(swiglu(x, p["w_gate"], p["w_up"], p["w_down"]),
                        p["ffn_norm"], eps)


def head_logits(x, final_norm, head, cfg):
    h = rms_norm(x, final_norm.astype(x.dtype), cfg["rms_norm_eps"])
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

    def pull(p, x, doc, d_x):
        _, vjp = jax.vjp(lambda p, x: run(p, x, doc), p, x)
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
    """The step's loss and gradients, one sequence at a time (a
    gradient is a sum over sequences) and, inside a sequence, one layer
    at a time: forward keeping the residual that enters each layer, then
    the chain rule from the head down. The objective is the mean
    cross-entropy over the step's predicting tokens. ``batch``:
    ``tokens``, ``doc`` (``pos`` is not read: no layer has positions),
    each int32 [B, S]; ``doc`` is 0 on padding. Returns ``(ce, grads)``."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(variant)
    tokens, doc = (jnp.asarray(batch[k], jnp.int32)
                   for k in ("tokens", "doc"))
    B, L = tokens.shape[0], cfg["num_hidden_layers"]
    n_pred = jnp.sum((doc[:, 1:] == doc[:, :-1]) & (doc[:, :-1] > 0)
                     ).astype(jnp.float32)
    forward, pull, head = _programs(json.dumps(cfg, sort_keys=True),
                                    variant)
    dt = jnp.bfloat16 if variant == "bfloat16" else jnp.float32
    layers = [layer_tensors(params, i) for i in range(L)]
    grads = zeros_like(params)
    ce = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            x = params["embed"][tokens[b]].astype(dt)
            entering = []
            for i in range(L):
                entering.append(x)
                x = forward(layers[i], x, doc[b])
            ce_sum, (d_x, d_norm, d_head) = head(
                x, params["final_norm"], params["head"], tokens[b], doc[b])
            ce += float(ce_sum) / float(n_pred)
            d_x = d_x / n_pred.astype(dt)
            g = {"final_norm": d_norm / n_pred, "head": d_head / n_pred}
            for i in reversed(range(L)):
                d_p, d_x = pull(layers[i], entering.pop(), doc[b], d_x)
                g.update({f"l{i}.{k}": v.astype(jnp.float32)
                          for k, v in d_p.items()})
            g["embed"] = jnp.zeros_like(params["embed"]).at[tokens[b]].add(
                d_x.astype(jnp.float32))
            grads = _add_trees(grads, g)
    return ce, grads


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(p, m, v, g, t, lr, b1, b2, eps):
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    mhat = m / (1.0 - b1 ** t)
    vhat = v / (1.0 - b2 ** t)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v


def adam_step(params, m, v, grads, step, *, lr, b1, b2, eps):
    """One Adam step on every tensor: ``t = step + 1``, bias-corrected
    moments, ``eps`` outside the root, no weight decay. ``m`` and ``v``
    may wait on the host (numpy): a tensor's moments are on the device
    only while its step runs, and come back as numpy."""
    t = jnp.float32(step + 1)
    new_p, new_m, new_v = {}, {}, {}
    for k in params:
        new_p[k], mk, vk = _adam(params[k], jnp.asarray(m[k]),
                                 jnp.asarray(v[k]), grads[k], t, lr, b1,
                                 b2, eps)
        new_m[k], new_v[k] = np.asarray(mk), np.asarray(vk)
    return new_p, new_m, new_v


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
    """Parameters by kind from the config's keys alone: one
    ``linear_attention`` and one ``full_attention`` mixer, the
    ``feed_forward`` of a layer, a ``linear_layer`` and a ``full_layer``
    whole (their two block norms included), the ``vocabulary`` (embedding
    and head, untied), and the ``model``."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Hl, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    dv, K = cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    linear = 2 * D * Hl * dk + 2 * D * Hl * dv + 2 * D * Hl \
        + K * Hl * (2 * dk + dv) + 2 * Hl + dv + Hl * dv * D
    full = 4 * D * D + 2 * D
    ffn = 3 * D * cfg["intermediate_size"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    n_linear = sum(k == "linear_attention" for k in kinds)
    vocabulary = 2 * D * cfg["vocab_size"]
    layers = n_linear * (linear + ffn + 2 * D) \
        + (len(kinds) - n_linear) * (full + ffn + 2 * D)
    return {"linear_attention": linear, "full_attention": full,
            "feed_forward": ffn, "linear_layer": linear + ffn + 2 * D,
            "full_layer": full + ffn + 2 * D, "vocabulary": vocabulary,
            "model": layers + vocabulary + D}
