"""Plain reference of the Nemotron-H decoder (Mamba-2 layers beside
relu^2 expert layers and grouped attention) as one chip of an
expert-parallel, vocabulary-parallel group of a pipeline stage trains
it: loss, gradients, the Adam step and the selection bias's own step, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
It imports nothing of the program; every tensor goes by its published
role in a flat dict (``PARAMETERS`` below). The generic pieces (the
start draw, the RMS norm, the causal taps, Adam, the bias's step) are
the LFM2 reference's (``perf/reference/lfm2.py``).

Source: huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
``config.json`` (``model_type`` ``nemotron_h``), computed as the public
``transformers`` implementation of that model type computes it, written
from knowledge of that code and of the config's keys. Layer ``i`` is
ONE part, named by the ``i``-th letter of ``hybrid_override_pattern``,
behind one pre-norm:

    x' = x + Part_i(RMSNorm(x; norm))

after the last layer one RMSNorm (``final_norm``), then ``logits =
RMSNorm(x) W_head^T`` (untied).

- ``M`` (Mamba-2, arXiv:2405.21060): ``z | x | B | C | dt_raw = u W_in``
  (``x`` H heads of P, ``B``, ``C`` G groups of N, head ``h`` reads
  group ``h // (H / G)``); ``x | B | C`` through a causal depthwise
  filter of ``conv_kernel`` taps with a bias (``conv_w``, ``conv_b``),
  then ``silu``; ``dt = softplus(dt_raw + dt_bias)``, ``A =
  -exp(A_log)``; TOKEN BY TOKEN, ``h_t = exp(dt_t A) h_{t-1} + B_t
  (dt_t x_t)^T`` and ``y_t = h_t^T C_t + D x_t``; then ``g = y * silu(z)``
  through an RMS norm over each of the G groups of channels, times
  ``ssm_norm``, and ``Part = g W_out^T``.
- ``*`` (attention): ``q = u W_q`` as H heads of ``head_dim``, ``k``,
  ``v`` as G = ``num_key_value_heads`` heads; NO QK-norm and NO rotary
  embedding (the family's attention applies none); query head ``h``
  reads key-value head ``h // (H / G)``; ``softmax(q k^T /
  sqrt(head_dim))``, causal; ``Part = concat_h(o_h) W_o^T``.
- ``E`` (experts): ``s = sigmoid(u W_r)`` in float32 over all
  ``n_routed_experts`` outputs; chosen: the ``num_experts_per_tok``
  largest of ``s + b`` (``b`` = ``expert_bias``, the published
  ``e_score_correction_bias``; ``n_group`` 1: no group limit); weights:
  ``s`` at the chosen divided by their sum + 1e-20 (``norm_topk_prob``),
  times ``routed_scaling_factor``; ``Part = sum_chosen weight_e
  expert_e(u) + shared(u)``, an expert (and the shared one)
  ``down(relu(up u)^2)``. ``b`` gets no gradient; after each step
  ``b_e += gamma sign(mean c - c_e)`` (:func:`bias_step`, the LFM2
  reference's rule).
- ``-`` (dense): ``down(relu(up u)^2)``.

Departures from a plain reading, each on purpose:

- **Packed documents.** The state is 0 before a document's first token;
  a convolution tap that would reach into an earlier document reads 0;
  a token attends only to earlier tokens of its own document.
- **The share.** ``ep_size`` chips share each expert layer: this one
  holds experts ``ep_rank * held … (ep_rank + 1) * held - 1``; the router
  keeps all outputs and choices, and what the absent experts would add
  is left out; the shared expert is whole here. The vocabulary rows held
  here ARE the vocabulary: ids, logits and loss are over the slice.
- **The loss** is the mean cross-entropy over the tokens that have a
  successor in their document; no balance loss (the config has none).
- **Adam** as the program's ``updaters/updaters.py`` writes it, no
  weight decay, no gradient clipping.
- **The program** adds 1e-6 under the chosen scores' sum where the
  published code adds 1e-20: a relative change of the weights of about
  3e-7, below every limit.

``variant`` runs a control, a deliberately wrong reference that
``correct`` must tell from the right one: ``"decay_bfloat16"`` (the
log decay's running sums over blocks of 128 tokens, as a chunked scan
forms them, rounded to bfloat16),
``"no_restart"`` (the state carried into the next document),
``"bfloat16"`` (every tensor and product in bfloat16; the caller may
also keep the tables in bfloat16, :func:`round_bfloat16`).
``"bias_frozen"`` (the bias never stepped) and ``"unchanged"`` (no state
stepped) are the caller's.
"""

from __future__ import annotations

import json
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.lfm2 import (QUERY_BLOCK, adam_step, bias_step,  # noqa: F401
                                 causal_taps, host_zeros_like, init_normal,
                                 is_bias, rms_norm, round_bfloat16,
                                 zeros_like)

STATE_BLOCK = 128       # tokens a checkpointed block of the recurrence
UNROLL = 8              # tokens a step of the loop inside a block
VARIANTS = ("decay_bfloat16", "no_restart", "bfloat16")

PARAMETERS = """
embed, head [vocab, hidden]; final_norm [hidden]; every layer i:
l{i}.norm [hidden]. An M layer: l{i}.w_z, l{i}.w_x [hidden, H P],
l{i}.w_B, l{i}.w_C [hidden, G N], l{i}.w_dt [hidden, H], l{i}.conv_w
[taps, H P + 2 G N] (tap taps-1 weighs the token itself), l{i}.conv_b
[H P + 2 G N], l{i}.a_log, l{i}.dt_bias, l{i}.d_skip [H], l{i}.ssm_norm
[H P], l{i}.w_out [hidden, H P] (x += g . w_out^T). A * layer: l{i}.w_q,
l{i}.w_o [hidden, H d], l{i}.w_k, l{i}.w_v [hidden, G d]. An E layer:
l{i}.router [hidden, experts], l{i}.expert_bias [experts],
l{i}.shared_up / _down [hidden, shared width], l{i}.exp_up / _down
[held, hidden, width] (down stored [hidden, width]). A - layer:
l{i}.w_up / _down [hidden, width].
"""


def init_decay(seed: int, index: int, heads: int, cfg: dict):
    """An M layer's ``[A_log; dt_bias; D]`` [3, H] at start, drawn with
    its own code from the same key: ``A_log = log U(1, 16)``, ``dt_bias``
    the inverse softplus of ``exp(U(log time_step_min, log
    time_step_max))`` floored at ``time_step_floor``, ``D = 1``."""
    from perf.reference.lfm2 import _key
    key = _key(seed, index)
    a = jax.random.uniform(jax.random.fold_in(key, 0), (heads,),
                           jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(
        jax.random.fold_in(key, 1), (heads,), jnp.float32,
        float(np.log(cfg["time_step_min"])),
        float(np.log(cfg["time_step_max"]))))
    dt = jnp.maximum(dt, cfg["time_step_floor"])
    return jnp.stack([jnp.log(a), dt + jnp.log(-jnp.expm1(-dt)),
                      jnp.ones((heads,), jnp.float32)])


def init_conv(seed: int, index: int, shape, taps: int):
    """An M layer's filter and bias at start: ``U(-b, b)``, ``b = 1 /
    sqrt(taps)`` (torch's ``Conv1d`` default, a channel's fan-in its
    taps), from the same key."""
    from perf.reference.lfm2 import _key
    bound = float(taps) ** -0.5
    return jax.random.uniform(_key(seed, index), tuple(shape), jnp.float32,
                              -bound, bound)


def relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down.T


# -- the Mamba-2 layer -----------------------------------------------------------

def _decay_summed_in_bfloat16(log_decay, start):
    """The ``"decay_bfloat16"`` control's decay a token: the log decay's
    running sums over blocks of ``STATE_BLOCK`` tokens (restarting at a
    document start), as a chunked scan forms them, rounded to bfloat16,
    and each token's decay the exponent of the difference of two."""
    S = log_decay.shape[0]
    first = start | (jnp.arange(S) % STATE_BLOCK == 0)

    def running(total, xs):
        a, restart = xs
        total = jnp.where(restart, a, total + a)
        return total, total

    _, summed = jax.lax.scan(running, jnp.zeros_like(log_decay[0]),
                             (log_decay, first))
    summed = summed.astype(jnp.bfloat16).astype(log_decay.dtype)
    before = jnp.concatenate([jnp.zeros_like(summed[:1]), summed[:-1]])
    return jnp.exp(jnp.where(first[:, None], summed, summed - before))


def recurrence(x, b, c, dt, a, start, variant=None):
    """The state-space recurrence token by token: ``x`` [S, H, P], ``b``,
    ``c`` [S, H, N] (each head's group), ``dt`` [S, H], ``a`` = ``-exp(
    A_log)`` [H], ``start`` [S]; returns ``y`` [S, H, P] before ``D x``.
    Checkpointed in blocks of ``STATE_BLOCK`` tokens so that its
    gradient keeps one state a block."""
    S, H, P = x.shape
    N = b.shape[-1]
    decay = _decay_summed_in_bfloat16(dt * a, start) \
        if variant == "decay_bfloat16" else jnp.exp(dt * a)
    if variant != "no_restart":
        decay = jnp.where(start[:, None], 0.0, decay)

    def token(state, xs):
        x, b, c, dt, decay = xs
        state = decay[:, None, None] * state \
            + b[:, :, None] * (dt[:, None] * x)[:, None, :]
        return state, jnp.einsum("hnp,hn->hp", state, c)

    @jax.checkpoint
    def block(state, xs):
        # unrolled: a token's few small ops are under a loop step's cost
        return jax.lax.scan(token, state, xs, unroll=UNROLL)

    n = min(S, STATE_BLOCK)
    blocked = jax.tree.map(lambda v: v.reshape(S // n, n, *v.shape[1:]),
                           (x, b, c, dt, decay))
    _, y = jax.lax.scan(block, jnp.zeros((H, N, P), x.dtype), blocked)
    return y.reshape(S, H, P)


def mamba(p, u, doc, cfg, variant=None):
    """The Mamba-2 mixer on one sequence's normed input ``u``
    [S, hidden]."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    N, G = cfg["ssm_state_size"], cfg["n_groups"]
    S = u.shape[0]
    z = u @ p["w_z"]
    xbc = jnp.concatenate([u @ p["w_x"], u @ p["w_B"], u @ p["w_C"]], -1)
    xbc = jax.nn.silu(causal_taps(xbc, p["conv_w"], doc) + p["conv_b"])
    x = xbc[:, :H * P].reshape(S, H, P)
    per_head = lambda v: jnp.repeat(v.reshape(S, G, N), H // G, axis=1)
    b = per_head(xbc[:, H * P:H * P + G * N])
    c = per_head(xbc[:, H * P + G * N:])
    dt = jax.nn.softplus((u @ p["w_dt"]).astype(jnp.float32)
                         + p["dt_bias"]).astype(u.dtype)
    a = -jnp.exp(p["a_log"].astype(jnp.float32)).astype(u.dtype)
    start = jnp.concatenate([jnp.ones((1,), bool), doc[1:] != doc[:-1]])
    y = recurrence(x, b, c, dt, a, start, variant) + p["d_skip"][:, None] * x
    g = (y.reshape(S, H * P) * jax.nn.silu(z)).reshape(S, G, -1)
    g32 = g.astype(jnp.float32)
    g = (g32 * jax.lax.rsqrt(jnp.mean(g32 * g32, -1, keepdims=True)
                             + cfg["norm_eps"])).astype(u.dtype)
    return (g.reshape(S, H * P) * p["ssm_norm"]) @ p["w_out"].T


# -- attention and the experts -------------------------------------------------

def attention(p, u, doc, cfg):
    """Grouped-query softmax attention without QK-norm and without a
    rotary embedding, causal inside the documents, on one sequence's
    normed input."""
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, S = cfg["head_dim"], u.shape[0]
    q = (u @ p["w_q"]).reshape(S, H, d)
    of = jnp.arange(H) // (H // G)      # a query head's key-value head
    k = (u @ p["w_k"]).reshape(S, G, d)[:, of]
    v = (u @ p["w_v"]).reshape(S, G, d)[:, of]

    def attend(q, q_doc, q_t):
        """A block of queries against every key."""
        scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
        allowed = (q_t[:, None] >= jnp.arange(S)[None, :]) \
            & (q_doc[:, None] == doc[None, :])
        scores = jnp.where(allowed[None], scores.astype(jnp.float32),
                           -1e30)
        prob = jax.nn.softmax(scores, axis=-1).astype(u.dtype)
        return jnp.einsum("hqk,khd->qhd", prob, v)

    Q = min(S, QUERY_BLOCK)
    blocks = lambda a: a.reshape(S // Q, Q, *a.shape[1:])
    o = jax.lax.map(lambda blk: jax.checkpoint(attend)(*blk),
                    (blocks(q), blocks(doc), blocks(jnp.arange(S)))
                    ).reshape(S, H * d)
    return o @ p["w_o"].T


def held_experts(cfg):
    held = cfg["n_routed_experts"] // cfg.get("ep_size", 1)
    return cfg.get("ep_rank", 0) * held, held


def route(p, h, cfg):
    """Scores over ALL the router's outputs in float32, the chosen
    experts and their weights (``h`` is one sequence)."""
    logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(p["expert_bias"].astype(jnp.float32))
    top_e = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])[1]
    top_s = jnp.take_along_axis(s, top_e, -1)
    if cfg.get("norm_topk_prob"):
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return top_s * cfg.get("routed_scaling_factor", 1.0), top_e


def routed(p, h, real, cfg):
    """What the experts held here add to one sequence's normed input;
    returns (that, counts of the real tokens over all experts [E],
    chosen experts [S, k])."""
    first, held = held_experts(cfg)
    top_s, top_e = route(p, h, cfg)
    chose = jax.nn.one_hot(top_e, cfg["n_routed_experts"],
                           dtype=jnp.float32).sum(1) * real[:, None]

    def one(y, expert):
        e, up, down = expert
        took = (top_e == first + e) & (real[:, None] > 0)       # [S, k]
        # the expert on EVERY token of the sequence, weight 0 where the
        # token did not choose it
        weight = jnp.sum(jnp.where(took, top_s, 0.0), 1)
        return y + weight[:, None].astype(h.dtype) * relu2(h, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(held), p["exp_up"], p["exp_down"]))
    return y, chose.sum(0), top_e


def layer(p, x, doc, cfg, variant=None):
    """One layer on one packed sequence: the residual after it, and what
    it routed (zeros in a layer without experts). The layer's kind is
    what its tensors are: ``w_z`` an M layer, ``w_q`` attention,
    ``router`` experts, else dense."""
    real = (doc > 0).astype(jnp.float32)
    if variant == "bfloat16":
        p = {k: v.astype(jnp.bfloat16) for k, v in p.items()}
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    nothing = (jnp.zeros((E,)), jnp.zeros((x.shape[0], k), jnp.int32))
    u = rms_norm(x, p["norm"], cfg["norm_eps"])
    if "w_z" in p:
        return x + mamba(p, u, doc, cfg, variant), nothing
    if "w_q" in p:
        return x + attention(p, u, doc, cfg), nothing
    if "router" not in p:
        return x + relu2(u, p["w_up"], p["w_down"]), nothing
    y, counts, top_e = routed(p, u, real, cfg)
    return x + y + relu2(u, p["shared_up"], p["shared_down"]), (counts,
                                                               top_e)


def head_loss(x, final_norm, head, tokens, doc, cfg):
    """Sum of the cross-entropy over the sequence's predicting tokens."""
    h = rms_norm(x, final_norm.astype(x.dtype), cfg["norm_eps"])
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
    """The jitted pieces a sequence goes through (the layers of a kind
    share a program): a layer forward, a layer's vector-Jacobian product
    from the residual that entered it (the layer is computed again), and
    the head's loss with its gradients."""
    cfg = json.loads(cfg_json)
    run = partial(layer, cfg=cfg, variant=variant)

    def pull(p, x, doc, d_x):
        _, vjp, _ = jax.vjp(lambda p, x: run(p, x, doc), p, x,
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
    the chain rule from the head down. ``batch``: ``tokens``, ``doc``,
    each int32 [B, S]; ``doc`` is 0 on padding. Returns ``(ce, grads,
    aux)``: ``grads["l{i}.expert_bias"]`` is the bias's delta ``sign(c -
    mean c)`` of the step's counts; aux ``counts`` [expert layers, E],
    ``chosen`` [expert layers, B * S, k]."""
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
    expert = [i for i in range(L) if "router" in layers[i]]
    grads = zeros_like(params)
    ce, counts, chosen = 0.0, 0.0, []
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            x = params["embed"][tokens[b]].astype(dt)
            entering, routes = [], []
            for i in range(L):
                entering.append(x)
                x, r = forward(layers[i], x, doc[b])
                routes.append(r)
            ce_sum, (d_x, d_norm, d_head) = head(
                x, params["final_norm"], params["head"], tokens[b], doc[b])
            ce += float(ce_sum) / float(n_pred)
            d_x = d_x / n_pred.astype(dt)
            g = {"final_norm": d_norm / n_pred,
                 "head": d_head.astype(jnp.float32) / n_pred}
            for i in reversed(range(L)):
                d_p, d_x = pull(layers[i], entering.pop(), doc[b], d_x)
                g.update({f"l{i}.{k}": v.astype(jnp.float32)
                          for k, v in d_p.items()})
            g["embed"] = jnp.zeros_like(params["embed"]).at[tokens[b]].add(
                d_x.astype(jnp.float32))
            grads = _add_trees(grads, g)
            if expert:
                counts = counts + np.stack([np.asarray(routes[i][0])
                                            for i in expert])
                chosen.append(np.stack([np.asarray(routes[i][1])
                                        for i in expert]))
    for n, i in enumerate(expert):
        c = jnp.asarray(counts[n], jnp.float32)
        grads[f"l{i}.expert_bias"] = jnp.sign(c - jnp.mean(c))
    return ce, grads, {"counts": counts,
                       "chosen": np.concatenate(chosen, axis=1)
                       if chosen else None}


def parameter_counts(cfg: dict) -> dict:
    """Parameters by kind from the config's keys alone: an ``ssm``
    layer (projections, filter and bias, ``A_log``, ``dt_bias``, ``D``,
    the gated norm), an ``attention`` layer, one routed ``expert``, the
    ``shared`` expert, a ``router`` (its bias included), a ``dense``
    layer, the ``vocabulary`` (embedding and head), the ``norms`` (one a
    layer and the final one), and the ``model`` held here, its layers
    the first ``num_hidden_layers`` letters of the pattern."""
    D = cfg["hidden_size"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    GN = cfg["n_groups"] * cfg["ssm_state_size"]
    conv = H * P + 2 * GN
    ssm = D * (2 * H * P + 2 * GN + H) + (cfg["conv_kernel"] + 1) * conv \
        + 3 * H + H * P + D * H * P
    Hq, G, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    attention_ = 2 * D * Hq * d + 2 * D * G * d
    expert = 2 * D * cfg["moe_intermediate_size"]
    shared = 2 * D * cfg["moe_shared_expert_intermediate_size"]
    router = D * cfg["n_routed_experts"] + cfg["n_routed_experts"]
    dense = 2 * D * cfg["intermediate_size"]
    vocabulary = 2 * D * cfg["vocab_size"]
    L = cfg["num_hidden_layers"]
    _, held = held_experts(cfg)
    by_letter = {"M": ssm, "*": attention_, "-": dense,
                 "E": held * expert + shared + router}
    model = sum(by_letter[k] for k in cfg["hybrid_override_pattern"][:L]) \
        + vocabulary + (L + 1) * D
    return {"ssm": ssm, "attention": attention_, "expert": expert,
            "shared": shared, "router": router, "dense": dense,
            "vocabulary": vocabulary, "model": model}
