"""What one training step of the Olmo-Hybrid share needs, from the
configuration's sizes and the window's own counts: the matrix products
of the forward pass and their two transposes in the backward pass (3 x
the forward; what is recomputed to save memory is NOT counted), the
attention scores of the layers that attend, the recurrence of the
linear-attention layers in its token-by-token form (what the chunked
form adds — the products among a chunk's keys, the triangular solve —
is NOT counted), plus Adam's pass over every parameter. Never what the
implementation happens to execute, so no share can pass 100 %.

``work``: ``tokens`` (real, unpadded tokens trained), ``attended_keys``
(sum over real tokens of the keys a token attends in ONE attention
layer: its position in its document + 1), ``steps``.

The models live here and not under ``perf/work/`` for the reason
``perf/dsv2_work.py`` gives; the metrics that read them bring readers of
their own (``perf/layer_metrics/olmoh_*.py``).
"""


def layers(sizes: dict) -> dict:
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    linear = sum(k == "linear_attention" for k in kinds)
    return {"linear": linear, "full": len(kinds) - linear}


def parameters(sizes: dict) -> dict:
    """Matrix parameters by kind (a token passes each once a pass), the
    vectors beside them, and the whole count held."""
    D, F = sizes["hidden_size"], sizes["intermediate_size"]
    H, dk = sizes["linear_num_key_heads"], sizes["linear_key_head_dim"]
    dv, K = sizes["linear_value_head_dim"], sizes["linear_conv_kernel_dim"]
    n = layers(sizes)
    linear = D * H * (2 * dk + 2 * dv + 2) + H * dv * D
    linear_small = K * H * (2 * dk + dv) + 2 * H + dv
    full = 4 * D * D
    mlp = 3 * D * F
    head = D * sizes["vocab_size"]
    every = n["linear"] + n["full"]
    held = n["linear"] * (linear + linear_small) + n["full"] * (full + 2 * D) \
        + every * (mlp + 2 * D) + 2 * head + D
    return {"linear": linear, "full": full, "mlp": mlp, "head": head,
            "held": held}


def _recurrence(sizes: dict) -> float:
    """Flops a token and pass over all linear layers: the state read
    against the key, the rank-one write, the state read against the
    query, 2 d_k d_v each a head."""
    return 6.0 * sizes["linear_key_head_dim"] \
        * sizes["linear_value_head_dim"] * sizes["linear_num_key_heads"] \
        * layers(sizes)["linear"]


def _per_key(sizes: dict) -> float:
    """Flops an attended (query, key) pair over all attention layers: a
    score and a weighted value of depth ``head_dim`` a head."""
    return 2.0 * 2.0 * sizes["hidden_size"] * layers(sizes)["full"]


def step(sizes: dict, work: dict) -> dict:
    """The whole step: products, attention scores and the recurrence of
    forward and backward, and Adam's pass over every parameter."""
    p, n = parameters(sizes), layers(sizes)
    per_token = 2.0 * (n["linear"] * p["linear"] + n["full"] * p["full"]
                       + (n["linear"] + n["full"]) * p["mlp"] + p["head"]) \
        + _recurrence(sizes)
    forward = per_token * work["tokens"] \
        + _per_key(sizes) * work["attended_keys"]
    # Adam: parameter, gradient, m and v read, parameter, m and v written
    return {"flops": 3.0 * forward,
            "bytes": 28.0 * p["held"] * work["steps"]}


def recur(sizes: dict, work: dict) -> dict:
    """The recurrence alone, token by token: its flops of forward and
    backward, and its operands — q, k, v, the decay, the write strength —
    read and its output written once a pass in float32."""
    H, dk = sizes["linear_num_key_heads"], sizes["linear_key_head_dim"]
    dv = sizes["linear_value_head_dim"]
    operands = 4.0 * H * (2 * dk + 2 * dv + 2) * layers(sizes)["linear"]
    return {"flops": 3.0 * _recurrence(sizes) * work["tokens"],
            "bytes": 3.0 * operands * work["tokens"]}


def attend(sizes: dict, work: dict) -> dict:
    """The attention layers' own products over the attended keys,
    forward and backward; masked pairs are no work."""
    return {"flops": 3.0 * _per_key(sizes) * work["attended_keys"],
            "bytes": 0.0}
