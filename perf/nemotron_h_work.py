"""What one training step of the Nemotron-H share needs, from the
configuration's sizes and the window's own counts: the matrix products
of the forward pass and their two transposes in the backward pass (3 x
the forward; what is recomputed to save memory is NOT counted), the
attention's scores and weighted values at the attended (query, key)
pairs, the Mamba-2 recurrence in its token-by-token form (what the
chunked form adds — the products among a chunk's tokens — is NOT
counted), the routed experts' products over the assignments held here,
plus Adam's pass over every parameter. Never what the implementation
happens to execute (chunks, tiles, padded slots), so no share can pass
100 %.

``sizes`` is the configuration's ``program`` block under the published
names: ``n_routed_experts`` counts the experts HELD (the router keeps
``n_routed_experts * ep_size`` outputs); a layer's kind is its letter of
``hybrid_override_pattern``. ``work``: ``tokens`` (real, unpadded
tokens trained), ``assignments`` ((token, expert held here) pairs, all
layers), ``attended_keys`` (sum over real tokens of the keys a token
attends in ONE attention layer: its position in its document + 1),
``steps``.
"""


def layers(sizes: dict) -> dict:
    """Layers of each kind: ``ssm`` (M), ``attention`` (*), ``experts``
    (E), ``dense`` (-)."""
    letters = sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]
    return {kind: letters.count(letter) for kind, letter in
            (("ssm", "M"), ("attention", "*"), ("experts", "E"),
             ("dense", "-"))}


def parameters(sizes: dict) -> dict:
    """Matrix parameters by kind (a token passes each once a pass, an
    expert's once an assignment), and the whole count held."""
    D = sizes["hidden_size"]
    H, P = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    GN = sizes["n_groups"] * sizes["ssm_state_size"]
    ssm = D * (2 * H * P + 2 * GN + H) + H * P * D
    ssm_small = (sizes["conv_kernel"] + 1) * (H * P + 2 * GN) + 3 * H \
        + H * P
    Hq, G, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                sizes["head_dim"])
    attention = 2 * D * Hq * d + 2 * D * G * d
    expert = 2 * D * sizes["moe_intermediate_size"]
    shared = 2 * D * sizes["moe_shared_expert_intermediate_size"] \
        * sizes["n_shared_experts"]
    E = sizes["n_routed_experts"] * sizes["ep_size"]
    router = D * E
    dense = 2 * D * sizes["intermediate_size"]
    head = D * sizes["vocab_size"]
    n = layers(sizes)
    held = n["ssm"] * (ssm + ssm_small) + n["attention"] * attention \
        + n["experts"] * (sizes["n_routed_experts"] * expert + shared
                          + router + E) \
        + n["dense"] * dense + 2 * head \
        + (sizes["num_hidden_layers"] + 1) * D
    return {"ssm": ssm, "attention": attention, "expert": expert,
            "shared": shared, "router": router, "dense": dense,
            "head": head, "held": held}


def _recurrence(sizes: dict) -> float:
    """Flops a token and pass over all M layers: the state's write (the
    outer product of ``B`` and ``dt x``) and its read against ``C``,
    2 N P each a head."""
    return 4.0 * sizes["ssm_state_size"] * sizes["mamba_head_dim"] \
        * sizes["mamba_num_heads"] * layers(sizes)["ssm"]


def _per_key(sizes: dict) -> float:
    """Flops an attended (query, key) pair over all attention layers: a
    score and a weighted value of depth ``head_dim`` a QUERY head."""
    return 2.0 * 2.0 * sizes["num_attention_heads"] * sizes["head_dim"] \
        * layers(sizes)["attention"]


def step(sizes: dict, work: dict) -> dict:
    """The whole step: products, the recurrence, attention at the
    attended pairs, the held experts' products, forward and backward,
    and Adam's pass over every parameter."""
    p, n = parameters(sizes), layers(sizes)
    per_token = 2.0 * (n["ssm"] * p["ssm"]
                       + n["attention"] * p["attention"]
                       + n["experts"] * (p["shared"] + p["router"])
                       + n["dense"] * p["dense"] + p["head"]) \
        + _recurrence(sizes)
    forward = per_token * work["tokens"] \
        + _per_key(sizes) * work["attended_keys"] \
        + 2.0 * p["expert"] * work["assignments"]
    # Adam: parameter, gradient, m and v read, parameter, m and v written
    return {"flops": 3.0 * forward,
            "bytes": 28.0 * p["held"] * work["steps"]}


def scan(sizes: dict, work: dict) -> dict:
    """The recurrence alone, token by token: its flops of forward and
    backward, and its operands — ``x``, ``B``, ``C``, ``dt`` — read and
    its output written once a pass in float32."""
    H, P = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    GN = sizes["n_groups"] * sizes["ssm_state_size"]
    operands = 4.0 * (2 * H * P + 2 * GN + H) * layers(sizes)["ssm"]
    return {"flops": 3.0 * _recurrence(sizes) * work["tokens"],
            "bytes": 3.0 * operands * work["tokens"]}
