"""What one training step of the DeepSeek-V2 share needs, from the
configuration's sizes and the window's own counts: the matrix products
and attention scores of the forward pass and their two transposes in
the backward pass (3 x the forward; what is recomputed to save memory is
NOT counted), plus Adam's pass over every parameter. Never what the
implementation happens to execute, so no share can pass 100 %.

``work``: ``tokens`` (real, unpadded tokens trained), ``assignments``
((token, expert held here) pairs, all expert layers), ``attended_keys``
(sum over real tokens of the keys a token attends: its position in its
document + 1), ``steps``.

The models live here and not under ``perf/work/``: the accepted layout
test creates that directory in its copy of ``perf/`` itself and fails on
a tree that already has it; the two metrics that read them bring readers
of their own (``perf/layer_metrics/dsv2_step_mfu.py``,
``dsv2_experts_roofline.py``).
"""


def parameters(sizes: dict) -> dict:
    """Parameters by kind; ``n_routed_experts`` counts the experts held."""
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    vd, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    F = sizes["moe_intermediate_size"]
    dense = sizes["first_k_dense_replace"]
    moe = sizes["num_hidden_layers"] - dense
    return {
        "attention": D * H * (nope + rope) + D * (rank + rope)
        + rank * H * (nope + vd) + H * vd * D,
        "dense_mlp": 3 * D * sizes["intermediate_size"],
        "shared": 3 * D * F * sizes["n_shared_experts"],
        "router": D * sizes["n_routed_experts"] * sizes["ep_size"],
        "expert": 3 * D * F,
        "head": D * sizes["vocab_size"],
        "norms": (3 * sizes["num_hidden_layers"] + 1) * D,
        "dense_layers": dense, "moe_layers": moe}


def step(sizes: dict, work: dict) -> dict:
    """The whole step: products and attention scores of forward and
    backward, and Adam's pass over every parameter."""
    p = parameters(sizes)
    layers = p["dense_layers"] + p["moe_layers"]
    per_token = 2.0 * (layers * p["attention"]
                       + p["dense_layers"] * p["dense_mlp"]
                       + p["moe_layers"] * (p["shared"] + p["router"])
                       + p["head"])
    per_key = 2.0 * sizes["num_attention_heads"] * layers * (
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
        + sizes["v_head_dim"])
    forward = per_token * work["tokens"] \
        + per_key * work["attended_keys"] \
        + 2.0 * p["expert"] * work["assignments"]
    held = layers * p["attention"] + p["dense_layers"] * p["dense_mlp"] \
        + p["moe_layers"] * (p["shared"] + p["router"]
                             + sizes["n_routed_experts"] * p["expert"]) \
        + 2 * p["head"] + p["norms"]
    # Adam: parameter, gradient, m and v read, parameter, m and v written
    return {"flops": 3.0 * forward,
            "bytes": 28.0 * held * work["steps"]}


def experts(sizes: dict, work: dict) -> dict:
    """The routed experts' grouped products alone: per (token, expert
    held here) pair three products of ``hidden x width`` forward and
    their two transposes backward; per step and expert layer the
    experts' weights read once a pass in the products' bfloat16 and their
    float32 gradient written once; per pair its row read and written in
    each pass."""
    D, F = sizes["hidden_size"], sizes["moe_intermediate_size"]
    layers = sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]
    weights = sizes["n_routed_experts"] * 3 * D * F
    pairs = float(work["assignments"])
    return {"flops": 3.0 * 2.0 * 3.0 * D * F * pairs,
            "bytes": work["steps"] * layers * weights * (3 * 2.0 + 4.0)
            + pairs * 3 * D * (2.0 + 4.0)}
