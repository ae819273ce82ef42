"""What one training step of the LFM2-MoE share needs, from the
configuration's sizes and the window's own counts: the matrix products
of the forward pass and their two transposes in the backward pass (3 x
the forward; what is recomputed to save memory is NOT counted), the
attention scores of the layers that attend (the lanes a head of 64 dims
is padded to are NOT counted), the short convolutions' gates and taps,
the routed experts' products over the assignments held here, plus Adam's
pass over every parameter. Never what the implementation happens to
execute, so no share can pass 100 %.

``sizes`` is the configuration's ``program`` block under the published
names: ``num_experts`` counts the experts HELD (the router keeps
``num_experts * ep_size`` outputs). ``work``: ``tokens`` (real, unpadded
tokens trained), ``assignments`` ((token, expert held here) pairs, all
expert layers), ``attended_keys`` (sum over real tokens of the keys a
token attends in ONE attention layer: its position in its document + 1),
``steps``.

The models live here and not under ``perf/work/`` for the reason
``perf/dsv2_work.py`` gives; the metrics that read them bring readers of
their own (``perf/layer_metrics/lfm2_*.py``), which share
:func:`scope_roofline`.
"""

from perf import peaks, program_readers


def scope_roofline(ctx: dict, model, scope: str):
    """Least time for ``model(sizes, work)`` over the device time of the
    ops of ``jit_run`` the program scoped ``scope``, in %; ``None`` where
    nothing ran under it."""
    by_scope = program_readers.scope_seconds(ctx, "jit_run")
    seconds = (by_scope or {}).get(scope, 0.0)
    if seconds <= 0.0 or not ctx["work"]:
        return None
    least = peaks.least_seconds(model(ctx["sizes"], ctx["work"]),
                                ctx["device_kind"], ctx["chips"])
    return 100.0 * least["seconds"] / seconds


def layers(sizes: dict) -> dict:
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    conv = sum(k == "conv" for k in kinds)
    dense = min(sizes["num_dense_layers"], len(kinds))
    return {"conv": conv, "full": len(kinds) - conv, "dense": dense,
            "expert": len(kinds) - dense}


def parameters(sizes: dict) -> dict:
    """Matrix parameters by kind (a token passes each once a pass, an
    expert's once an assignment), and the whole count held."""
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    d = D // H
    kv = sizes["num_key_value_heads"] * d
    n = layers(sizes)
    conv = 3 * D * D + D * D
    full = 2 * D * D + 2 * D * kv
    dense = 3 * D * sizes["intermediate_size"]
    expert = 3 * D * sizes["moe_intermediate_size"]
    outputs = sizes["num_experts"] * sizes["ep_size"]
    router = D * outputs
    head = D * sizes["vocab_size"]          # ONE table: embedding and head
    held = n["conv"] * (conv + sizes["conv_L_cache"] * D) \
        + n["full"] * (full + 2 * d) + n["dense"] * dense \
        + n["expert"] * (router + outputs
                         + sizes["num_experts"] * expert) \
        + head + (2 * (n["conv"] + n["full"]) + 1) * D
    return {"conv": conv, "full": full, "dense": dense, "expert": expert,
            "router": router, "head": head, "held": held}


def _mix_flops(sizes: dict) -> float:
    """Flops a token and pass over all conv layers: ``B * X``, the taps'
    multiply-adds, ``C *``."""
    return (2.0 * sizes["conv_L_cache"] + 2.0) * sizes["hidden_size"] \
        * layers(sizes)["conv"]


def _per_key(sizes: dict) -> float:
    """Flops an attended (query, key) pair over all attention layers: a
    score and a weighted value of depth ``head_dim`` a QUERY head."""
    return 2.0 * 2.0 * sizes["hidden_size"] * layers(sizes)["full"]


def step(sizes: dict, work: dict) -> dict:
    """The whole step: products, attention scores, gates and taps and
    the experts' products of forward and backward, and Adam's pass over
    every parameter."""
    p, n = parameters(sizes), layers(sizes)
    per_token = 2.0 * (n["conv"] * p["conv"] + n["full"] * p["full"]
                       + n["dense"] * p["dense"]
                       + n["expert"] * p["router"] + p["head"]) \
        + _mix_flops(sizes)
    forward = per_token * work["tokens"] \
        + _per_key(sizes) * work["attended_keys"] \
        + 2.0 * p["expert"] * work["assignments"]
    # Adam: parameter, gradient, m and v read, parameter, m and v written
    return {"flops": 3.0 * forward,
            "bytes": 28.0 * p["held"] * work["steps"]}


def conv_mix(sizes: dict, work: dict) -> dict:
    """The gates and taps alone: per token and conv layer ``B``, ``C``
    and ``X`` read and one D-wide row written in float32 forward, twice
    that backward."""
    D, n = sizes["hidden_size"], layers(sizes)["conv"]
    return {"flops": 3.0 * _mix_flops(sizes) * work["tokens"],
            "bytes": 3.0 * 4.0 * (3 * D + D) * n * work["tokens"]}


def attend(sizes: dict, work: dict) -> dict:
    """The attention layers' own products over the attended keys,
    forward and backward; masked pairs are no work. Per token and layer
    the queries read and the output written at ``H`` heads, keys and
    values read once a GROUP (``G`` heads), in the products' bfloat16,
    forward; twice that backward."""
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    kv = sizes["num_key_value_heads"] * (D // H)
    return {"flops": 3.0 * _per_key(sizes) * work["attended_keys"],
            "bytes": 3.0 * 2.0 * (2 * D + 2 * kv) * layers(sizes)["full"]
            * work["tokens"]}


def experts(sizes: dict, work: dict) -> dict:
    """The routed experts' grouped products alone: per (token, expert
    held here) pair three products of ``hidden x width`` forward and
    their two transposes backward; per step and expert layer the
    experts' weights read once a pass in the products' bfloat16 and their
    float32 gradient written once; per pair its row read and written in
    each pass."""
    D, F = sizes["hidden_size"], sizes["moe_intermediate_size"]
    weights = sizes["num_experts"] * 3 * D * F
    pairs = float(work["assignments"])
    return {"flops": 3.0 * 2.0 * 3.0 * D * F * pairs,
            "bytes": work["steps"] * layers(sizes)["expert"] * weights
            * (3 * 2.0 + 4.0) + pairs * 3 * D * (2.0 + 4.0)}
