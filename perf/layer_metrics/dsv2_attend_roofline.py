"""Least time for the attention's own products over the device time of
the ops of ``jit_run`` the program scoped ``lm.mla.attend``.

The work: per attended (query, key) pair, head and layer one score of
depth ``qk_nope_head_dim + qk_rope_head_dim`` and one weighted value of
depth ``v_head_dim`` forward and their two transposes backward (3 x the
forward) — the ``per_key`` term of ``perf/dsv2_work.py`` ``step`` times
the window's ``attended_keys``. What the layer recomputes is not
counted, and a pair the mask forbids is no work, so the share cannot
pass 100 %. The operands' bytes (a sequence's queries, keys and values
read once a pass) never bound it at these depths and are left out."""

from perf import peaks, program_readers


def work(sizes: dict, counted: dict) -> dict:
    per_key = 2.0 * sizes["num_attention_heads"] \
        * sizes["num_hidden_layers"] * (sizes["qk_nope_head_dim"]
                                        + sizes["qk_rope_head_dim"]
                                        + sizes["v_head_dim"])
    return {"flops": 3.0 * per_key * counted["attended_keys"],
            "bytes": 0.0}


def read(ctx):
    by_scope = program_readers.scope_seconds(ctx, "jit_run")
    seconds = (by_scope or {}).get("lm.mla.attend", 0.0)
    if seconds <= 0.0 or not ctx["work"]:
        return None
    least = peaks.least_seconds(work(ctx["sizes"], ctx["work"]),
                                ctx["device_kind"], ctx["chips"])
    return 100.0 * least["seconds"] / seconds
