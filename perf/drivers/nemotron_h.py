"""Driver of the Nemotron-H training cell: builds
``apps.transformer_lm.TransformerLM`` from the configuration's
``program`` block — Mamba-2 layers beside relu^2 expert layers and
grouped attention without QK-norm or rotary embedding, one part a layer
(``hybrid_override_pattern``), a sigmoid router whose choice a bias
table steers, as one expert-parallel rank of sixteen — feeds it packed
documents drawn from the seed, and trains through
``TransformerLM.train(total_steps=...)`` as a user does. The tables
start from the configuration's own draw (``program.init_seed``), the
same for every ``--seed``.

It is the LFM2-MoE driver (``perf/drivers/lfm2.py``: the Olmo-Hybrid
and DeepSeek-V2-Lite drivers' set-up, window and documents, the packer's
pool, the checked first steps running on until one holds a document
boundary, the routing's and the bias tables' comparison) with this
model's reference and comparison: ``correct``
replays the same packed steps from the same start through the plain
reference (``perf/reference/nemotron_h.py``: float32, the Mamba-2
recurrence token by token) after the window, a sequence and a layer at
a time, and compares what the timed object produced: the loss of every
checked step; of the steps whose whole aux the program kept (its last
four) the norm of every table's gradient, the worst of each kind of
table apart (``grad_norm_gap_ssm``, ``_attention``, ``_router``,
``_experts``, ``_vocabulary``, ``_norms``), three gradients entry by
entry (the first
1,024 rows of the embedding; expert 0 of the first expert layer; the
first Mamba-2 layer's ``A_log``, ``dt_bias`` and ``D``, which the scan's
decay and restarts move most directly) and
the experts each real token chose (``routing_mismatch``); every table's
change after the last checked step; the bias tables as the LFM2 driver
holds them (``bias_mismatch``); and — exactly — that nothing was
dropped, that the routed counts are a recount of the routing, and that
every real token of the window was trained.

``Cell.control`` names a deliberately wrong reference (``VARIANTS`` of
the reference, ``"bias_frozen"`` or ``"unchanged"``):
``perf/tests/calibrate_nemotron_h.py`` runs ``check()`` once a control
to read what each fails.

A program whose ``LMConfig`` reads no ``hybrid_override_pattern`` (the
parent of the PR that brought this cell) cannot build the
configuration: ``setup`` says so and the run ends at once, with no
result.
"""

from __future__ import annotations

import dataclasses
import os
import re
import time

import numpy as np

from perf import program
from perf.reference import nemotron_h as ref

lfm2 = program.load_module(
    "perf_driver_lfm2",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "lfm2.py"))
dsv2 = lfm2.dsv2

TINY = {
    "program": {"hidden_size": 64, "mamba_num_heads": 4,
                "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
                "chunk_size": 16, "intermediate_size": 64,
                "moe_intermediate_size": 32,
                "moe_shared_expert_intermediate_size": 48,
                "n_routed_experts": 2, "ep_size": 4,
                "num_experts_per_tok": 3,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 16, "vocab_size": 256, "sequences": 2,
                "sequence_length": 64, "open_sequences": 8,
                "attention_block": 16, "expert_chunk_rows": 64,
                "mlp_chunks": 2, "head_chunks": 2, "attention_chunks": 2,
                "ssm_chunks": 2, "learning_rate": 0.001},
    # the rehearsal's small sums are noisier than the cell's
    "limits": {"ce_gap": 2e-3, "grad_norm_gap_ssm": 0.05,
               "grad_norm_gap_attention": 0.05,
               "grad_norm_gap_experts": 0.1, "grad_norm_gap_router": 0.2,
               "grad_norm_gap_vocabulary": 0.05,
               "grad_norm_gap_norms": 0.05, "embed_grad_gap": 0.05,
               "expert_grad_gap": 0.1, "ssm_decay_grad_gap": 0.2,
               "routing_mismatch": 0.05,
               "table_change_gap": 0.5},
    "traffic": {"doc_length": {"law": "lognormal", "median": 16,
                               "sigma": 1.0, "min": 3, "max": 64},
                "stream_steps": 12},
}


def table_kind(name: str) -> str:
    """What ``grad_norm_gap_<kind>`` holds a table's gradient norm to."""
    if name in ("embed", "head"):
        return "vocabulary"
    if name == "norms" or name.endswith(".ssm_norm"):
        return "norms"
    if ".ssm_" in name:
        return "ssm"
    if name.endswith(".attn"):
        return "attention"
    if name.endswith(".router"):
        # its gradient follows every choice bfloat16 flips
        return "router"
    return "experts"        # routed experts, shared, a dense mlp


class Cell(lfm2.Cell):
    def __init__(self, *, config, traffic, tiny, **kw):
        if tiny:        # this model's small sizes, not the others'
            config = dict(config,
                          program=dict(config["program"], **TINY["program"]),
                          correct=dict(config["correct"], limits=dict(
                              config["correct"]["limits"],
                              **TINY["limits"])))
            traffic = dict(traffic, **TINY["traffic"])
        super().__init__(config=config, traffic=traffic, tiny=False, **kw)

    def setup(self) -> None:
        from multiverso_tpu.apps.transformer_lm import LMConfig
        if "hybrid_override_pattern" not in {
                f.name for f in dataclasses.fields(LMConfig)}:
            raise SystemExit(
                "this program's LMConfig reads no hybrid_override_pattern: "
                "it cannot build a model of one part a layer")
        super().setup()

    def _start(self, index: int, name: str):
        import jax.numpy as jnp
        shape = self.shapes[name]
        if name == "norms" or name.endswith(".ssm_norm") \
                or ref.is_bias(name):
            return jnp.full(shape, float(not ref.is_bias(name)),
                            jnp.float32)
        if name.endswith(".ssm_decay"):
            return ref.init_decay(self.config.seed, index, shape[1],
                                  self.reference_config())
        if name.endswith(".ssm_conv"):
            return ref.init_conv(self.config.seed, index, shape,
                                 self.config.conv_kernel)
        from multiverso_tpu.apps.transformer_lm import start_std
        return ref.init_normal(self.config.seed, index, shape,
                               start_std(self.config, name))

    # -- what correct compares ---------------------------------------------

    def reference_config(self) -> dict:
        """The reference's own keys: the published names, and
        ``n_routed_experts`` counting ALL the router's outputs."""
        c = self.config
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

    def check(self) -> list:
        from multiverso_tpu.apps.transformer_lm import table_layout
        c, control = self.config, self.control
        variant = control if control in ref.VARIANTS else None
        cfg = self.reference_config()
        t0 = time.perf_counter()
        layout = table_layout(c)
        names = list(self.shapes)
        p = {}
        for index, name in enumerate(names):
            p.update(self._by_role({name: self._start(index, name)}))
        start = {k: np.asarray(x) for k, x in p.items()}
        biases = [k for k in p if ref.is_bias(k)]
        settled = {k: np.ones(p[k].shape, bool) for k in biases}
        m = v = None
        checks, hyper = [], dict(b1=c.beta1, b2=c.beta2, eps=c.adam_eps)

        def rate(step):     # the schedule, written out: linear warm-up
            return c.learning_rate * min(1.0, (step + 1) / c.warmup_steps)

        replay = self.replay_steps or self.checked
        for s in range(replay):
            ce, grads, aux = ref.loss_and_grads(p, self.batches[s], cfg,
                                                variant)
            got_ce = self.losses[s][0]
            checks.append((f"ce_gap_s{s + 1}", abs(got_ce - ce) / abs(ce)))
            self.log(f"step {s + 1}: cross-entropy program {got_ce:.7f} "
                     f"reference {ce:.7f}")
            differing = self._differing(s, aux)
            if differing is not None:
                checks += self._gradients(s, grads, layout)
                real = np.count_nonzero(self.batches[s]["doc"])
                checks.append((f"routing_mismatch_s{s + 1}",
                               float(differing.sum())
                               / (real * len(differing))))
            for k, counts, n in zip(biases, aux["counts"],
                                    differing if differing is not None
                                    else [np.inf] * len(biases)):
                settled[k] &= np.abs(counts - counts.mean()) > n
            # the state as it was / nothing follows: no optimizer step
            if control != "unchanged" and not s + 1 == replay < self.checked:
                if m is None:       # the moments wait on the host
                    m, v = ref.host_zeros_like(p), ref.host_zeros_like(p)
                p, m, v = ref.adam_step(p, m, v, grads, s, lr=rate(s),
                                        **hyper)
                if control != "bias_frozen":
                    p = ref.bias_step(p, grads, c.expert_bias_rate)
                if control == "bfloat16":       # tables held in bfloat16
                    p = ref.round_bfloat16(p)
            del grads       # the next step's need the room
        if replay == self.checked or control == "unchanged":
            worst = 0.0
            for name in (n for n in names if not ref.is_bias(n)):
                got = self._by_role({name: self.changes[name]})
                want = np.concatenate([
                    (np.asarray(p[r]) - start[r]).ravel()
                    for r in layout[name]])
                got = np.concatenate([got[r].ravel()
                                      for r in layout[name]])
                worst = max(worst, dsv2._change_gap(got, want))
            checks.append((f"table_change_gap_s{self.checked}", worst))
        if replay == self.checked:
            wrong = 0
            for k in biases:
                differs = np.abs(self.changes[k] - np.asarray(p[k])) \
                    > 0.5 * c.expert_bias_rate
                wrong += int(np.sum(differs & settled[k]))
                self.log(f"bias {k}: {int(differs.sum())} entries differ, "
                         f"{int(settled[k].sum())} of {len(differs)} "
                         "held to the reference's")
            checks.append(("bias_mismatch", wrong))
        dropped = sum(int(s["moe"][1]) for s in self.steps) \
            + int(self.dropped) + int(self.untrained)
        checks.append(("tokens_dropped", dropped))
        checks.append(("routed_counts_mismatch", self._recount()))
        self.log(f"reference took {time.perf_counter() - t0:.1f} s")
        return [{"name": n, "value": float(x),
                 "limit": self.limits[re.sub(r"_s\d+$", "", n)]}
                for n, x in checks]

    def _gradients(self, s: int, grads: dict, layout: dict) -> list:
        """Checked step ``s``'s gradients against the reference's: the
        norm of every table's gradient, the worst of each kind apart
        (the biases apart: theirs is no gradient, and ``bias_mismatch``
        holds them), and three gradients entry by entry."""
        from multiverso_tpu.apps.transformer_lm import EXPERTS, SSM
        step, c = self.steps[s - self.ungraded], self.config
        worst = {}
        for name, got in zip(self.shapes, step["grad_norms"]):
            if ref.is_bias(name):
                continue
            want = float(np.sqrt(sum(
                float(np.sum(np.square(np.asarray(grads[r], np.float64))))
                for r in layout[name])))
            kind = table_kind(name)
            worst[kind] = max(worst.get(kind, 0.0),
                              abs(float(got) - want) / max(want, 1e-30))
            self.log(f"gradient norm {name}: program {float(got):.6g} "
                     f"reference {want:.6g}")
        rows = step["probe_embed"].shape[0]
        first = lambda kind: next(i for i in range(c.num_hidden_layers)
                                  if c.mixer(i) == kind)
        expert = np.stack([np.asarray(grads[f"l{first(EXPERTS)}.exp_{part}"]
                                      [0]) for part in ("up", "down")])
        decay = np.stack([np.asarray(grads[f"l{first(SSM)}.{part}"])
                          for part in ("a_log", "dt_bias", "d_skip")])
        return [(f"grad_norm_gap_{kind}_s{s + 1}", gap)
                for kind, gap in sorted(worst.items())] + [
            (f"embed_grad_gap_s{s + 1}",
             dsv2._gap(step["probe_embed"], grads["embed"][:rows])),
            (f"expert_grad_gap_s{s + 1}",
             dsv2._gap(step["probe_expert"], expert)),
            (f"ssm_decay_grad_gap_s{s + 1}",
             dsv2._gap(step["probe_ssm_decay"], decay))]
