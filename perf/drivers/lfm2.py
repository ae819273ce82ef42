"""Driver of the LFM2-MoE training cell: builds
``apps.transformer_lm.TransformerLM`` from the configuration's
``program`` block — gated short convolutions beside grouped-query rotary
attention, a sigmoid router whose choice a bias table steers, ONE tied
vocabulary table, as one expert-parallel rank of four — feeds it packed
documents drawn from the seed, and trains through
``TransformerLM.train(total_steps=...)`` as a user does. The tables
start from the configuration's own draw (``program.init_seed``), the
same for every ``--seed``.

It is the Olmo-Hybrid driver (``perf/drivers/olmo_hybrid.py``: the
DeepSeek-V2-Lite driver's set-up, window and documents, the packer's
pool, the checked first steps running on until one holds a document
boundary) with this model's reference and comparison: ``correct``
replays the same packed steps from the same start through the plain
reference (``perf/reference/lfm2.py``) after the window, a sequence and
a layer at a time, and compares what the timed object produced: the
loss of every checked step; of the steps whose whole aux the program
kept (its last four) the norm of every table's gradient, two gradients
entry by entry (the first 1,024 rows of the tied vocabulary table; the
input projection of the first short convolution behind experts) and the
experts each real token chose (``routing_mismatch``: the share of
(token, expert layer) pairs whose set differs); every table's change
after the last checked step; the BIAS tables after the checked steps
(``bias_mismatch``, limit 0: entries that differ from the reference's
although, in every checked step, the reference's ``|c_e - mean c|`` was
larger than the number of that layer's tokens on whose choice program
and reference disagreed — the bias steps by a sign rule, so any other
difference is a fault and no rounding); and — exactly — that nothing was
dropped, that the routed counts are a recount of the routing, and that
every real token of the window was trained.

``Cell.control`` names a deliberately wrong reference (``VARIANTS`` of
the reference, ``"bias_frozen"`` — the reference's bias never stepped —
or ``"unchanged"``): ``perf/tests/calibrate_lfm2.py`` runs ``check()``
once a control to read what each fails.

A program whose ``LMConfig`` reads no ``conv_L_cache`` (the parent of
the PR that brought this cell) cannot build the configuration:
``setup`` says so and the run ends at once, with no result.
"""

from __future__ import annotations

import dataclasses
import os
import re
import time

import numpy as np

from perf import program
from perf.reference import lfm2 as ref

_here = os.path.dirname(os.path.abspath(__file__))
dsv2 = program.load_module("perf_driver_dsv2",
                           os.path.join(_here, "dsv2.py"))
olmo_hybrid = program.load_module("perf_driver_olmo_hybrid",
                                  os.path.join(_here, "olmo_hybrid.py"))

TINY = {
    "program": {"hidden_size": 64, "intermediate_size": 128,
                "moe_intermediate_size": 32, "num_experts": 2,
                "num_experts_per_tok": 3, "num_attention_heads": 4,
                "num_key_value_heads": 2, "vocab_size": 256,
                "sequences": 4, "sequence_length": 64,
                "attention_block": 16, "expert_chunk_rows": 32,
                "mlp_chunks": 2, "head_chunks": 2, "learning_rate": 0.001},
    # the rehearsal's small sums are noisier than the cell's
    "limits": {"ce_gap": 2e-3, "grad_norm_gap": 0.05,
               "embed_grad_gap": 0.05, "conv_in_grad_gap": 0.05,
               "routing_mismatch": 0.05, "table_change_gap": 0.5},
    "traffic": {"doc_length": {"law": "lognormal", "median": 16,
                               "sigma": 1.0, "min": 3, "max": 64},
                "stream_steps": 12},
}


class Cell(olmo_hybrid.Cell):
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
        if "conv_L_cache" not in {f.name
                                  for f in dataclasses.fields(LMConfig)}:
            raise SystemExit(
                "this program's LMConfig reads no conv_L_cache: it cannot "
                "build a model whose layer_types name a short convolution")
        super().setup()

    def _start(self, index: int, name: str):
        import jax.numpy as jnp
        shape = self.shapes[name]
        if name == "norms" or ref.is_bias(name):
            return jnp.full(shape, float(name == "norms"), jnp.float32)
        from multiverso_tpu.apps.transformer_lm import start_std
        return ref.init_normal(self.config.seed, index, shape,
                               start_std(self.config, name))

    # -- what correct compares ---------------------------------------------

    def reference_config(self) -> dict:
        """The reference's own keys: the published names, and
        ``num_experts`` counting ALL the router's outputs."""
        c = self.config
        return {"hidden_size": c.hidden_size,
                "num_hidden_layers": c.num_hidden_layers,
                "layer_types": list(c.layer_types[:c.num_hidden_layers]),
                "num_dense_layers": c.first_k_dense_replace,
                "intermediate_size": c.intermediate_size,
                "moe_intermediate_size": c.moe_intermediate_size,
                "num_experts": c.router_width, "ep_size": c.ep_size,
                "ep_rank": c.ep_rank,
                "num_experts_per_tok": c.num_experts_per_tok,
                "norm_topk_prob": c.norm_topk_prob,
                "routed_scaling_factor": c.routed_scaling_factor,
                "num_attention_heads": c.num_attention_heads,
                "num_key_value_heads": c.kv_heads,
                "norm_eps": c.rms_norm_eps, "conv_L_cache": c.conv_L_cache,
                "rope_theta": c.rope_theta, "vocab_size": c.vocab_size}

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
        # a bias entry is held to the reference's while, in every step,
        # its expert's load stood further from the mean than the two
        # routings differed
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

    def _differing(self, s: int, aux: dict):
        """Real tokens of checked step ``s`` whose set of experts the
        program chose differs from the reference's, a count an expert
        layer; ``None`` for a step whose routing the program did not
        keep."""
        if s < self.ungraded:
            return None
        got = self.steps[s - self.ungraded]["chosen"]
        real = (self.batches[s]["doc"] > 0).reshape(-1)
        return np.sum(np.any(np.sort(got, -1) != np.sort(aux["chosen"], -1),
                             axis=-1)[:, real], axis=1)

    def _gradients(self, s: int, grads: dict, layout: dict) -> list:
        """Checked step ``s``'s gradients against the reference's: the
        norm of every table's gradient (the biases apart: theirs is no
        gradient, and ``bias_mismatch`` holds them), and two gradients
        entry by entry."""
        step, c = self.steps[s - self.ungraded], self.config
        worst = 0.0
        for name, got in zip(self.shapes, step["grad_norms"]):
            if ref.is_bias(name):
                continue
            want = float(np.sqrt(sum(
                float(np.sum(np.square(np.asarray(grads[r], np.float64))))
                for r in layout[name])))
            worst = max(worst, abs(float(got) - want) / max(want, 1e-30))
            self.log(f"gradient norm {name}: program {float(got):.6g} "
                     f"reference {want:.6g}")
        rows = step["probe_embed"].shape[0]
        # the first short convolution behind experts (else the first)
        conv = min((i for i in range(c.num_hidden_layers)
                    if c.layer_types[i] == "conv"),
                   key=lambda i: (c.is_dense(i), i))
        return [(f"grad_norm_gap_s{s + 1}", worst),
                (f"embed_grad_gap_s{s + 1}",
                 dsv2._gap(step["probe_embed"], grads["embed"][:rows])),
                (f"conv_in_grad_gap_s{s + 1}",
                 dsv2._gap(step["probe_conv_in"],
                           grads[f"l{conv}.conv_in"]))]

    def _recount(self) -> int:
        """Entries in which the routed counts a kept step returned
        differ from a count of the routing it returned, padding left
        out."""
        wrong = 0
        for step, batch in zip(self.steps, self.batches[self.ungraded:]):
            real = (batch["doc"] > 0).reshape(-1)
            for chosen, counts in zip(step["chosen"], step["counts"]):
                recount = np.bincount(chosen[real].reshape(-1),
                                      minlength=len(counts))
                wrong += int(np.sum(recount != counts))
        return wrong
