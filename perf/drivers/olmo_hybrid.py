"""Driver of the Olmo-Hybrid training cell: builds
``apps.transformer_lm.TransformerLM`` from the configuration's
``program`` block — gated delta-rule linear attention beside full
attention, 3 : 1 — feeds it packed documents drawn from the seed, and
trains through ``TransformerLM.train(total_steps=...)`` as a user does.
The tables start from the configuration's own draw
(``program.init_seed``), the same for every ``--seed``.

It is the DeepSeek-V2-Lite driver (``perf/drivers/dsv2.py``: set-up, the
checked first steps, the window's one ``train()`` call, the documents)
with this model's reference and comparison: ``correct`` replays the
same packed steps from the same start through the plain reference
(``perf/reference/olmo_hybrid.py``: float32, the recurrence token by
token) after the window, a sequence and a layer at a time, and compares
what the timed object produced: the loss of each checked step, its
gradients (norms of every table's, two of them entry by entry: the
first 1,024 embedding rows and layer 0's key projection of the
recurrence), the change of every table after the last, and — exactly —
that every real token of the window was trained.

The checked steps are the stream's first ``correct.checked_steps`` and
as many more as it takes until one of them holds a document boundary:
first fit closes the fullest sequence first, so the first steps of a
seed are often one 4,096-token document each, in which no state, tap or
attention can cross a boundary. A limit is its kind's
(``embed_grad_gap``), whatever the step (``embed_grad_gap_s5``).

``Cell.control`` names a deliberately wrong reference (``VARIANTS`` of
the reference or ``"unchanged"``): ``perf/tests/calibrate_olmo_hybrid.py``
runs ``check()`` once a control to read what each fails.

A program whose ``LMConfig`` knows no ``layer_types`` (the parent of the
PR that brought this cell) cannot build the configuration: ``setup``
says so and the run ends at once, with no result.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import re
import time

import numpy as np

from perf import program
from perf.reference import olmo_hybrid as ref

dsv2 = program.load_module(
    "perf_driver_dsv2",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "dsv2.py"))

TINY = {
    "program": {"hidden_size": 48, "intermediate_size": 96,
                "num_attention_heads": 3, "num_key_value_heads": 3,
                "linear_num_key_heads": 3, "linear_num_value_heads": 3,
                "linear_key_head_dim": 12, "linear_value_head_dim": 24,
                "vocab_size": 256, "sequences": 2, "sequence_length": 64,
                "attention_block": 16, "gdn_chunk": 16, "mlp_chunks": 2,
                "head_chunks": 2, "learning_rate": 0.001},
    # the rehearsal's small sums are noisier than the cell's
    "limits": {"ce_gap": 2e-3, "grad_norm_gap": 0.05, "embed_grad_gap": 0.05,
               "gdn_k_grad_gap": 0.05, "table_change_gap": 0.5},
    "traffic": {"doc_length": {"law": "lognormal", "median": 16,
                               "sigma": 1.0, "min": 3, "max": 64},
                "stream_steps": 12},
}


class Cell(dsv2.Cell):
    def __init__(self, *, config, traffic, tiny, **kw):
        if tiny:        # this model's small sizes, not the other's
            config = dict(config,
                          program=dict(config["program"], **TINY["program"]),
                          correct=dict(config["correct"], limits=dict(
                              config["correct"]["limits"],
                              **TINY["limits"])))
            traffic = dict(traffic, **TINY["traffic"])
        super().__init__(config=config, traffic=traffic, tiny=False, **kw)
        self.untrained = 0          # real tokens the window did not train

    @property
    def checked(self) -> int:
        """The steps set-up drives the program through and ``check()``
        replays: the configuration's ``checked_steps``, and as many more
        as it takes until one of them holds a document boundary (known
        once the seed's documents are)."""
        if self._checked is None:
            boundary = next(s for s, b in enumerate(self._pack(None))
                            if b["doc"].max() > 1)
            self._checked = max(self._least_checked, boundary + 1)
        return self._checked

    @checked.setter
    def checked(self, least: int) -> None:
        self._least_checked, self._checked = least, None

    def setup(self) -> None:
        from multiverso_tpu.apps.transformer_lm import LMConfig
        if "layer_types" not in {f.name
                                 for f in dataclasses.fields(LMConfig)}:
            raise SystemExit(
                "this program's LMConfig reads no layer_types: it cannot "
                "build a model whose mixers differ by layer")
        super().setup()
        # the program keeps the last steps' gradients only
        self.ungraded = self.checked - len(self.steps)
        self.log(f"documents in the {self.checked} checked steps: "
                 f"{[int(b['doc'].max()) for b in self.batches]}; "
                 f"gradients compared from step {self.ungraded + 1}")

    def _pack(self, n):
        """The first ``n`` steps of the stream (``None``: the stream),
        packed as the program packs them: over the configuration's pool
        of open sequences."""
        from multiverso_tpu.data.packing import pack_documents
        c = self.config
        steps = pack_documents(self.docs, c.sequences, c.sequence_length,
                               open_sequences=c.open_sequences)
        return steps if n is None else list(itertools.islice(steps, n))

    def _start(self, index: int, name: str):
        import jax.numpy as jnp
        shape = self.shapes[name]
        if name == "norms":
            return jnp.ones(shape, jnp.float32)
        if name.endswith(".gdn_decay"):
            return ref.init_decay(self.config.seed, index, shape[1])
        from multiverso_tpu.apps.transformer_lm import start_std
        return ref.init_normal(self.config.seed, index, shape,
                               start_std(self.config, name))

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> dict:
        result = super().window(seconds)
        # real tokens of the window's steps that the program's own
        # count (lm.tokens) does not hold
        self.untrained = sum(
            int(np.count_nonzero(b["doc"]))
            for b in self._pack(result["work"]["steps"])) \
            - result["work"]["tokens"]
        return result

    # -- what correct compares ---------------------------------------------

    def reference_config(self) -> dict:
        return dataclasses.asdict(self.config)

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
        m = v = None
        checks, hyper = [], dict(b1=c.beta1, b2=c.beta2, eps=c.adam_eps)

        def rate(step):     # the schedule, written out: linear warm-up
            return c.learning_rate * min(1.0, (step + 1) / c.warmup_steps)

        replay = self.replay_steps or self.checked
        for s in range(replay):
            ce, grads = ref.loss_and_grads(p, self.batches[s], cfg, variant)
            got_ce = self.losses[s][0]
            checks.append((f"ce_gap_s{s + 1}", abs(got_ce - ce) / abs(ce)))
            self.log(f"step {s + 1}: cross-entropy program {got_ce:.7f} "
                     f"reference {ce:.7f}")
            if s >= self.ungraded:
                checks += self._gradients(s, grads, layout)
            # the state as it was / nothing follows: no optimizer step
            if control != "unchanged" and not s + 1 == replay < self.checked:
                if m is None:       # the moments wait on the host
                    m, v = ref.host_zeros_like(p), ref.host_zeros_like(p)
                p, m, v = ref.adam_step(p, m, v, grads, s, lr=rate(s),
                                        **hyper)
                if control == "bfloat16":       # tables held in bfloat16
                    p = ref.round_bfloat16(p)
            del grads       # the next step's need the room
        if replay == self.checked or control == "unchanged":
            worst = 0.0
            for name in names:
                got = self._by_role({name: self.changes[name]})
                want = np.concatenate([
                    (np.asarray(p[r]) - start[r]).ravel()
                    for r in layout[name]])
                got = np.concatenate([got[r].ravel()
                                      for r in layout[name]])
                worst = max(worst, dsv2._change_gap(got, want))
            checks.append((f"table_change_gap_s{self.checked}", worst))
        checks.append(("tokens_dropped", int(self.untrained)))
        self.log(f"reference took {time.perf_counter() - t0:.1f} s")
        return [{"name": n, "value": float(x),
                 "limit": self.limits[re.sub(r"_s\d+$", "", n)]}
                for n, x in checks]

    def _gradients(self, s: int, grads: dict, layout: dict) -> list:
        """Checked step ``s``'s gradients against the reference's: the
        norm of every table's gradient, and two gradients entry by
        entry."""
        step = self.steps[s - self.ungraded]
        worst = 0.0
        for name, got in zip(self.shapes, step["grad_norms"]):
            want = float(np.sqrt(sum(
                float(np.sum(np.square(np.asarray(grads[r], np.float64))))
                for r in layout[name])))
            worst = max(worst, abs(float(got) - want) / max(want, 1e-30))
            self.log(f"gradient norm {name}: program {float(got):.6g} "
                     f"reference {want:.6g}")
        rows = step["probe_embed"].shape[0]
        return [(f"grad_norm_gap_s{s + 1}", worst),
                (f"embed_grad_gap_s{s + 1}",
                 dsv2._gap(step["probe_embed"], grads["embed"][:rows])),
                (f"gdn_k_grad_gap_s{s + 1}",
                 dsv2._gap(step["probe_gdn_k"], grads["l0.w_k"]))]
