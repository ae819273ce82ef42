"""Driver of the DeepSeek-V2-Lite training cell: builds
``apps.transformer_lm.TransformerLM`` from the configuration's
``program`` block, feeds it packed documents drawn from the seed, and
trains through ``TransformerLM.train(total_steps=...)`` as a user does —
the input thread packs, one fused superstep a step, Adam in the tables.
The tables start from the configuration's own draw (``program.init_seed``),
the same for every ``--seed``: how many rows this chip's experts receive
follows the router's start, and a run's work may not hang on its seed.

Set-up builds ONE trainer and drives it through its checked first steps
with the window's own entry (they compile the one program there is);
``correct`` replays the same packed steps from the same start through the
plain reference (``perf/reference/dsv2.py``) after the window, one
sequence at a time so that it fits, and compares what the timed object
produced: the losses of each checked step, the gradients of the first
(norms of every table's, two of them entry by entry), the change of
every table after the last, the experts each token chose, and — exactly
— that nothing was dropped and that the routed counts are a recount of
the routing.

``Cell.control`` names a deliberately wrong reference (``VARIANTS`` of
the reference, ``"bfloat16_compute"`` — its bfloat16 variant with the
tables and Adam kept float32 — or ``"unchanged"``):
``perf/tests/calibrate_dsv2.py`` runs ``check()`` once a control to read
what each fails.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from perf import corpus, program
from perf.reference import dsv2 as ref

TINY = {
    "program": {"hidden_size": 64, "num_hidden_layers": 3,
                "intermediate_size": 128, "moe_intermediate_size": 32,
                "n_routed_experts": 2, "ep_size": 4,
                "num_experts_per_tok": 3, "num_attention_heads": 4,
                "kv_lora_rank": 16, "qk_nope_head_dim": 16,
                "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 256,
                "sequences": 4, "sequence_length": 64,
                "attention_block": 16, "expert_chunk_rows": 32,
                "mlp_chunks": 2, "head_chunks": 2, "init_std": 0.05,
                "learning_rate": 0.001},
    # the rehearsal's small sums are noisier than the cell's
    "limits": {"ce_gap_s1": 1e-3, "ce_gap_s2": 2e-3, "ce_gap_s3": 2e-3,
               "balance_gap_s1": 5e-3, "balance_gap_s2": 2e-2,
               "balance_gap_s3": 5e-2, "grad_norm_gap_s1": 0.05,
               "embed_grad_gap_s1": 0.05, "expert_grad_gap_s1": 0.1,
               "routing_mismatch_s1": 0.05, "table_change_gap_s3": 0.5},
    "traffic": {"doc_length": {"law": "lognormal", "median": 16,
                               "sigma": 1.0, "min": 3, "max": 64},
                "stream_steps": 12},
}


class Epochs:
    """The documents again and again: a window may outlast them."""

    def __init__(self, docs: list) -> None:
        self.docs = docs

    def __iter__(self):
        return itertools.chain.from_iterable(itertools.repeat(self.docs))

    def __len__(self) -> int:
        return len(self.docs)


def documents(seed: int, traffic: dict, vocab: int, slots: int):
    """Token arrays, one a document, from the seed: lengths lognormal
    (clipped), ids zipf over the vocabulary held here; enough of them
    for ``stream_steps`` steps of ``slots`` token slots."""
    law = traffic["doc_length"]
    n = int(traffic["stream_steps"]) * slots
    rng = np.random.default_rng([int(seed), 2])
    lens = np.exp(rng.normal(np.log(law["median"]), law["sigma"],
                             max(8, 2 * n // law["median"])))
    lens = np.clip(np.rint(lens), law["min"], law["max"]).astype(np.int64)
    lens = lens[:int(np.searchsorted(np.cumsum(lens), n)) + 1]
    ids = np.asarray(corpus.zipf_words(seed, int(lens.sum()), vocab,
                                       traffic["token_zipf_exponent"]))
    return Epochs(np.split(ids, np.cumsum(lens)[:-1]))


def _gap(got, want) -> float:
    """Norm of the difference over the norm of the reference's."""
    import jax.numpy as jnp
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm((jnp.asarray(got, jnp.float32) - want)
                                 .ravel())
                 / jnp.maximum(jnp.linalg.norm(want.ravel()), 1e-30))


def _change_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Norm of the difference of two changes of a table over the larger
    of their norms: a state left unchanged on either side reads 1."""
    diff = float(np.linalg.norm(got.astype(np.float64) - want))
    return diff / max(float(np.linalg.norm(want.astype(np.float64))),
                      float(np.linalg.norm(got.astype(np.float64))), 1e-30)


class Cell:
    def __init__(self, *, config, traffic, seed, seconds, chips, devices,
                 tiny, log):
        self.cfg = dict(config)
        self.sizes = dict(config["program"])
        self.limits = dict(config["correct"]["limits"])
        self.checked = int(config["correct"]["checked_steps"])
        self.traffic = dict(traffic)
        if tiny:
            self.sizes.update(TINY["program"])
            self.traffic.update(TINY["traffic"])
            self.limits.update(TINY["limits"])
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.chips = chips
        self.devices = devices
        self.log = log
        self.app = None
        self.control = None         # a deliberately wrong reference
        self.replay_steps = None    # fewer than the checked steps
        self.dropped = 0            # the window's moe.tokens_dropped

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        import jax
        from multiverso_tpu.apps.transformer_lm import (LMConfig,
                                                        TransformerLM,
                                                        table_shapes)

        self._jax = jax
        mesh = program.init_mesh(self.cfg, self.traffic, self.chips,
                                 self.devices)
        t0 = time.perf_counter()
        # the documents are the seed's; the tables start from the
        # configuration's own draw (``init_seed``), so that every seed
        # routes through the same router and does the same work
        self.config = c = LMConfig.from_dict(dict(
            self.sizes, seed=self.sizes["init_seed"]))
        slots = c.sequences * c.sequence_length
        self.docs = documents(self.seed, self.traffic, c.vocab_size, slots)
        self.shapes = table_shapes(c)
        self.app = app = TransformerLM(c, self.docs, mesh=mesh)
        self.log(f"{app.parameters / 1e6:.1f}M parameters in "
                 f"{len(app.tables)} tables, {len(self.docs)} documents "
                 f"built in {time.perf_counter() - t0:.1f} s")
        self.batches = self._pack(self.checked)
        t0 = time.perf_counter()
        self._train(self.checked)
        self.log(f"checked steps (the compile among them) took "
                 f"{time.perf_counter() - t0:.1f} s")
        self.losses = list(app.loss_history)
        self.steps = jax.device_get(list(app.aux_tail)[-self.checked:])
        # every table's change over the checked steps, kept on the host
        self.changes = {}
        for index, (name, table) in enumerate(app.tables.items()):
            rows = self.shapes[name][0]
            self.changes[name] = np.asarray(
                table.raw()[:rows] - self._start(index, name))
        # a warm call times a step, for the window's count
        t0 = time.perf_counter()
        self._train(2)
        self.step_s = (time.perf_counter() - t0) / 2
        self.log(f"a step takes {self.step_s:.3f} s")

    def _pack(self, n: int) -> list:
        """The first ``n`` steps of the stream, packed as the program
        packs them (its stream restarts with every ``train()`` call)."""
        from multiverso_tpu.data.packing import pack_documents
        c = self.config
        return list(itertools.islice(
            pack_documents(self.docs, c.sequences, c.sequence_length), n))

    def _start(self, index: int, name: str):
        import jax.numpy as jnp
        shape = self.shapes[name]
        if name == "norms":
            return jnp.ones(shape, jnp.float32)
        from multiverso_tpu.apps.transformer_lm import start_std
        return ref.init_normal(self.config.seed, index, shape,
                               start_std(self.config, name))

    def _train(self, steps: int) -> None:
        with self._jax.profiler.TraceAnnotation("bench.dsv2.train"):
            self.app.train(total_steps=steps)

    # -- the window ----------------------------------------------------------

    registry_snapshot = staticmethod(program.registry_snapshot)

    def window(self, seconds: float) -> dict:
        steps = max(1, int(seconds / self.step_s))
        before = self.registry_snapshot()["counters"]
        done0 = len(self.app.loss_history)
        t0 = time.perf_counter()
        self._train(steps)                # ends on the tables' fence
        elapsed = time.perf_counter() - t0
        after = self.registry_snapshot()
        grown = {k: after["counters"].get(k, 0) - before.get(k, 0)
                 for k in ("lm.tokens", "lm.pad_tokens",
                           "moe.tokens_routed", "moe.tokens_dropped")}
        done = len(self.app.loss_history) - done0
        self.dropped = grown["moe.tokens_dropped"]
        # the keys each real token attended: its position in its
        # document + 1, over the steps the window trained on
        attended = sum(int(np.sum((b["pos"] + 1)[b["doc"] > 0]))
                       for b in self._pack(done))
        return {"attempted": steps, "failed": steps - done,
                "metrics": {"train_tokens_per_s":
                            grown["lm.tokens"] / elapsed},
                "work": {"steps": done, "tokens": grown["lm.tokens"],
                         "pad_tokens": grown["lm.pad_tokens"],
                         "assignments": grown["moe.tokens_routed"],
                         "attended_keys": attended},
                "values": {"window_s": elapsed,
                           "expert_load_max_over_mean": after["gauges"].get(
                               "moe.expert_load_max_over_mean")}}

    # -- what correct compares ---------------------------------------------

    def collect(self) -> None:
        self.app = None
        program.free()

    def reference_config(self) -> dict:
        """The reference counts all the router's outputs as experts."""
        c = self.config
        import dataclasses
        return dict(dataclasses.asdict(c), n_routed_experts=c.router_width)

    def _by_role(self, tables: dict) -> dict:
        from multiverso_tpu.apps.transformer_lm import named_parameters
        return named_parameters(self.config, tables)

    def check(self) -> list:
        c, control = self.config, self.control
        # "bfloat16_compute": the bfloat16 reference, its tables float32
        variant = "bfloat16" if control == "bfloat16_compute" \
            else control if control in ref.VARIANTS else None
        cfg = self.reference_config()
        t0 = time.perf_counter()
        from multiverso_tpu.apps.transformer_lm import table_layout
        layout = table_layout(c)
        names = list(self.shapes)
        p = {}
        for index, name in enumerate(names):
            p.update(self._by_role({name: self._start(index, name)}))
        start = {k: np.asarray(x) for k, x in p.items()}
        m, v = None, None
        checks, hyper = [], dict(b1=c.beta1, b2=c.beta2, eps=c.adam_eps)

        def rate(step):     # the schedule, written out: linear warm-up
            return c.learning_rate * min(1.0, (step + 1) / c.warmup_steps)

        replay = self.replay_steps or self.checked
        for s in range(replay):
            ce, balance, grads, aux = ref.loss_and_grads(
                p, self.batches[s], cfg, variant)
            got_ce, got_balance = self.losses[s]
            checks += [(f"ce_gap_s{s + 1}", abs(got_ce - ce) / abs(ce)),
                       (f"balance_gap_s{s + 1}",
                        abs(got_balance - balance) / abs(balance))]
            self.log(f"step {s + 1}: cross-entropy program {got_ce:.7f} "
                     f"reference {ce:.7f}; balance {got_balance:.7f} "
                     f"{balance:.7f}")
            if s == 0:
                checks += self._first_step(grads, aux)
            if control == "unchanged" or s + 1 == replay < self.checked:
                continue        # the state as it was / nothing follows
            if m is None:
                m, v = ref.zeros_like(p), ref.zeros_like(p)
            p, m, v = ref.adam_step(p, m, v, grads, s, lr=rate(s), **hyper)
            if control == "bfloat16":       # tables held in bfloat16
                p = ref.round_bfloat16(p)
            # the moments wait on the host: the next step's gradients
            # need the room
            m = {k: np.asarray(x) for k, x in m.items()}
            v = {k: np.asarray(x) for k, x in v.items()}
        if replay == self.checked or control == "unchanged":
            worst = 0.0
            for name in names:
                got = self._by_role({name: self.changes[name]})
                want = np.concatenate([
                    (np.asarray(p[r]) - start[r]).ravel()
                    for r in layout[name]])
                got = np.concatenate([got[r].ravel()
                                      for r in layout[name]])
                worst = max(worst, _change_gap(got, want))
            checks.append((f"table_change_gap_s{self.checked}", worst))
        dropped = sum(int(s["moe"][1]) for s in self.steps) \
            + int(self.dropped)
        checks.append(("tokens_dropped", dropped))
        checks.append(("routed_counts_mismatch", self._recount()))
        self.log(f"reference took {time.perf_counter() - t0:.1f} s")
        return [{"name": n, "value": float(x), "limit": self.limits.get(n)}
                for n, x in checks]

    def _first_step(self, grads: dict, aux: dict) -> list:
        """The first step's gradients and routing against the
        reference's: the norm of every table's gradient, two gradients
        entry by entry, and the share of (token, layer) pairs whose set
        of experts differs."""
        from multiverso_tpu.apps.transformer_lm import table_layout
        step, c = self.steps[0], self.config
        layout = table_layout(c)
        worst = 0.0
        for name, got in zip(self.shapes, step["grad_norms"]):
            want = float(np.sqrt(sum(
                float(np.sum(np.square(np.asarray(grads[r], np.float64))))
                for r in layout[name])))
            worst = max(worst, abs(float(got) - want) / max(want, 1e-30))
            self.log(f"gradient norm {name}: program {float(got):.6g} "
                     f"reference {want:.6g}")
        out = [("grad_norm_gap_s1", worst)]
        rows = step["probe_embed"].shape[0]
        out.append(("embed_grad_gap_s1",
                    _gap(step["probe_embed"], grads["embed"][:rows])))
        if "probe_expert" in step:
            layer = next(i for i in range(c.num_hidden_layers)
                         if not c.is_dense(i))
            want = np.stack([np.asarray(grads[f"l{layer}.exp_{part}"][0])
                             for part in ("gate", "up", "down")])
            out.append(("expert_grad_gap_s1",
                        _gap(step["probe_expert"], want)))
            real = (self.batches[0]["doc"] > 0).reshape(-1)
            got, ref_chosen = step["chosen"], aux["chosen"]
            n = ref_chosen.shape[1]     # a token the reference left out
            differs = np.ones(got.shape[:2], bool)          # differs
            differs[:, :n] = np.any(np.sort(got[:, :n], -1)
                                    != np.sort(ref_chosen, -1), axis=-1)
            out.append(("routing_mismatch_s1",
                        float(np.mean(differs[:, real]))))
        return out

    def _recount(self) -> int:
        """Entries in which the routed counts a checked step returned
        differ from a count of the routing it returned, padding left
        out."""
        wrong = 0
        for step, batch in zip(self.steps, self.batches):
            if "chosen" not in step:
                continue
            real = (batch["doc"] > 0).reshape(-1)
            for chosen, counts in zip(step["chosen"], step["counts"]):
                recount = np.bincount(chosen[real].reshape(-1),
                                      minlength=len(counts))
                wrong += int(np.sum(recount != counts))
        return wrong

    def close(self) -> None:
        self.app = None
