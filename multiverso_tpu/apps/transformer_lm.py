"""A decoder language model trained through Table / Updater / superstep.

Every parameter lives in a :class:`~multiverso_tpu.tables.base.Table`
with updater ``adam``; one :class:`FusedSuperstep` a step computes the
loss and its gradients and folds each table's delta into the table
through ``table.updater.apply`` — the same pure function ``Table.add``
uses. The
embedding is a ``MatrixTable`` read by row gather and written by row
scatter-add of the token gradients, word2vec's forms.

The model is built from the keys of a published ``config.json``
(:class:`LMConfig`, which reads them under their published names):
which mixer a layer has (``layer_types`` names it a layer:
``linear_attention``, the gated delta rule of
:mod:`multiverso_tpu.ops.gated_delta`; ``conv``, the gated short
convolution of :mod:`multiverso_tpu.ops.short_conv`; or
``full_attention``, softmax attention with per-head keys; else
``kv_lora_rank`` set: multi-head latent attention,
:mod:`multiverso_tpu.ops.latent_attention`), which feed-forward block (a
dense SwiGLU for the first ``first_k_dense_replace`` /
``num_dense_layers`` layers, then routed experts,
:mod:`multiverso_tpu.ops.moe`, beside shared ones where the config has
them; dense throughout where it has no experts) and what ``model_type``
says of the block (:class:`_Block`): where its norms stand — before
mixer and feed-forward, or, ``olmo_hybrid``, on their outputs —, whether
full attention's QK-norm is over all heads' dims with nothing rotated
or, ``lfm2_moe``, over a head's with a rotary embedding behind it and
grouped key-value heads, and whether the router scores by a softmax or a
sigmoid; as is every width. ``tie_word_embeddings``: no ``head`` table,
``embed`` is gathered from AND multiplied by and takes ONE optimizer
step on the sum of its two gradients. ``use_expert_bias``: an expert
layer's choice is steered by a bias that is a table of its own, stepped
by the routing's counts and no gradient. ``ep_size`` / ``ep_rank`` say
that this chip is one of a group that shares each layer:
``n_routed_experts`` (``num_experts``) is then the number of experts HELD
HERE (the router keeps ``n_routed_experts * ep_size`` outputs),
``vocab_size`` the rows of the vocabulary held here (``vocab_shard``
shards: ids, logits and loss are over the slice). The chip runs without
the group's exchange; nothing stands in for it.

Tables (the count stays in the tens): ``embed`` [V, D] (MatrixTable),
``head`` [V, D] (untied models), ``norms`` [rows, D] (2 to 4 rows a
layer, :func:`norm_offsets`, and the final norm's); a latent-attention
layer: ``l{i}.attn`` [D, H (nope + rope) | rank + rope | H v] (``w_q |
w_kv_a | w_o``, the last stored out x in), ``l{i}.kv_b`` [rank, H (nope
+ v)]; a linear-attention layer: ``l{i}.gdn_in`` [D, q | k | v | gate |
a | b], ``l{i}.gdn_conv`` [taps, q | k | v], ``l{i}.gdn_decay`` [2, H]
(``a_log``, ``dt_bias``), ``l{i}.gdn_out`` [D, H v] (out x in); a
short-convolution layer: ``l{i}.conv_in`` [D, B | C | X],
``l{i}.conv_taps`` [taps, D], ``l{i}.conv_out`` [D, D] (in x out); a
full-attention layer: ``l{i}.attn`` [D, q | k | v | o] (``k`` and ``v``
as wide as the key-value heads; ``w_o`` stored out x in); a dense layer
``l{i}.mlp`` [3, D, F] (gate, up, down stored [D, F]); an expert layer
``l{i}.router`` [D, E], ``l{i}.expert_bias`` [E] (updater ``sgd``: its
delta is no derivative), ``l{i}.shared`` [3, D, shared F],
``l{i}.experts`` [held, 3, D, F] — the leading dimension is the expert,
which is what a table shards over the model axis. :func:`table_layout`
says where every tensor of a published role lies.

Precision: tables, gradients and Adam's moments float32; matrix products
on bfloat16 operands with float32 accumulation; norms, rotary, router
logits and scores, the selection bias, attention softmax, the
recurrence's decay, gates and state between chunks, the short
convolution's gates and taps, and the loss float32. The forward
pass keeps only the residual entering each layer; the backward pass
goes a layer at a time, recomputes the layer, and folds each table's
gradient into the table (Adam) before the layer below starts, so no more
than a layer's gradients are alive at once. Inside a layer the attention
is a Pallas kernel over blocks of queries and keys whose scores stay in
VMEM (forward and backward, :mod:`multiverso_tpu.ops.latent_attention`),
the dense and shared feed-forward and the output head go a group of
sequences at a time, the routed experts a block of sorted rows at a time.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial
from typing import (Any, Callable, Dict, Iterable, List, NamedTuple,
                    Optional, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from multiverso_tpu import core, telemetry
from multiverso_tpu.data.packing import Batch, pack_documents, real_tokens
from multiverso_tpu.ops import gated_delta as gdn
from multiverso_tpu.ops import interpret_mode
from multiverso_tpu.ops import latent_attention as mla
from multiverso_tpu.ops import moe
from multiverso_tpu.ops import short_conv as sconv
from multiverso_tpu.tables import MatrixTable
from multiverso_tpu.tables.base import Table
from multiverso_tpu.tables.superstep import make_superstep
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils import log
from multiverso_tpu.utils.async_buffer import prefetch_iterator

PROBE_ROWS = 1024       # embedding rows whose gradient a step returns
AUX_KEEP = 4            # the last steps whose whole aux stays on the device
PREFETCH_STEPS = 4      # packed steps the input thread runs ahead
_SMALL_AUX = ("ce", "balance", "moe", "imbalance", "attend", "gdn", "conv",
              "bias")
LATENT, LINEAR, FULL, CONV = ("latent", "linear_attention",
                              "full_attention", "conv")


class _Block(NamedTuple):
    """What a ``model_type`` says of its decoder block."""
    # the norms stand on the outputs of mixer and feed-forward (the
    # Olmo 2 / 3 family's reordered norm), not before them
    post_norm: bool
    # full attention's QK-norm is over ONE head's dims (one weight
    # vector a projection) and a rotary embedding follows it; else it is
    # over all heads' dims and nothing is rotated
    head_norm: bool
    score: str                  # the router's: "softmax" or "sigmoid"
    mixers: Tuple[str, ...]     # what its layer_types may name


_BLOCKS = {"deepseek_v2": _Block(False, False, "softmax", ()),
           "olmo_hybrid": _Block(True, False, "softmax", (LINEAR, FULL)),
           "lfm2_moe": _Block(False, True, "sigmoid", (CONV, FULL))}
# published names of keys this class holds under an older model's
_PUBLISHED_NAMES = {"num_experts": "n_routed_experts",
                    "num_dense_layers": "first_k_dense_replace",
                    "norm_eps": "rms_norm_eps"}


@dataclasses.dataclass
class LMConfig:
    # the published config.json's keys
    hidden_size: int = 64
    num_hidden_layers: int = 2
    first_k_dense_replace: int = 1
    intermediate_size: int = 128
    moe_intermediate_size: int = 32
    n_routed_experts: int = 2           # experts HELD HERE
    n_shared_experts: int = 2
    num_experts_per_tok: int = 2
    num_attention_heads: int = 4
    kv_lora_rank: Optional[int] = 16
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-6
    vocab_size: int = 256               # rows HELD HERE
    scoring_func: Optional[str] = None  # None: the model_type's own
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    aux_loss_alpha: float = 0.001
    model_type: str = "deepseek_v2"
    # a layer's mixer by name (its first num_hidden_layers entries);
    # None: latent attention throughout
    layer_types: Optional[List[str]] = None
    num_key_value_heads: Optional[int] = None
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    rope_parameters: Optional[dict] = None
    conv_L_cache: int = 3               # taps of the short convolution
    conv_bias: bool = False
    # a selection bias an expert layer: a table of its own, stepped by
    # the routing's counts at expert_bias_rate (arXiv:2408.15664)
    use_expert_bias: bool = False
    expert_bias_rate: float = 1e-3
    # published keys that change the model and are built one way only
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    # this chip's place in the group that shares each layer
    ep_size: int = 1
    ep_rank: int = 0
    vocab_shard: int = 1
    # the step and the optimizer
    sequences: int = 2
    sequence_length: int = 64
    # sequences the packer holds open (None: 4 x sequences)
    open_sequences: Optional[int] = None
    learning_rate: float = 4.2e-4
    warmup_steps: int = 1               # linear warm-up; 1 = none
    beta1: float = 0.9
    beta2: float = 0.95
    adam_eps: float = 1e-8
    init_std: float = 0.006
    # the embedding rows' own start; None = init_std. With rows as small
    # as the other tensors the residual is mostly attention's running
    # mean and a random router sends every token of a layer to the same
    # experts; with unit rows (nn.Embedding's default) it routes by token
    embed_init_std: Optional[float] = None
    seed: int = 0
    # the attention kernels' query (and key) block; then what is
    # recomputed in blocks (memory only; the numbers are the same)
    attention_block: int = 512
    expert_chunk_rows: int = 8192
    mlp_chunks: int = 1
    head_chunks: int = 1
    gdn_chunk: int = 64         # tokens the recurrence solves at once
    compute_dtype: str = "bfloat16"     # operands of the matrix products

    def __post_init__(self) -> None:
        if self.scoring_func is None and self.model_type in _BLOCKS:
            self.scoring_func = self.block.score

    @classmethod
    def from_dict(cls, d: dict) -> "LMConfig":
        """From a published config's keys (and the program's): a key
        the config lacks is a part the model lacks — no experts without
        ``n_routed_experts`` (published also as ``num_experts``), no
        shared ones without ``n_shared_experts``, no balance loss without
        ``aux_loss_alpha``, no latent attention without ``kv_lora_rank``
        — and not this class's small default."""
        names = {f.name for f in dataclasses.fields(cls)}
        d = {_PUBLISHED_NAMES.get(k, k): v for k, v in d.items()}
        absent = {"n_routed_experts": 0, "kv_lora_rank": None,
                  "n_shared_experts": 0, "aux_loss_alpha": 0.0}
        return cls(**{**{k: v for k, v in absent.items() if k not in d},
                      **{k: v for k, v in d.items() if k in names}})

    @property
    def router_width(self) -> int:
        return self.n_routed_experts * self.ep_size

    @property
    def first_expert(self) -> int:
        return self.n_routed_experts * self.ep_rank

    def learning_rate_at(self, step: int) -> float:
        """The rate of optimizer step ``step`` (from 0): linear warm-up
        over ``warmup_steps``, then constant."""
        return self.learning_rate * min(1.0, (step + 1) / self.warmup_steps)

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace \
            or self.n_routed_experts == 0

    def mixer(self, layer: int) -> str:
        """``LATENT``, ``LINEAR`` or ``FULL``."""
        return LATENT if self.layer_types is None \
            else self.layer_types[layer]

    def layers_of(self, kind: str) -> int:
        return sum(self.mixer(i) == kind
                   for i in range(self.num_hidden_layers))

    @property
    def block(self) -> _Block:
        return _BLOCKS[self.model_type]

    @property
    def post_norm(self) -> bool:
        return self.block.post_norm

    @property
    def head_dim(self) -> int:
        """Of ``full_attention``: the hidden size over the heads."""
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        """Of ``full_attention``: key-value heads (a query head's group
        shares one)."""
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def attention_theta(self) -> Optional[float]:
        """``full_attention``'s rotary base; ``None``: nothing is
        rotated. ``rope_parameters.rope_theta`` where the config has
        that group, else — behind a QK-norm a head — ``rope_theta``."""
        if self.rope_parameters is not None:
            return self.rope_parameters.get("rope_theta")
        return self.rope_theta if self.block.head_norm else None

    @property
    def expert_layers(self) -> int:
        return sum(not self.is_dense(i)
                   for i in range(self.num_hidden_layers))

    def check(self) -> None:
        if self.model_type not in _BLOCKS:
            raise NotImplementedError(
                f"model_type {self.model_type!r}: built are "
                f"{sorted(_BLOCKS)}")
        if self.attention_bias:
            raise NotImplementedError("attention_bias: no projection "
                                      "has a bias here")
        if self.hidden_act != "silu":
            raise NotImplementedError(f"hidden_act {self.hidden_act!r}: "
                                      "only silu (SwiGLU) is built")
        if self.q_lora_rank is not None:
            raise NotImplementedError(
                "q_lora_rank: only the direct query projection is built")
        if self.layer_types is None:
            self._check_latent()
        else:
            self._check_layer_types()
        if self.scoring_func != self.block.score:
            raise NotImplementedError(
                f"scoring_func {self.scoring_func!r} in a {self.model_type} "
                f"block: its router's scores are a {self.block.score}")
        if self.n_routed_experts and self.num_experts_per_tok \
                > self.router_width:
            raise ValueError(f"num_experts_per_tok "
                             f"{self.num_experts_per_tok} of "
                             f"{self.router_width} router outputs")
        if self.warmup_steps < 1:
            raise ValueError(f"warmup_steps {self.warmup_steps}: at least 1")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of {self.ep_size}")
        if self.router_width > 256:
            raise ValueError("the step returns the chosen experts as "
                             "uint8: at most 256 router outputs")
        for n, name in ((self.mlp_chunks, "mlp_chunks"),
                        (self.head_chunks, "head_chunks")):
            if self.sequences % n:
                raise ValueError(f"{name} {n} does not divide "
                                 f"{self.sequences} sequences")

    def _check_latent(self) -> None:
        if self.kv_lora_rank is None:
            raise NotImplementedError(
                "attention without kv_lora_rank and without layer_types: "
                "latent attention is what a config that names no mixer "
                "gets")
        if self.block.mixers:
            raise NotImplementedError(
                f"latent attention in a {self.model_type} block")

    def _check_full_attention(self) -> None:
        heads, kv = self.num_attention_heads, self.kv_heads
        if self.hidden_size % heads:
            raise ValueError(f"{heads} heads do not divide hidden size "
                             f"{self.hidden_size}")
        if heads % kv:
            raise ValueError(f"num_key_value_heads {kv} do not divide "
                             f"{heads} heads")
        if self.block.head_norm:
            if self.attention_theta is None or self.rope_scaling:
                raise NotImplementedError(
                    "rope_theta / rope_scaling: behind a QK-norm a head, "
                    "full attention is built with a plain rotary "
                    "embedding")
            if self.head_dim % 2:
                raise ValueError(f"a rotary embedding over a head of "
                                 f"{self.head_dim} dims")
            return
        if kv != heads:
            raise NotImplementedError(
                f"num_key_value_heads {kv} of {heads} heads: behind a "
                "QK-norm over all heads' dims, grouped key-value heads "
                "are not built")
        if self.attention_theta is not None:
            raise NotImplementedError(
                "rope_parameters.rope_theta: behind a QK-norm over all "
                "heads' dims, full attention is built without a rotary "
                "embedding")

    def _check_layer_types(self) -> None:
        kinds = self.layer_types[:self.num_hidden_layers]
        if len(kinds) < self.num_hidden_layers:
            raise ValueError(f"layer_types names {len(kinds)} of "
                             f"{self.num_hidden_layers} layers")
        mixers = self.block.mixers
        if not mixers:
            raise NotImplementedError(
                f"layer_types in a {self.model_type} block: its mixer is "
                "latent attention throughout")
        for kind in kinds:
            if kind not in mixers:
                raise NotImplementedError(
                    f"layer_types entry {kind!r}: a {self.model_type} "
                    f"block builds {' and '.join(map(repr, mixers))}")
        if self.kv_lora_rank is not None:
            raise NotImplementedError(
                "kv_lora_rank beside layer_types: a config names its "
                "mixers one way")
        if FULL in kinds:
            self._check_full_attention()
        if CONV in kinds:
            if self.conv_bias:
                raise NotImplementedError(
                    "conv_bias: the short convolution is built without "
                    "a bias")
            if self.conv_L_cache < 1:
                raise ValueError(f"conv_L_cache {self.conv_L_cache}: at "
                                 "least one tap")
        if LINEAR in kinds:
            if self.linear_num_value_heads != self.linear_num_key_heads:
                raise NotImplementedError(
                    "linear_num_value_heads other than "
                    "linear_num_key_heads: value heads that share a key "
                    "head are not built")
            if min(self.linear_num_key_heads, self.linear_key_head_dim,
                   self.linear_value_head_dim,
                   self.linear_conv_kernel_dim) < 1:
                raise ValueError("linear_attention wants its linear_* "
                                 "sizes")
            if self.linear_value_head_dim > self.hidden_size:
                raise ValueError("linear_value_head_dim over hidden_size")
            if self.sequence_length % min(self.gdn_chunk,
                                          self.sequence_length):
                raise ValueError(f"gdn_chunk {self.gdn_chunk} does not "
                                 f"divide {self.sequence_length}")


# -- tables and the tensors in them ------------------------------------------

def _attn_columns(c: LMConfig) -> Tuple[int, int, int]:
    return (c.num_attention_heads * (c.qk_nope_head_dim
                                     + c.qk_rope_head_dim),
            c.kv_lora_rank + c.qk_rope_head_dim,
            c.num_attention_heads * c.v_head_dim)


def gdn_shape(c: LMConfig) -> gdn.GatedDeltaShape:
    return gdn.GatedDeltaShape(
        c.linear_num_key_heads, c.linear_key_head_dim,
        c.linear_value_head_dim, c.linear_conv_kernel_dim,
        bool(c.linear_allow_neg_eigval), c.rms_norm_eps, c.gdn_chunk,
        c.compute_dtype)


def norm_offsets(c: LMConfig) -> List[int]:
    """Row of ``norms`` at which each layer's rows start, and (last) the
    final norm's: a latent layer has 3 (attention, latent, feed-forward),
    a linear-attention layer 3 (mixer, feed-forward, the gated output
    norm), a full-attention layer 4 (mixer, feed-forward, q, k), a
    short-convolution layer 2 (mixer, feed-forward)."""
    per = {FULL: 4, CONV: 2}
    rows = [0]
    for i in range(c.num_hidden_layers):
        rows.append(rows[-1] + per.get(c.mixer(i), 3))
    return rows


def _full_columns(c: LMConfig) -> Tuple[int, int, int, int]:
    """``w_q | w_k | w_v | w_o`` of a full-attention layer's table."""
    D, kv = c.hidden_size, c.kv_heads * c.head_dim
    return D, kv, kv, D


def table_shapes(c: LMConfig) -> Dict[str, Tuple[int, ...]]:
    """Every table's logical shape, in the order the superstep holds
    them; the index in this order seeds the table's start values."""
    D, L = c.hidden_size, c.num_hidden_layers
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (c.vocab_size, D)}
    if not c.tie_word_embeddings:
        shapes["head"] = (c.vocab_size, D)
    shapes["norms"] = (norm_offsets(c)[L] + 1, D)
    for i in range(L):
        kind = c.mixer(i)
        if kind == LATENT:
            shapes[f"l{i}.attn"] = (D, sum(_attn_columns(c)))
            shapes[f"l{i}.kv_b"] = (
                c.kv_lora_rank,
                c.num_attention_heads * (c.qk_nope_head_dim
                                         + c.v_head_dim))
        elif kind == LINEAR:
            g = gdn_shape(c)
            shapes[f"l{i}.gdn_in"] = (D, g.in_width)
            shapes[f"l{i}.gdn_conv"] = (g.taps, g.conv_width)
            shapes[f"l{i}.gdn_decay"] = (2, g.heads)
            shapes[f"l{i}.gdn_out"] = (D, g.heads * g.dv)
        elif kind == CONV:
            shapes[f"l{i}.conv_in"] = (D, 3 * D)
            shapes[f"l{i}.conv_taps"] = (c.conv_L_cache, D)
            shapes[f"l{i}.conv_out"] = (D, D)
        else:
            shapes[f"l{i}.attn"] = (D, sum(_full_columns(c)))
        if c.is_dense(i):
            shapes[f"l{i}.mlp"] = (3, D, c.intermediate_size)
        else:
            F = c.moe_intermediate_size
            shapes[f"l{i}.router"] = (D, c.router_width)
            if c.use_expert_bias:
                shapes[f"l{i}.expert_bias"] = (c.router_width,)
            if c.n_shared_experts:
                shapes[f"l{i}.shared"] = (3, D, c.n_shared_experts * F)
            shapes[f"l{i}.experts"] = (c.n_routed_experts, 3, D, F)
    return shapes


def is_bias(name: str) -> bool:
    """Whether table ``name`` is an expert layer's selection bias: the
    one kind whose updater is ``sgd`` and whose delta is no gradient."""
    return name.endswith(".expert_bias")


def table_layout(c: LMConfig) -> Dict[str, Dict[str, tuple]]:
    """``{table: {role: index}}``: the tensor with that published role
    is ``table[index]``. Roles: ``embed``, ``head`` [V, D],
    ``final_norm``; a latent layer's ``attn_norm``, ``w_q``, ``w_kv_a``,
    ``kv_norm``, ``w_kv_b``, ``w_o`` [D, H v] (out x in), ``ffn_norm``;
    a linear- or full-attention layer's ``mixer_norm``, ``ffn_norm``,
    ``w_q``, ``w_k``, ``w_v``, ``w_o`` (out x in), and the first's
    ``w_g``, ``w_a``, ``w_b``, ``conv`` [taps, q | k | v], ``a_log``,
    ``dt_bias``, ``o_norm`` [v head dim], the second's ``q_norm``,
    ``k_norm`` (a head's dims wide where the QK-norm is a head's); a
    short-convolution layer's ``mixer_norm``, ``ffn_norm``, ``conv_in``
    [D, B | C | X], ``conv_taps`` [taps, D], ``conv_out`` [D, D] (in x
    out); ``w_gate`` / ``w_up`` / ``w_down`` [D, F]; ``router``,
    ``expert_bias`` [E], ``shared_gate`` / ``_up`` / ``_down``,
    ``exp_gate`` / ``_up`` / ``_down`` [held, D, F]. A tied model has no
    ``head``: its head is ``embed``."""
    every = slice(None)
    L = c.num_hidden_layers
    rows = norm_offsets(c)
    layout: Dict[str, Dict[str, tuple]] = {
        "embed": {"embed": (slice(0, c.vocab_size),)}}   # less the scratch row
    if not c.tie_word_embeddings:
        layout["head"] = {"head": (every,)}
    layout["norms"] = {"final_norm": (rows[L],)}
    qk_norm = (slice(0, c.head_dim),) if c.block.head_norm else ()

    def columns(names, widths):
        ends = np.cumsum(widths)
        return {f"l{i}.{n}": (every, slice(int(e - w), int(e)))
                for n, w, e in zip(names, widths, ends)}

    for i in range(L):
        kind, r = c.mixer(i), rows[i]
        if kind == LATENT:
            layout["norms"].update({
                f"l{i}.attn_norm": (r,),
                f"l{i}.kv_norm": (r + 1, slice(0, c.kv_lora_rank)),
                f"l{i}.ffn_norm": (r + 2,)})
            layout[f"l{i}.attn"] = columns(("w_q", "w_kv_a", "w_o"),
                                           _attn_columns(c))
            layout[f"l{i}.kv_b"] = {f"l{i}.w_kv_b": (every,)}
        elif kind == LINEAR:
            g = gdn_shape(c)
            layout["norms"].update({
                f"l{i}.mixer_norm": (r,), f"l{i}.ffn_norm": (r + 1,),
                f"l{i}.o_norm": (r + 2, slice(0, g.dv))})
            layout[f"l{i}.gdn_in"] = columns(
                ("w_q", "w_k", "w_v", "w_g", "w_a", "w_b"),
                (g.heads * g.dk, g.heads * g.dk, g.heads * g.dv,
                 g.heads * g.dv, g.heads, g.heads))
            layout[f"l{i}.gdn_conv"] = {f"l{i}.conv": (every,)}
            layout[f"l{i}.gdn_decay"] = {f"l{i}.a_log": (0,),
                                         f"l{i}.dt_bias": (1,)}
            layout[f"l{i}.gdn_out"] = {f"l{i}.w_o": (every,)}
        elif kind == CONV:
            layout["norms"].update({
                f"l{i}.mixer_norm": (r,), f"l{i}.ffn_norm": (r + 1,)})
            for part in ("conv_in", "conv_taps", "conv_out"):
                layout[f"l{i}.{part}"] = {f"l{i}.{part}": (every,)}
        else:
            layout["norms"].update({
                f"l{i}.mixer_norm": (r,), f"l{i}.ffn_norm": (r + 1,),
                f"l{i}.q_norm": (r + 2, *qk_norm),
                f"l{i}.k_norm": (r + 3, *qk_norm)})
            layout[f"l{i}.attn"] = columns(("w_q", "w_k", "w_v", "w_o"),
                                           _full_columns(c))
        if c.is_dense(i):
            layout[f"l{i}.mlp"] = {f"l{i}.w_{part}": (j,) for j, part in
                                   enumerate(("gate", "up", "down"))}
        else:
            layout[f"l{i}.router"] = {f"l{i}.router": (every,)}
            if c.use_expert_bias:
                layout[f"l{i}.expert_bias"] = {
                    f"l{i}.expert_bias": (every,)}
            if c.n_shared_experts:
                layout[f"l{i}.shared"] = {
                    f"l{i}.shared_{part}": (j,) for j, part in
                    enumerate(("gate", "up", "down"))}
            layout[f"l{i}.experts"] = {
                f"l{i}.exp_{part}": (every, j) for j, part in
                enumerate(("gate", "up", "down"))}
    return layout


def named_parameters(c: LMConfig, tables: Dict[str, Any]) -> Dict[str, Any]:
    """The tensors of the model under their published roles
    (:func:`table_layout`), cut out of the tables given (numpy or jax
    arrays; views where the arrays give views)."""
    return {role: tables[name][index]
            for name, roles in table_layout(c).items() if name in tables
            for role, index in roles.items()}


def start_std(c: LMConfig, name: str) -> float:
    """The standard deviation table ``name`` starts from."""
    if name == "embed" and c.embed_init_std is not None:
        return c.embed_init_std
    return c.init_std


def _table_key(c: LMConfig, index):
    """The key a table's start is drawn from: the seed's, folded with
    the table's index in :func:`table_shapes` (traceable)."""
    seed = int(c.seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, index)


def start_values(c: LMConfig, index, shape, std):
    """A table's start (the norm weights apart, which are 1):
    normal(0, ``std``) from the seed and the table's index in
    :func:`table_shapes` (traceable, the index and ``std`` too)."""
    return std * jax.random.normal(_table_key(c, index), tuple(shape),
                                   jnp.float32)


def decay_start(c: LMConfig, index, heads: int):
    """A linear-attention layer's ``[a_log; dt_bias]`` [2, H] as the
    public layer starts them: ``a_log = log U(0, 16)`` (the draw kept
    off 0), ``dt_bias`` the inverse softplus of ``exp(U(log 0.001,
    log 0.1))``, from the seed and the table's index."""
    key = _table_key(c, index)
    a = jax.random.uniform(jax.random.fold_in(key, 0), (heads,),
                           jnp.float32, 0.0, 16.0)
    dt = jnp.exp(jax.random.uniform(
        jax.random.fold_in(key, 1), (heads,), jnp.float32,
        float(np.log(0.001)), float(np.log(0.1))))
    return jnp.stack([jnp.log(jnp.maximum(a, 1e-4)),
                      dt + jnp.log(-jnp.expm1(-dt))])


# -- the phases of a step, each under a program scope --------------------------

@telemetry.scope("lm.embed_gather")
def _embed_gather(embed, tokens):
    return jnp.take(embed, tokens, axis=0)


@telemetry.scope("lm.embed_scatter")
def _embed_scatter(embed, tokens, d_x, d_head=None):
    """The token gradients as a delta of the table's shape: rows
    scatter-added, word2vec's form — onto zeros, or, for a tied table,
    onto the head's dense gradient ``d_head`` (the table's logical
    rows; a scratch row follows them): its ONE delta."""
    base = jnp.zeros_like(embed) if d_head is None else jnp.pad(
        d_head.astype(embed.dtype),
        ((0, embed.shape[0] - d_head.shape[0]), (0, 0)))
    return base.at[tokens.reshape(-1)].add(
        d_x.reshape(-1, d_x.shape[-1]).astype(embed.dtype))


def _swiglu(h, w):
    """``h`` in the products' dtype; ``w`` [3, D, F] float32: gate, up,
    and down stored [D, F]."""
    w = w.astype(h.dtype)
    gate = jnp.dot(h, w[0], preferred_element_type=jnp.float32)
    up = jnp.dot(h, w[1], preferred_element_type=jnp.float32)
    return lax.dot_general((jax.nn.silu(gate) * up).astype(h.dtype), w[2],
                           (((h.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _over_sequences(fn: Callable, chunks: int, x, *rest):
    """``fn(x_group, *rest)`` a group of sequences at a time, each group
    recomputed in the backward pass; the groups' results stacked."""
    if chunks == 1:
        return fn(x, *rest)[None]
    grouped = jax.tree.map(
        lambda a: a.reshape(chunks, a.shape[0] // chunks, *a.shape[1:]), x)
    return lax.map(jax.checkpoint(lambda g: fn(g, *rest)), grouped)


@telemetry.scope("lm.dense_mlp")
def _dense_mlp_group(h, w):
    return _swiglu(h, w)


@telemetry.scope("lm.moe.shared")
def _shared_experts_group(h, w):
    return _swiglu(h, w)


@telemetry.scope("lm.block_norm")
def _input_norm(x, weight, eps):
    """A pre-norm block's norm on the residual its ``layer_types`` mixer
    reads; ``eps`` arrives as an array."""
    return mla.rms_norm(x, weight, eps)


@telemetry.scope("lm.block_norm")
def _output_norm(y, weight, eps):
    """A block's norm on the output of its mixer or feed-forward (the
    reordered norm); ``eps`` arrives as an array."""
    return mla.rms_norm(y, weight, eps)


@telemetry.scope("lm.head_loss")
def _head_loss_group(group, final_norm, head, eps):
    """Sum of the cross-entropy over a group's predicting tokens;
    ``head`` arrives in the products' dtype."""
    x, target, predicts = group
    h = mla.rms_norm(x, final_norm, eps).astype(head.dtype)
    logits = lax.dot_general(h, head, (((2,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    nll = jax.nn.logsumexp(logits, -1) \
        - jnp.take_along_axis(logits, target[..., None], -1)[..., 0]
    return jnp.sum(nll * predicts)


class TransformerLM:
    """The app: the tables of a decoder and the fused training step.

    ``docs`` is a re-iterable of token-id arrays (one a document); each
    :meth:`train` call packs it from its start into steps of
    ``sequences`` x ``sequence_length`` slots on a producer thread."""

    def __init__(self, config: LMConfig,
                 docs: Optional[Iterable[np.ndarray]] = None, *,
                 mesh=None, name: str = "lm") -> None:
        config.check()
        self.config = c = config
        self.docs = docs
        self.mesh = mesh if mesh is not None else core.mesh()
        if self.mesh.size != 1:
            raise NotImplementedError(
                "TransformerLM runs one chip of its group: the exchange "
                "of tokens over the model axis is not built")
        # a CPU mesh (tests) runs the Pallas kernels interpreted
        self._interpret = interpret_mode(self.mesh)
        if c.layer_types is None:
            self._shape = mla.LatentShape(
                c.num_attention_heads, c.qk_nope_head_dim,
                c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank,
                c.rms_norm_eps, c.compute_dtype)
            self._rotary = mla.Rotary.from_config(
                rope_dim=c.qk_rope_head_dim,
                qk_dim=c.qk_nope_head_dim + c.qk_rope_head_dim,
                theta=float(c.rope_theta), scaling=c.rope_scaling)
        elif c.layers_of(LINEAR):
            self._gdn = gdn_shape(c)
        self._norm_rows = norm_offsets(c)
        option = AddOption(learning_rate=c.learning_rate, momentum=c.beta1,
                           rho=c.beta2, lam=c.adam_eps)
        self.tables: Dict[str, Table] = {}
        with telemetry.span("lm.setup.init_tables"):
            draw = jax.jit(
                lambda index, std, start, shape, pad: jnp.pad(
                    jnp.full(shape, float(start == "ones"), jnp.float32)
                    if start in ("ones", "zeros")
                    else decay_start(c, index, shape[1])
                    if start == "decay"
                    else start_values(c, index, shape, std), pad),
                static_argnums=(2, 3, 4))       # one program a shape
            for index, (tname, shape) in enumerate(
                    table_shapes(c).items()):
                # the embedding is a MatrixTable: a scratch row follows
                # its logical rows
                pad = ((0, 1 if tname == "embed" else 0),) \
                    + ((0, 0),) * (len(shape) - 1)
                # a selection bias is state stepped by a rule that is no
                # gradient: plain sgd on the routing's delta, at its own
                # rate
                updater, own = ("sgd", AddOption(
                    learning_rate=c.expert_bias_rate)) if is_bias(tname) \
                    else ("adam", dataclasses.replace(option))
                kw = dict(updater=updater, mesh=self.mesh,
                          default_option=own)
                table = MatrixTable(
                    *shape, "float32", name=f"{name}.{tname}", **kw) \
                    if tname == "embed" \
                    else Table(f"{name}.{tname}", shape, "float32", **kw)
                # the start is drawn on the device and installed as it
                # is: 2.5 GB of it never pass the host
                start = "ones" if tname == "norms" else "decay" \
                    if tname.endswith(".gdn_decay") else "zeros" \
                    if is_bias(tname) else "normal"
                table.put_raw(draw(index, start_std(c, tname), start,
                                   shape, pad))
                self.tables[tname] = table
            jax.block_until_ready([t.param for t in self.tables.values()])
        self.parameters = sum(int(np.prod(s))
                              for s in table_shapes(c).values())
        self.loss_history: List[Tuple[float, float]] = []
        self.aux_tail: collections.deque = collections.deque(
            maxlen=AUX_KEEP)
        self.tokens_trained = 0
        self._build_superstep()

    # -- the model ---------------------------------------------------------

    def _latent(self, x, t, norms, doc, pos):
        """Latent attention's addition to the residual (its own norm
        first, inside ``project``)."""
        c = self.config
        q, a, _ = _attn_columns(c)
        attn = t["attn"]
        q_nope, q_pe, k_nope, k_pe, v = mla.project(
            x, pos, norms[0], attn[:, :q], attn[:, q:q + a],
            norms[1, :c.kv_lora_rank], t["kv_b"], self._shape,
            self._rotary)
        o = mla.attend(q_nope, q_pe, k_nope, k_pe, v, doc,
                       scale=self._rotary.score_scale,
                       block=c.attention_block, interpret=self._interpret)
        return mla.output(o, attn[:, q + a:])

    def _gated_delta(self, x, doc, t_in, conv, decay, t_out, o_norm):
        """The gated delta rule's addition to the residual, before the
        block's norm."""
        g = self._gdn
        qkv, gate, log_decay, beta = gdn.project(x, t_in, decay[0],
                                                 decay[1], g)
        o = gdn.recur(gdn.short_conv(qkv, conv, doc), log_decay, beta, doc,
                      g, interpret=self._interpret)
        return gdn.gate_out(o, gate, o_norm, t_out, g)

    def _attention(self, x, doc, pos, attn, q_norm, k_norm):
        """Softmax attention with per-head keys, on ``x`` as the block
        hands it over: QK-norm over all heads' dims and no rotary part,
        or (``head_norm``) QK-norm a head, a rotary embedding and
        grouped key-value heads."""
        c = self.config
        q_end, k_end, v_end, _ = np.cumsum(_full_columns(c))
        w_q, w_k, w_v = (attn[:, :q_end], attn[:, q_end:k_end],
                         attn[:, k_end:v_end])
        if c.block.head_norm:
            d = c.head_dim
            q, k, v = mla.project_grouped(
                x, pos, w_q, w_k, w_v, q_norm[:d], k_norm[:d],
                c.num_attention_heads, c.kv_heads, c.rms_norm_eps,
                c.attention_theta, c.compute_dtype)
        else:
            q, k, v = mla.project_heads(
                x, w_q, w_k, w_v, q_norm, k_norm, c.num_attention_heads,
                c.rms_norm_eps, c.compute_dtype)
        o = mla.attend_heads(q, k, v, doc, scale=c.head_dim ** -0.5,
                             block=c.attention_block,
                             interpret=self._interpret)
        return mla.output_heads(o, attn[:, v_end:])

    def _short_conv(self, u, doc, w_in, taps, w_out):
        """The gated short convolution's addition to the residual, from
        the block's normed input."""
        dtype = self.config.compute_dtype
        return sconv.project_out(
            sconv.mix(sconv.project_in(u, w_in, dtype), taps, doc), w_out,
            dtype)

    def _layer(self, i: int, x, t, norms, doc, pos, real):
        """One decoder layer on the residual ``x`` [B, S, D] float32,
        from the layer's tables ``t`` and its rows of ``norms``;
        returns ``(new residual, the layer's balance loss)`` and what an
        expert layer routed (counts, chosen experts, rows computed)."""
        c = self.config
        kind, eps = c.mixer(i), jnp.float32(c.rms_norm_eps)
        if kind == LATENT:
            x = x + self._latent(x, t, norms, doc, pos)
        elif c.post_norm:
            if kind == LINEAR:
                y = self._gated_delta(
                    x, doc, t["gdn_in"], t["gdn_conv"], t["gdn_decay"],
                    t["gdn_out"], norms[2, :c.linear_value_head_dim])
            else:
                y = self._attention(x, doc, pos, t["attn"], norms[2],
                                    norms[3])
            x = x + _output_norm(y, norms[0], eps)
        else:
            u = _input_norm(x, norms[0], eps)
            if kind == CONV:
                x = x + self._short_conv(u, doc, t["conv_in"],
                                         t["conv_taps"], t["conv_out"])
            else:
                x = x + self._attention(u, doc, pos, t["attn"], norms[2],
                                        norms[3])
        if c.post_norm:
            y = _over_sequences(_dense_mlp_group, c.mlp_chunks,
                                x.astype(c.compute_dtype), t["mlp"])
            return (x + _output_norm(y.reshape(x.shape), norms[1], eps),
                    jnp.zeros(())), None
        # the feed-forward's norm: a latent layer's third row, else the
        # second
        h = _input_norm(x, norms[2 if kind == LATENT else 1], eps)
        hb = h.astype(c.compute_dtype)
        if c.is_dense(i):
            y = _over_sequences(_dense_mlp_group, c.mlp_chunks, hb,
                                t["mlp"])
            return (x + y.reshape(x.shape), jnp.zeros(())), None
        B, S, D = x.shape
        routing = moe.route(
            h, t["router"], real, top_k=c.num_experts_per_tok,
            norm_topk_prob=c.norm_topk_prob,
            scaling=c.routed_scaling_factor, alpha=c.aux_loss_alpha,
            score=c.scoring_func, bias=t.get("expert_bias"))
        plan = moe.plan(routing.top_e, real, first=c.first_expert,
                        held=c.n_routed_experts,
                        chunk_rows=c.expert_chunk_rows)
        row_w = moe.row_weights(routing.top_s, plan.row_src)
        y, rows = moe.routed_experts(h.reshape(B * S, D), t["experts"],
                                     row_w, plan, c.expert_chunk_rows,
                                     jnp.dtype(c.compute_dtype))
        if c.n_shared_experts:
            x = x + _over_sequences(_shared_experts_group, c.mlp_chunks, hb,
                                    t["shared"]).reshape(x.shape)
        return (x + y.reshape(x.shape), routing.balance), (
            routing.counts, routing.top_e.astype(jnp.uint8), rows)

    def _layer_tables(self, tables: Dict[str, Any], i: int):
        prefix = f"l{i}."
        rows = self._norm_rows
        return ({k[len(prefix):]: v for k, v in tables.items()
                 if k.startswith(prefix)},
                tables["norms"][rows[i]:rows[i + 1]])

    def _head_loss(self, x, final_norm, head, tokens, doc):
        """Mean cross-entropy over the tokens that have a successor in
        their document, a group of sequences at a time."""
        c = self.config
        target = jnp.roll(tokens, -1, axis=1)
        predicts = ((jnp.roll(doc, -1, axis=1) == doc) & (doc > 0)
                    ).at[:, -1].set(False).astype(jnp.float32)
        ce_sum = jnp.sum(_over_sequences(
            _head_loss_group, c.head_chunks, (x, target, predicts),
            final_norm, head.astype(c.compute_dtype), c.rms_norm_eps))
        return ce_sum / jnp.maximum(predicts.sum(), 1.0)

    def _sweep(self, tables: Dict[str, Any], batch,
               consume: Callable[[str, Any], Any]):
        """One step's loss and gradients, a layer at a time from the
        last to the first. The forward pass keeps only the residual
        entering each layer; the backward pass recomputes a layer from
        it, and hands each table's gradient to ``consume(name, grad)``
        as soon as the layer is done — before the layer below starts
        (an optimization barrier holds the order), so that what
        ``consume`` frees (the optimizer folds a gradient into its table
        and lets it go) is free while the rest is computed. Two deltas
        are no gradient of their own table alone: a selection bias gets
        what the layer's routing asks of it (:func:`moe.bias_delta`),
        and a tied ``embed`` ONE delta, the head's dense gradient — held
        until the sweep's end — with the tokens' rows scatter-added onto
        it. Returns ``(aux, {table: what consume returned})``."""
        c = self.config
        L = c.num_hidden_layers
        tokens, doc, pos = batch[0], batch[1], batch[2]
        real = (doc > 0).astype(jnp.float32)

        def layer(i, x, t, n):
            # what a block does between its phases (residual adds, casts,
            # regrouping by sequences, the cotangents' sums where the
            # residual forks) is named too; a phase's ops keep the
            # phase's name, the innermost scope
            return telemetry.scope("lm.residual")(partial(self._layer, i))(
                x, t, n, doc, pos, real)

        x = _embed_gather(tables["embed"], tokens)
        entering = []
        for i in range(L):
            entering.append(x)
            (x, _), _ = layer(i, x, *self._layer_tables(tables, i))
        norms, rows = tables["norms"], self._norm_rows
        tied = c.tie_word_embeddings
        ce, (d_x, d_final, d_head) = jax.value_and_grad(
            self._head_loss, argnums=(0, 1, 2))(
                x, norms[rows[L]],
                tables["embed"][:c.vocab_size] if tied else tables["head"],
                tokens, doc)
        out = {} if tied else {"head": consume("head", d_head)}
        d_norms = jnp.zeros_like(norms).at[rows[L]].set(d_final)
        balance, routed = jnp.zeros(()), []
        for i in reversed(range(L)):
            # the barrier keeps the compiler from sharing this forward
            # pass with the first one (and its memory with it)
            x, d_x = lax.optimization_barrier((entering.pop(), d_x))
            (_, b), vjp, r = jax.vjp(partial(layer, i), x,
                                     *self._layer_tables(tables, i),
                                     has_aux=True)
            d_x, d_t, d_n = vjp((d_x, jnp.ones(())))
            d_norms = d_norms.at[rows[i]:rows[i + 1]].set(d_n)
            if "expert_bias" in d_t:
                d_t["expert_bias"] = moe.bias_delta(r[0])
            done = {f"l{i}.{k}": consume(f"l{i}.{k}", g)
                    for k, g in d_t.items()}
            d_x, done = lax.optimization_barrier((d_x, done))
            out.update(done)
            balance = balance + b
            if r is not None:
                routed.insert(0, r)
        out["norms"] = consume("norms", d_norms)
        out["embed"] = consume("embed", _embed_scatter(
            tables["embed"], tokens, d_x, d_head if tied else None))
        aux = {"ce": ce, "balance": balance}
        attending = L - c.layers_of(LINEAR) - c.layers_of(CONV)
        if attending:
            # every attending layer's kernels run the same block pairs
            aux["attend"] = attending * mla.key_blocks(doc,
                                                       c.attention_block)
        if c.layers_of(LINEAR):
            # (sequence, linear layer, chunk) triples computed, and the
            # restarts of their states: one a document and layer
            B, S = doc.shape
            aux["gdn"] = c.layers_of(LINEAR) * jnp.stack([
                jnp.asarray(B * (S // min(c.gdn_chunk, S)), jnp.int32),
                gdn.doc_starts(doc)])
        if c.layers_of(CONV):
            # documents whose first tokens read zeroed taps, a layer
            aux["conv"] = c.layers_of(CONV) * gdn.doc_starts(doc)
        if routed:
            aux.update(zip(("counts", "chosen", "rows"),
                           (jnp.stack(a) for a in zip(*routed))))
        return aux, out

    # -- the fused superstep -------------------------------------------------

    def _build_superstep(self) -> None:
        self._fused = make_superstep(
            tuple(self.tables.values()),
            self._step_body(list(self.tables),
                            [t.updater.apply for t in self.tables.values()]),
            name="lm_superstep")

    def _step_body(self, names: List[str], appliers: List[Callable]):
        """The superstep's body over the tables ``names``: loss,
        gradients, and each table's updater applied to its gradient."""
        c = self.config
        appliers = [telemetry.scope("lm.adam")(a) for a in appliers]
        probe_rows = min(PROBE_ROWS, c.vocab_size)
        probe_expert = next((f"l{i}.experts" for i in
                             range(c.num_hidden_layers)
                             if not c.is_dense(i)), None)
        # the first recurrence's key projection, entry by entry
        probe_gdn = next((f"l{i}.gdn_in" for i in
                          range(c.num_hidden_layers)
                          if c.mixer(i) == LINEAR), None)
        gdn_keys = c.linear_num_key_heads * c.linear_key_head_dim
        # the first short convolution behind experts (else the first)
        convs = [i for i in range(c.num_hidden_layers)
                 if c.mixer(i) == CONV]
        probe_conv = next((f"l{i}.conv_in" for i in sorted(
            convs, key=c.is_dense)), None)
        biases = [n for n in names if is_bias(n)]

        def body(params, states, locals_, options, batch):
            held = {n: (apply, p, s, o) for n, apply, p, s, o in
                    zip(names, appliers, params, states, options)}

            def fold(name, grad):
                """The optimizer's step on one table, and what the step
                reports of its gradient."""
                apply, p, s, o = held[name]
                report = {"norm": jnp.sqrt(jnp.sum(jnp.square(grad)))}
                if name == "embed":
                    report["probe_embed"] = grad[:probe_rows]
                elif name == probe_expert:
                    report["probe_expert"] = grad[0]
                elif name == probe_gdn:
                    report["probe_gdn_k"] = grad[:, gdn_keys:2 * gdn_keys]
                elif name == probe_conv:
                    report["probe_conv_in"] = grad
                return apply(p, s, grad, o), report

            aux, done = self._sweep(dict(zip(names, params)), batch, fold)
            aux["grad_norms"] = jnp.stack([done[n][1]["norm"]
                                           for n in names])
            aux["probe_embed"] = done["embed"][1]["probe_embed"]
            if probe_gdn is not None:
                aux["probe_gdn_k"] = done[probe_gdn][1]["probe_gdn_k"]
            if probe_conv is not None:
                aux["probe_conv_in"] = done[probe_conv][1]["probe_conv_in"]
            if biases:      # the largest |bias| the step leaves
                aux["bias"] = jnp.max(jnp.abs(jnp.stack(
                    [done[n][0][0] for n in biases])))
            if probe_expert is not None:
                routed = jnp.sum(lax.dynamic_slice_in_dim(
                    aux["counts"], c.first_expert, c.n_routed_experts, 1))
                aux["moe"] = jnp.stack([routed, routed
                                        - jnp.sum(aux.pop("rows"))])
                aux["imbalance"] = jnp.max(jax.vmap(
                    lambda n: moe.expert_load_max_over_mean(
                        n, first=c.first_expert,
                        held=c.n_routed_experts))(aux["counts"]))
                aux["probe_expert"] = done[probe_expert][1]["probe_expert"]
            return (tuple(done[n][0][0] for n in names),
                    tuple(done[n][0][1] for n in names), locals_, aux)

        return body

    # -- training ------------------------------------------------------------

    def _batches(self) -> Iterable[Batch]:
        if self.docs is None:
            raise ValueError("TransformerLM was given no documents")
        c = self.config
        return pack_documents(self.docs, c.sequences, c.sequence_length,
                              open_sequences=c.open_sequences)

    def _place(self, batch: Batch) -> jax.Array:
        return core.place(np.stack([batch["tokens"], batch["doc"],
                                    batch["pos"]]), mesh=self.mesh)

    def train(self, total_steps: Optional[int] = None) -> float:
        """Train on the documents from their start, ``total_steps``
        steps or until they fill no further step; returns the last
        step's loss. Ends on the fence of the updated tables."""
        steps: List[dict] = []
        tokens = pads = 0
        t0 = time.perf_counter()
        batches = prefetch_iterator(self._batches(), depth=PREFETCH_STEPS,
                                    name="lm.docs")
        try:
            while total_steps is None or len(steps) < total_steps:
                with telemetry.span("lm.wait_data"):
                    batch = next(batches, None)
                if batch is None:
                    break
                with telemetry.span("lm.place"):
                    placed = self._place(batch)
                with telemetry.span("lm.superstep"):
                    lr = self.config.learning_rate_at(
                        self.tables["embed"].default_option.step)
                    for tname, table in self.tables.items():
                        if not is_bias(tname):  # a bias keeps its own rate
                            table.default_option.learning_rate = lr
                    _, aux = self._fused((), placed)
                telemetry.beat()
                # of every step only the scalars wait for the fence; the
                # whole aux (probed gradients, chosen experts) of the
                # last few
                steps.append({k: aux[k] for k in _SMALL_AUX if k in aux})
                self.aux_tail.append(aux)
                n = real_tokens(batch)
                tokens += n
                pads += batch["doc"].size - n
        finally:
            batches.close()
        with telemetry.span("lm.fence"):
            self.tables["embed"].wait()
            dt = time.perf_counter() - t0
            small = jax.device_get(steps)
        self.loss_history += [(float(s["ce"]), float(s["balance"]))
                              for s in small]
        self.tokens_trained += tokens
        telemetry.counter("lm.tokens").inc(tokens)
        telemetry.counter("lm.pad_tokens").inc(pads)
        for key, names in (("attend", ("lm.attend.key_blocks",
                                       "lm.attend.key_blocks_computed")),
                           ("gdn", ("lm.gdn.chunks", "lm.gdn.doc_starts")),
                           ("conv", ("lm.conv.doc_starts",))):
            if small and key in small[-1]:
                for i, name in enumerate(names):
                    telemetry.counter(name).inc(int(sum(
                        np.atleast_1d(s[key])[i] for s in small)))
        if small and "bias" in small[-1]:
            telemetry.counter("moe.bias_steps").inc(
                len(small) * self.config.expert_layers)
            telemetry.gauge("moe.expert_bias_max_abs").set(
                float(small[-1]["bias"]))
        if small and "moe" in small[-1]:
            telemetry.counter("moe.tokens_routed").inc(
                int(sum(s["moe"][0] for s in small)))
            telemetry.counter("moe.tokens_dropped").inc(
                int(sum(s["moe"][1] for s in small)))
            telemetry.gauge("moe.expert_load_max_over_mean").set(
                float(small[-1]["imbalance"]))
        if not small:
            return float("nan")
        final = sum(self.loss_history[-1])
        log.info("lm train done: %d steps, loss=%.4f, %.0f tokens/s",
                 len(steps), final, tokens / dt)
        return final

    def _raw(self) -> Dict[str, jax.Array]:
        return {n: t.raw() for n, t in self.tables.items()}

    def gradients(self, batch: Batch) -> Tuple[dict, Dict[str, jax.Array]]:
        """One step's ``(aux, gradient of every table)`` at the tables as
        they are, nothing updated (an evaluation, compiled on first
        use; the gradients take a third of the tables' memory)."""
        if not hasattr(self, "_eval_gradients"):
            self._eval_gradients = jax.jit(
                lambda tables, b: self._sweep(tables, b, lambda _, g: g))
        return self._eval_gradients(self._raw(), self._place(batch))

    def hidden_states(self, batch: Batch) -> jax.Array:
        """The residual after the last layer, [B, S, D] float32."""
        if not hasattr(self, "_eval_hidden"):
            def hidden(tables, b):
                x = _embed_gather(tables["embed"], b[0])
                real = (b[1] > 0).astype(jnp.float32)
                for i in range(self.config.num_hidden_layers):
                    (x, _), _ = self._layer(
                        i, x, *self._layer_tables(tables, i), b[1], b[2],
                        real)
                return x
            self._eval_hidden = jax.jit(hidden)
        return self._eval_hidden(self._raw(), self._place(batch))

    def logits(self, batch: Batch) -> jax.Array:
        """The logits of the vocabulary rows held here, [B, S, V]
        float32: the final norm and the head on :meth:`hidden_states`."""
        c = self.config
        tables = self._raw()
        h = mla.rms_norm(self.hidden_states(batch),
                         tables["norms"][self._norm_rows[-1]],
                         c.rms_norm_eps).astype(c.compute_dtype)
        head = tables["embed"][:c.vocab_size] if c.tie_word_embeddings \
            else tables["head"]
        return lax.dot_general(
            h, head.astype(c.compute_dtype),
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def named_parameters(self) -> Dict[str, jax.Array]:
        """Live views of the tables under the model's own names."""
        return named_parameters(self.config, self._raw())
