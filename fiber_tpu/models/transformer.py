"""Tiny transformer LM over the sequence-parallel attention planes.

The reference framework has no model-training story at all (it is a
task-parallel library); this module is the beyond-parity demonstration
that fiber_tpu's long-context planes — ring attention
(:func:`fiber_tpu.ops.ring_attention`) and Ulysses
(:func:`fiber_tpu.ops.ulysses_attention`) — are not inference toys: a
causal LM trains through them with jax AD (their gradients match
full-matrix attention; tests/test_device.py pins that), with the
sequence axis sharded over the mesh so context length scales with
device count.

Deliberately small and dependency-free (pure jnp pytree params, no
flax): the framework's flagship workloads are population-based, and
this exists to prove the sequence-parallel plane end to end —
embedding -> [RMSNorm -> attention -> residual -> RMSNorm -> MLP ->
residual] x L -> norm -> logits.

A model is a description of its layers (:class:`Block`): each layer has
a mixer (attention with its own query-head count, window and rope, latent
attention over low-rank queries, keys and values (:class:`Latent`), or a
state-space mixer, :mod:`fiber_tpu.ops.ssm`), a feed-forward (ungated
MLP, gated MLP, or sparse experts of which this program holds a share,
:mod:`fiber_tpu.ops.moe`), or one of the two alone, each part behind a
norm and, with ``post_norm``, before a second one. :class:`BlockLM`
runs any such description, once or ``passes`` times over the same
weights (with an :class:`ExitGate`, under the expected-exit loss), with
a multi-token-prediction module (:class:`MTP`) or without;
:class:`TinyLM` is the uniform one.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple


# ---------------------------------------------------------------------
# The description of a model's layers. A model is a sequence of
# ``Block``s; ``TinyLM`` is the uniform one (every layer the same).
# ---------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN scaling of a rope's frequencies (arXiv:2309.00071,
    NTK-by-parts, as ``transformers`` computes it): per frequency a
    linear ramp between the rope's own ``base^(-2i/r)`` and that over
    ``factor``, the ramp's ends where a frequency makes ``beta_fast``
    and ``beta_slow`` turns over ``original_max_position`` positions;
    cos and sin times ``attention_factor`` (``0.1 ln(factor) + 1``
    where none is given)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class Rope:
    """A layer's rotary embedding: ``base``, how many leading features
    of each head rotate (``rotary``; None = the whole head; the rest
    pass through), and an optional YaRN scaling. Feature ``j`` of the
    first half turns with feature ``j + r/2`` (``rotate_half``), or,
    ``interleaved``, feature ``2j`` with ``2j + 1`` (adjacent pairs, the
    ``rope_interleave`` of DeepSeek-V3's configuration); pair ``j``
    turns by ``position * base^(-2j/r)`` either way."""

    base: float = 10000.0
    rotary: Optional[int] = None
    yarn: Optional[Yarn] = None
    interleaved: bool = False

    def table(self, head_dim: int):
        """(features that rotate, inverse frequencies or None for the
        plain ``base^(-2i/r)``, factor on cos and sin)."""
        r = head_dim if self.rotary is None else int(self.rotary)
        if r < 2 or r % 2 or r > head_dim:
            raise ValueError(
                f"rope rotates {r} of {head_dim} features: need an even "
                "count within the head")
        if self.yarn is None:
            return r, None, 1.0
        import math

        import numpy as np

        y = self.yarn
        extrapolation = 1.0 / (self.base ** (np.arange(0, r, 2) / r))
        interpolation = extrapolation / y.factor

        def turns_at(turns):
            return (r * math.log(y.original_max_position
                                 / (turns * 2 * math.pi))
                    / (2 * math.log(self.base)))

        low = max(math.floor(turns_at(y.beta_fast)), 0)
        high = min(math.ceil(turns_at(y.beta_slow)), r - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
        inv = interpolation * ramp + extrapolation * (1.0 - ramp)
        factor = (y.attention_factor if y.attention_factor is not None
                  else 0.1 * math.log(y.factor) + 1.0)
        return r, inv.astype(np.float32), float(factor)


@dataclasses.dataclass(frozen=True)
class Experts:
    """A sparse-expert feed-forward (``fiber_tpu.ops.moe``): ``total``
    routed experts of ``width``, ``top_k`` a token (sigmoid scores,
    weights renormalised over the taken and times ``scale``), one
    shared expert of ``shared_width``; ``kind`` is every expert's form:
    ``"swiglu"`` (gated silu, three matrices) or ``"relu2"`` (ungated
    ``relu(h Wu)^2 Wd``, two). ``share = (index, shares)``:
    this program holds experts ``[index * total / shares, (index + 1) *
    total / shares)`` of an expert-parallel layer and computes their
    part of the result; ``(0, 1)`` is the whole layer. ``chunk_rows``
    sizes the dispatch's buffers (memory), never what is computed."""

    total: int
    top_k: int
    width: int
    shared_width: int
    scale: float = 1.0
    share: Tuple[int, int] = (0, 1)
    chunk_rows: int = 4096
    kind: str = "swiglu"


@dataclasses.dataclass(frozen=True)
class StateSpace:
    """A Mamba-2 mixer (``fiber_tpu.ops.ssm``): ``heads`` heads of
    ``head_dim`` with a state of ``state`` a feature, ``B`` and ``C``
    shared by the heads of each of ``groups`` groups, a causal depthwise
    convolution of ``conv`` positions over x, B and C, the scan in
    blocks of ``chunk`` positions. ``recompute`` wraps the mixer in
    ``jax.checkpoint``: the backward pass keeps the layer's input and
    recomputes the blocks' intermediates (memory, never what is
    computed). ``dt_min`` / ``dt_max`` / ``dt_floor``: ``init`` draws
    the step sizes log-uniformly in between and floors them."""

    heads: int
    head_dim: int
    state: int
    groups: int = 1
    conv: int = 4
    chunk: int = 128
    recompute: bool = True
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.inner + 2 * self.groups * self.state


@dataclasses.dataclass(frozen=True)
class Latent:
    """Latent attention (MLA, DeepSeek-V3 report arXiv:2412.19437
    §2.1.1): queries from a low-rank ``c_q = RMSNorm(x W_qa)`` of
    ``q_rank``, ``q = c_q W_qb`` as heads of ``nope + rope_dim``; keys
    and values from one ``[c_kv ; k_pe] = x W_kva`` of ``kv_rank +
    rope_dim``, ``[k_nope ; v] = RMSNorm(c_kv) W_kvb`` as heads of
    ``nope + v_dim``; the rope turns each head's last ``rope_dim``
    features of q and the one ``k_pe`` that all heads share; a head
    attends over ``qk_dim = nope + rope_dim`` features at scale
    ``qk_dim^-0.5`` and returns ``v_dim``, which the out-projection
    reads, ``heads x v_dim`` wide."""

    q_rank: int
    kv_rank: int
    nope: int
    rope_dim: int
    v_dim: int

    @property
    def qk_dim(self) -> int:
        return self.nope + self.rope_dim

    @property
    def label(self) -> str:
        """What the ``lm.train_step`` span says of it."""
        return (f"q{self.q_rank}/kv{self.kv_rank}/qk{self.nope}+"
                f"{self.rope_dim}/v{self.v_dim}")


@dataclasses.dataclass(frozen=True)
class Block:
    """One layer: a mixer, a feed-forward, or one of the two alone, each
    part with its own RMSNorm and residual. ``mixer="attention"``:
    ``heads`` query heads (of the model's ``head_dim``, grouped over its
    ``kv_heads``), causal attention over the last ``window`` positions
    (None = all), its ``rope`` (None with a learned position table or
    with no position scheme at all); ``"latent"``: latent attention
    ``latent=`` (:class:`Latent`) with ``heads`` heads of its own widths
    and its ``rope``, causal over all positions; ``"ssm"``: the
    state-space mixer ``ssm=``; None: no mixer. The feed-forward:
    ``ffn="mlp"`` (ungated tanh-GELU of ``width`` with biases),
    ``"gated"`` (SwiGLU of ``width``, no biases), ``"experts"``
    (``experts=``) or None.
    ``post_norm``: a second RMSNorm, with a gain of its own, behind each
    part (a sandwich): ``x + norm(part(norm(x)))``."""

    heads: int = 0
    window: Optional[int] = None
    rope: Optional[Rope] = Rope()
    ffn: Optional[str] = "mlp"
    width: int = 0
    experts: Optional[Experts] = None
    mixer: Optional[str] = "attention"
    ssm: Optional[StateSpace] = None
    post_norm: bool = False
    latent: Optional[Latent] = None

    @property
    def attn_kind(self) -> str:
        if self.mixer == "latent":
            return "latent"
        return "window" if self.window is not None else "full"

    @property
    def attends(self) -> bool:
        """Whether the mixer is attention of either kind."""
        return self.mixer in ("attention", "latent")

    @property
    def kind(self) -> str:
        mixer = (self.attn_kind if self.attends
                 else {"ssm": "ssm"}.get(self.mixer))
        return "/".join(part for part in (mixer, self.ffn) if part)


@dataclasses.dataclass(frozen=True)
class ExitGate:
    """The exit gate of a model whose layers run ``passes`` times
    (arXiv:2510.25741): a linear map with bias from each pass's normed
    rows to one number, ``lambda_t = sigmoid(x_t w + b)``; a position
    exits after pass ``t`` with probability ``p_t = lambda_t prod_(j<t)
    (1 - lambda_j)``, after the last with what is left. ``loss`` is then
    the expected-exit loss, per position ``sum_t p_t CE_t - beta H(p)``
    (``H`` the entropy of ``p``: a uniform prior over the exits)."""

    beta: float = 0.05


@dataclasses.dataclass(frozen=True)
class MTP:
    """Multi-token prediction (DeepSeek-V3 report §2.2): a module that
    predicts the token after next. Row ``i`` of the main stack, before
    the final norm, and the embedding of token ``i + 1`` (zero for the
    last row) each pass an RMSNorm of their own, are joined
    (embedding first) and projected back to the model's width by
    ``eh_proj``; one whole layer (``block``) runs over the result,
    causal, with the main model's ropes; a norm of its own and the
    main model's head give the logits of token ``i + 2``. The loss
    adds ``weight`` times the mean of their cross-entropy over rows
    ``0 .. S-3``. ``depth``: modules chained (1 is what is
    implemented). Training only: ``apply`` and ``generate`` are the
    main model's."""

    block: Block
    depth: int = 1
    weight: float = 0.3


class BlockLM:
    """Causal LM built from a description of its layers (``blocks``, a
    sequence of :class:`Block`): per layer its own mixer (attention
    with its query-head count, window and rope, or a state-space mixer)
    and feed-forward kind, or one part alone; RMSNorm (``norm_eps``)
    before each part, no attention bias, an untied head. ``attention``,
    ``mesh`` and ``interpret`` are :class:`TinyLM`'s (which is the
    uniform description); a window needs the flash plane. ``pos``:
    ``"rope"`` (every attention block has a rope), ``"learned"`` (a
    position table, no ropes) or ``"none"`` (no position scheme at all:
    the state-space layers carry position). A state-space layer runs on
    one device and over whole chunks of positions.

    ``passes``: how often the stack of layers runs over the same weights
    (1 = once, and then nothing else changes). Pass ``t`` starts from
    pass ``t - 1``'s rows after the final norm, which closes every pass;
    the passes are one ``lax.scan`` whose body holds the layers once, so
    a shared weight's gradient is the sum over the passes'. ``apply``
    and ``generate`` give the last pass's logits (decode keeps a cache
    per pass and layer: a pass attends its own keys and values). With an
    ``exit_gate`` (:class:`ExitGate`; needs ``passes > 1``) ``loss`` is
    the expected-exit loss over every pass's logits, else the last
    pass's cross-entropy; ``pass_losses`` gives the parts. Attention
    layers with dense feed-forwards only, on one device.

    What a step recomputes (memory, never what is computed):
    ``recompute="layer"`` puts each application of a layer under
    ``jax.checkpoint``, so that the backward pass keeps a layer's input
    and, where the layer calls the flash kernels, their forward output
    (S, heads, head_dim) and row statistics (heads, S), which
    ``ops/pallas_attention.py`` names for the checkpoint's policy.
    Everything else of the layer (norms, projections, ropes, the
    feed-forward) is recomputed from the input; the forward kernel is
    not run again, the kept output being what it would write.
    ``head_block`` computes head and cross-entropy over blocks of that
    many rows (all passes' rows alike), each block recomputed in the
    backward pass, so that no (rows, vocab) array outlives its block
    (None: the logits whole).

    ``mtp`` (:class:`MTP`): a multi-token-prediction module whose term
    ``loss`` adds (through the blocked head too); with it, or with a
    latent layer, the model trains on one device, once over its stack,
    with the flash kernels or the reference attention, and does not
    decode.

    ``apply`` / ``loss`` / ``generate`` as :class:`TinyLM`;
    ``routing(params, tokens)`` gives, for the expert layers (the MTP
    module's last), the taken expert ids of every token and the load of
    each held expert."""

    def __init__(self, blocks: Sequence[Block], *, vocab: int, dim: int,
                 head_dim: int, kv_heads: int, max_seq: int,
                 attention: str = "flash", pos: str = "rope", mesh=None,
                 interpret: bool = False, norm_eps: float = 1e-6,
                 passes: int = 1, exit_gate: Optional[ExitGate] = None,
                 recompute: Optional[str] = None,
                 head_block: Optional[int] = None,
                 mtp: Optional[MTP] = None) -> None:
        blocks = tuple(blocks)
        every = blocks + ((mtp.block,) if mtp is not None else ())
        if passes < 1:
            raise ValueError(f"passes must be >= 1, got {passes}")
        if exit_gate is not None and passes == 1:
            raise ValueError(
                "an exit gate chooses among passes: with passes=1 there "
                "is one exit; drop the gate or give the model passes")
        if recompute not in (None, "layer"):
            raise ValueError(f"unknown recomputation {recompute!r}")
        if head_block is not None and head_block < 1:
            raise ValueError(f"head_block must be >= 1, got {head_block}")
        if passes > 1 and any(b.ffn == "experts" or b.mixer == "ssm"
                              for b in blocks):
            raise ValueError(
                "passes > 1 runs attention layers with dense "
                "feed-forwards: an expert layer's routing and a "
                "state-space layer's carried state have no pass")
        if attention not in ("ring", "ulysses", "flash", "reference"):
            raise ValueError(f"unknown attention {attention!r}")
        if mtp is not None and mtp.depth != 1:
            raise ValueError(
                f"an MTP of depth {mtp.depth}: one module (DeepSeek-V3's "
                "D = 1) is what is implemented")
        # latent layers and an MTP module train on one device, once over
        # the stack, and do not decode yet
        latent_or_mtp = (mtp is not None
                         or any(b.mixer == "latent" for b in every))
        if latent_or_mtp:
            what = "an MTP module" if mtp is not None else "a latent layer"
            if passes > 1:
                raise ValueError(
                    f"{what} runs once over the stack: passes > 1 has no "
                    "second stream for it")
            if attention not in ("flash", "reference"):
                raise ValueError(
                    f"{what} attends through the flash kernels or the "
                    f"reference, not {attention!r}: the sequence-parallel "
                    "planes have no value width of its own")
        if pos not in ("learned", "rope", "none"):
            raise ValueError(f"unknown positional scheme {pos!r}")
        if kv_heads < 1:
            raise ValueError(f"kv_heads must be >= 1, got {kv_heads}")
        for b in every:
            if b.mixer not in ("attention", "latent", "ssm", None):
                raise ValueError(f"unknown mixer {b.mixer!r}")
            if (b.mixer == "latent") != (b.latent is not None):
                raise ValueError(
                    "mixer='latent' comes with latent=, and no other does")
            if b.mixer is None and b.ffn is None:
                raise ValueError(
                    "a layer with no part: give the block a mixer or a "
                    "feed-forward")
            if (b.mixer == "ssm") != (b.ssm is not None):
                raise ValueError(
                    "mixer='ssm' comes with ssm=, and no other does")
            if b.mixer == "ssm":
                if b.ssm.heads % b.ssm.groups:
                    raise ValueError(
                        f"{b.ssm.heads} state-space heads do not divide "
                        f"into {b.ssm.groups} groups")
                if max_seq % b.ssm.chunk:
                    raise ValueError(
                        f"a sequence of {max_seq} positions is not whole "
                        f"chunks of {b.ssm.chunk}")
            if b.attends:
                self._check_attention(b, kv_heads, head_dim, pos, attention)
            if b.ffn not in ("mlp", "gated", "experts", None):
                raise ValueError(f"unknown feed-forward {b.ffn!r}")
            if (b.ffn == "experts") != (b.experts is not None):
                raise ValueError(
                    "ffn='experts' comes with experts=, and no other does")
            if b.ffn == "experts":
                from fiber_tpu.ops.moe import EXPERT_MATRICES, held_experts

                if b.experts.kind not in EXPERT_MATRICES:
                    raise ValueError(
                        f"unknown expert kind {b.experts.kind!r}")
                held_experts(b.experts.total, b.experts.share)
            elif b.ffn is not None and b.width < 1:
                raise ValueError(f"feed-forward width {b.width}")
        self._flash_multi = False
        attends = any(b.attends for b in every)
        if mesh is not None:
            import numpy as np

            multi = int(np.prod(list(mesh.shape.values()))) > 1
            if multi and any(b.mixer == "ssm" for b in blocks):
                raise ValueError(
                    "a state-space layer runs on one device: the "
                    "sequence-parallel plane hands keys and values on, "
                    "not state; drop the mesh")
            if multi and (passes > 1 or head_block is not None):
                raise ValueError(
                    "passes > 1 and head_block run on one device: the "
                    "sequence-parallel plane orders the rows and "
                    "carries no pass; drop the mesh")
            if multi and latent_or_mtp:
                raise ValueError(
                    "latent layers and an MTP module run on one device: "
                    "the ring has no value width of its own and no "
                    "shifted stream; drop the mesh")
            if multi and "pool" not in mesh.shape:
                # Loud, at construction: the sequence-parallel planes
                # shard over the mesh's "pool" axis — without this
                # check the mistake surfaces as a KeyError deep inside
                # the first apply().
                raise ValueError(
                    "multi-device TinyLM needs a mesh with a 'pool' "
                    f"axis; got axes {tuple(mesh.shape)}")
            if multi and attention == "flash":
                # Multi-device flash = ring attention with the Pallas
                # kernel as the per-device block: the sequence shards
                # over the mesh AND every rotation streams scores
                # through VMEM (ring_attention local="flash").
                self._flash_multi = True
        if self._flash_multi and any(b.window is not None for b in blocks):
            raise ValueError(
                "window= is single-device (a windowed partial's lse "
                "is not ring-mergeable); drop the mesh or the window")
        if attention == "flash" and attends and not interpret:
            import jax

            platform = (mesh.devices.flat[0] if mesh is not None
                        else jax.devices()[0]).platform
            if platform != "tpu":
                raise ValueError(
                    "attention='flash' compiles Pallas kernels through "
                    f"Mosaic and needs a TPU (platform is {platform!r}); "
                    "pass interpret=True to run them in the Pallas "
                    "interpreter instead")
        self.blocks = blocks
        self.vocab = vocab
        self.dim = dim
        # kv_heads < heads is grouped-query attention: the flash plane
        # reads the small KV natively (kernel index maps share KV
        # blocks across each query group); the XLA planes broadcast KV
        # to full heads at attend time (compute identical, memory not
        # saved there — GQA's KV-cache/HBM win is a kernel property).
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.layers = len(blocks)
        self.max_seq = max_seq
        self.attention = attention
        # "learned": absolute position table added to embeddings.
        # "rope": rotary embeddings applied to q/k per attention layer
        # (relative positions; the modern long-context default — decays
        # gracefully past training lengths where a learned table ends).
        # "none": neither (state-space layers carry position).
        self.pos = pos
        #: the epsilon of every RMSNorm (the group norm of a state-space
        #: mixer too)
        self.norm_eps = norm_eps
        #: flash plane only: run the kernels in the Pallas interpreter
        self.interpret = interpret
        #: how often the stack runs over the same weights
        self.passes = passes
        self.exit_gate = exit_gate
        #: None, or "layer": every layer application under jax.checkpoint,
        #: which keeps the flash kernel's output and row statistics
        self.recompute = recompute
        #: rows of a block of the head and its cross-entropy (None: whole)
        self.head_block = head_block
        #: the multi-token-prediction module, or None
        self.mtp = mtp
        #: the layers of the stack and, last, the MTP module's
        self._every = every
        self._latent_or_mtp = latent_or_mtp
        self._mesh = mesh
        self._probe = None

    @staticmethod
    def _check_attention(b, kv_heads, head_dim, pos, attention):
        """Refuse an attention block the model cannot run."""
        if b.mixer == "latent":
            if b.heads < 1:
                raise ValueError(f"a latent layer of {b.heads} heads")
            if b.window is not None:
                raise ValueError(
                    "a latent layer attends over all positions: the "
                    "window is not implemented for it")
            if b.rope is None:
                raise ValueError(
                    "a latent layer's shared key is its rope: give it one")
        elif b.heads < 1 or b.heads % kv_heads:
            raise ValueError(
                f"heads {b.heads} not divisible by kv_heads {kv_heads}")
        if (pos == "rope") != (b.rope is not None):
            raise ValueError(
                "pos='rope' gives every block a rope and "
                "pos='learned' or 'none' none")
        if b.rope is not None:
            b.rope.table(BlockLM._rope_width(b, head_dim))
        if b.window is not None:
            if b.window < 1:
                raise ValueError(
                    f"window must be >= 1, got {b.window}")
            if attention != "flash":
                # The window lives in the flash kernels' block-skip
                # grid; the XLA planes have no windowed engine and
                # silently ignoring it would train a different model.
                raise ValueError(
                    "window= needs attention='flash' (the sliding "
                    "window is a kernel feature)")

    @property
    def _loss_by_pass(self) -> bool:
        """Whether ``loss`` is formed from ``pass_losses`` (several
        passes, or a blocked head); else it lowers as it always did."""
        return self.passes > 1 or self.head_block is not None

    @property
    def _recompute_label(self) -> str:
        return (self.recompute or "none") + (
            "+head" if self.head_block is not None else "")

    @property
    def _kept_label(self) -> str:
        """What the backward pass keeps of a layer application: ``all``
        that jax's AD keeps (no ``recompute``), else the layer's
        ``input`` and, where the layer calls the flash kernels itself
        (one device), their output and row statistics besides."""
        if self.recompute != "layer":
            return "all"
        kernels = (self.attention == "flash" and not self._flash_multi
                   and any(b.attends for b in self._every))
        return "input+attn_out+lse" if kernels else "input"

    @property
    def span_fields(self) -> dict:
        """What the ``lm.train_step`` span says of the model: the layer
        kinds in order; with expert layers, how many experts are held
        here, exist in all and are taken a token; where the stack runs
        more than once or a step recomputes, ``passes``, ``recompute``
        (``none``, ``layer``, with ``+head`` for a blocked head) and
        ``kept`` (what the backward pass keeps of a layer application:
        ``all``, ``input``, ``input+attn_out+lse``); with latent layers
        ``latent``, their widths (``q1536/kv512/qk128+64/v128``); with an
        MTP module ``mtp``, its depth and weight (``1/0.3``)."""
        fields = {"layers": ",".join(b.kind for b in self.blocks)}
        if self.passes > 1 or self._recompute_label != "none":
            fields.update(passes=self.passes,
                          recompute=self._recompute_label,
                          kept=self._kept_label)
        latent = [b.latent for b in self._every if b.latent is not None]
        if latent:
            fields["latent"] = latent[0].label
        if self.mtp is not None:
            fields["mtp"] = f"{self.mtp.depth}/{self.mtp.weight:g}"
        experts = [b.experts for b in self._every if b.experts is not None]
        if experts:
            from fiber_tpu.ops.moe import held_experts

            e = experts[0]
            fields.update(experts_held=held_experts(e.total, e.share)[1],
                          experts_total=e.total, top_k=e.top_k)
        return fields

    # ------------------------------------------------------------------
    def init(self, key) -> dict:
        """Weights 0.02 * normal, gains 1, biases 0. The stream: split
        the key in four (embed, pos, out, rest); per layer split
        ``rest`` in seven: 0 wq (or wqkv), 1 wo, 2 w1 / wg, 3 w2 / wd,
        4 wkv, 5 wu (gated) or, split in seven again, the expert
        layer's router, shared wg / wu / wd, held experts' wg / wu / wd
        (each one draw of the stacked shape; an ungated expert has no
        wg and skips its draws), 6 rest. A state-space mixer splits 0 in
        five: in_proj, conv_w, conv_b (uniform in +-conv^-0.5), the step
        sizes (``dt_bias`` is the inverse softplus of ``dt`` drawn
        log-uniformly in [dt_min, dt_max], floored at dt_floor),
        out_proj; ``A_log = log(1..heads)``, ``D = 1``. A latent mixer
        splits 0 in two (``wq_a``, ``wq_b``) and 4 in two (``wkv_a``,
        ``wkv_b``); 1 is its ``wo``; its ``q_norm`` and ``kv_norm`` are
        gains. A part the layer
        does not have draws nothing and has no leaf (``norm1`` is the
        mixer's gain, ``norm2`` the feed-forward's; with ``post_norm``
        ``post_norm1`` and ``post_norm2`` are the gains behind them,
        and a gain draws nothing). An exit gate's ``gate_w`` is drawn
        from the rest the last layer leaves; ``gate_b`` is 0. An MTP
        module (``params["mtp"]``) splits that rest in two: ``eh_proj``
        (2 dim, dim) from the first, its layer's seven keys from the
        second as any layer's; ``enorm``, ``hnorm`` and ``norm`` are
        gains."""
        import jax
        import jax.numpy as jnp

        k_emb, k_pos, k_out, key = jax.random.split(key, 4)
        scale = 0.02
        params = {
            "embed": scale * jax.random.normal(
                k_emb, (self.vocab, self.dim)),
            "out": scale * jax.random.normal(
                k_out, (self.dim, self.vocab)),
            "final_norm": jnp.ones((self.dim,)),
            "blocks": [],
        }
        if self.pos == "learned":
            params["pos"] = scale * jax.random.normal(
                k_pos, (self.max_seq, self.dim))

        def normal(k, *shape):
            return scale * jax.random.normal(k, shape)

        for spec in self.blocks:
            keys = jax.random.split(key, 7)
            key = keys[6]
            params["blocks"].append(self._init_block(spec, keys, normal))
        if self.exit_gate is not None:
            params["gate_w"] = normal(key, self.dim)
            params["gate_b"] = jnp.zeros(())
        if self.mtp is not None:
            k_eh, k_blk = jax.random.split(key)
            d = self.dim
            params["mtp"] = {
                "enorm": jnp.ones((d,)), "hnorm": jnp.ones((d,)),
                "eh_proj": normal(k_eh, 2 * d, d), "norm": jnp.ones((d,)),
                "block": self._init_block(
                    self.mtp.block, jax.random.split(k_blk, 7), normal)}
        return params

    def _init_block(self, spec, keys, normal) -> dict:
        """One layer's leaves from its seven keys (``init`` says the
        stream)."""
        import jax
        import jax.numpy as jnp

        d, q_dim = self.dim, spec.heads * self.head_dim
        blk = {}
        if spec.mixer == "latent":
            a, h = spec.latent, spec.heads
            k_qa, k_qb = jax.random.split(keys[0])
            k_kva, k_kvb = jax.random.split(keys[4])
            blk.update(
                norm1=jnp.ones((d,)),
                wq_a=normal(k_qa, d, a.q_rank),
                q_norm=jnp.ones((a.q_rank,)),
                wq_b=normal(k_qb, a.q_rank, h * a.qk_dim),
                wkv_a=normal(k_kva, d, a.kv_rank + a.rope_dim),
                kv_norm=jnp.ones((a.kv_rank,)),
                wkv_b=normal(k_kvb, a.kv_rank, h * (a.nope + a.v_dim)),
                wo=normal(keys[1], h * a.v_dim, d))
        elif spec.mixer == "attention":
            blk.update(norm1=jnp.ones((d,)),
                       wo=normal(keys[1], q_dim, d))
            if self.kv_heads == spec.heads:
                blk["wqkv"] = normal(keys[0], d, 3 * q_dim)
            else:
                kv_dim = self.kv_heads * self.head_dim
                blk["wq"] = normal(keys[0], d, q_dim)
                blk["wkv"] = normal(keys[4], d, 2 * kv_dim)
        elif spec.mixer == "ssm":
            blk.update(norm1=jnp.ones((d,)),
                       **self._init_ssm(spec.ssm, keys[0], normal))
        if spec.ffn is not None:
            blk["norm2"] = jnp.ones((d,))
        if spec.post_norm:
            for part, n in ((spec.mixer, "1"), (spec.ffn, "2")):
                if part is not None:
                    blk["post_norm" + n] = jnp.ones((d,))
        if spec.ffn == "mlp":
            h = spec.width
            blk.update(w1=normal(keys[2], d, h), b1=jnp.zeros((h,)),
                       w2=normal(keys[3], h, d), b2=jnp.zeros((d,)))
        elif spec.ffn == "gated":
            h = spec.width
            blk.update(wg=normal(keys[2], d, h),
                       wd=normal(keys[3], h, d),
                       wu=normal(keys[5], d, h))
        elif spec.ffn == "experts":
            from fiber_tpu.ops.moe import EXPERT_MATRICES, held_experts

            e = spec.experts
            held = held_experts(e.total, e.share)[1]
            sub = jax.random.split(keys[5], 7)

            def matrix(k, m, width, *lead):
                return normal(k, *lead, *((width, d) if m == "wd"
                                          else (d, width)))

            blk["router"] = normal(sub[0], d, e.total)
            for i, m in enumerate(("wg", "wu", "wd")):
                if m in EXPERT_MATRICES[e.kind]:
                    blk["shared_" + m] = matrix(sub[1 + i], m,
                                                e.shared_width)
                    blk["experts_" + m] = matrix(sub[4 + i], m,
                                                 e.width, held)
        return blk

    def _init_ssm(self, ssm, key, normal) -> dict:
        """A state-space mixer's leaves (``init`` says the stream)."""
        import math

        import jax
        import jax.numpy as jnp

        k_in, k_w, k_b, k_dt, k_out = jax.random.split(key, 5)
        bound = ssm.conv ** -0.5
        dt = jnp.exp(jax.random.uniform(
            k_dt, (ssm.heads,), minval=math.log(ssm.dt_min),
            maxval=math.log(ssm.dt_max)))
        dt = jnp.maximum(dt, ssm.dt_floor)
        return {
            "in_proj": normal(k_in, self.dim,
                              ssm.inner + ssm.conv_dim + ssm.heads),
            "conv_w": jax.random.uniform(
                k_w, (ssm.conv_dim, ssm.conv), minval=-bound, maxval=bound),
            "conv_b": jax.random.uniform(
                k_b, (ssm.conv_dim,), minval=-bound, maxval=bound),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "A_log": jnp.log(jnp.arange(1.0, ssm.heads + 1.0)),
            "D": jnp.ones((ssm.heads,)),
            "ssm_norm": jnp.ones((ssm.inner,)),
            "out_proj": normal(k_out, ssm.inner, self.dim),
        }

    # ------------------------------------------------------------------
    def _attend(self, q, k, v, window=None):
        if k.shape[1] != q.shape[1] and self.attention != "flash":
            # GQA on the XLA planes: broadcast KV to full heads (repeat
            # order matches the kernel's ih // group sharing). Only the
            # flash kernels read the small KV natively.
            import jax.numpy as jnp

            reps = q.shape[1] // k.shape[1]
            k = jnp.repeat(k, reps, axis=1)
            v = jnp.repeat(v, reps, axis=1)
        if self.attention == "reference":
            from fiber_tpu.ops.ring_attention import reference_attention

            return reference_attention(q, k, v, causal=True)
        if self.attention == "flash":
            from fiber_tpu.ops.pallas_attention import flash_attention

            if self._flash_multi:
                from fiber_tpu.ops.ring_attention import (
                    ring_attention_ordered)

                return ring_attention_ordered(
                    q, k, v, mesh=self._mesh, causal=True,
                    local="flash", interpret=self.interpret)
            return flash_attention(q, k, v, causal=True,
                                   window=window,
                                   interpret=self.interpret)
        if self.attention == "ulysses":
            from fiber_tpu.ops.ulysses_attention import ulysses_attention

            return ulysses_attention(q, k, v, mesh=self._mesh,
                                     causal=True)
        from fiber_tpu.ops.ring_attention import ring_attention_ordered

        return ring_attention_ordered(q, k, v, mesh=self._mesh,
                                      causal=True)

    def _ring_order(self):
        """The positions of the rows as ``_forward`` holds them: on the
        ring (``attention="ring"``, or flash over several chips) the
        ring's own order (``ops.ring_attention.ring_order``: zigzag for
        a length whole in 2n half-blocks, so that no chip waits under
        the causal mask), as a numpy array; None for the natural order
        (one chip, Ulysses, the reference, a length that does not
        halve). Everything but attention acts on one row at a time, so
        ordering the token ids orders the whole step at no traffic."""
        if self.attention != "ring" and not self._flash_multi:
            return None
        from fiber_tpu.ops.ring_attention import ring_order
        from fiber_tpu.parallel.mesh import default_mesh

        mesh = self._mesh or default_mesh()
        return ring_order(self.max_seq, mesh.shape["pool"], causal=True)

    def _rms(self, x, g):
        import jax.numpy as jnp

        return g * x / jnp.sqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.norm_eps)

    @staticmethod
    def _project_qkv(blk, h):
        """Pre-attention projections, flat head layout. Works on (S,
        dim) rows and single (dim,) vectors alike — SHARED by apply()
        and _decode_step() so the block structure cannot silently
        diverge between the training and decode paths."""
        import jax.numpy as jnp

        if "wqkv" in blk:
            return jnp.split(h @ blk["wqkv"], 3, axis=-1)
        q = h @ blk["wq"]
        k, v = jnp.split(h @ blk["wkv"], 2, axis=-1)
        return q, k, v

    @staticmethod
    def _rope_angles(positions, dh, base=10000.0, inv_freq=None,
                     factor=1.0):
        """cos/sin tables for rotary embeddings at ``positions``
        (scalar or (S,)): shape (..., dh/2). Frequencies ``base^(-2i /
        dh)``, or the table ``inv_freq`` (a scaled rope's); ``factor``
        multiplies both (YaRN's attention factor)."""
        import jax.numpy as jnp

        inv = (1.0 / (base ** (jnp.arange(0, dh, 2) / dh))
               if inv_freq is None else jnp.asarray(inv_freq))
        ang = jnp.asarray(positions, jnp.float32)[..., None] * inv
        if factor != 1.0:
            return factor * jnp.cos(ang), factor * jnp.sin(ang)
        return jnp.cos(ang), jnp.sin(ang)

    @staticmethod
    def _rope_width(spec, head_dim):
        """The features a block's rope acts on: the model's heads, or a
        latent layer's rope part."""
        return spec.latent.rope_dim if spec.mixer == "latent" else head_dim

    @staticmethod
    def _rope_key(spec):
        """Where ``_rope_tables`` keeps a block's table: by its rope, and
        a latent layer's by the width it turns too."""
        return ((spec.rope, spec.latent.rope_dim) if spec.mixer == "latent"
                else spec.rope)

    def _rope_tables(self, positions):
        """{``_rope_key``: (cos, sin)} for each distinct rope of the
        blocks (the MTP module's too)."""
        tables = {}
        for spec in self._every:
            key = self._rope_key(spec)
            if spec.attends and spec.rope is not None and key not in tables:
                r, inv, factor = spec.rope.table(
                    self._rope_width(spec, self.head_dim))
                tables[key] = self._rope_angles(
                    positions, r, spec.rope.base, inv, factor)
        return tables

    @staticmethod
    def _rope_rotate(x, cos, sin, interleaved=False):
        """Rotate feature pairs of the leading ``2 * cos.shape[-1]``
        features of x's last axis; the rest pass through. Pairs are the
        two halves' (``rotate_half``) or, ``interleaved``, adjacent
        features ``(2j, 2j + 1)``. cos/sin broadcast against x's leading
        axes. The result keeps x's dtype: f32 cos/sin must not silently
        promote a bf16 stream (which would also let decode's cache cast
        rotated keys back DOWN, drifting incremental decode away from
        full-apply)."""
        import jax.numpy as jnp

        r = 2 * cos.shape[-1]
        if r < x.shape[-1]:
            return jnp.concatenate(
                [BlockLM._rope_rotate(x[..., :r], cos, sin, interleaved),
                 x[..., r:]], axis=-1)
        if interleaved:
            x1, x2 = x[..., 0::2], x[..., 1::2]
            return jnp.stack(
                [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                axis=-1).reshape(x.shape).astype(x.dtype)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
            axis=-1).astype(x.dtype)

    def _ssm_parts(self, ssm, blk, x):
        """A state-space mixer's input side, on (S, dim) rows and single
        (dim,) vectors alike (shared by apply() and _decode_step() like
        _project_qkv): (gate z, the convolution's input xBC, dt before
        its bias)."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("in_proj"):
            h = self._rms(x, blk["norm1"]) @ blk["in_proj"]
            return jnp.split(h, [ssm.inner, ssm.inner + ssm.conv_dim],
                             axis=-1)

    def _ssm_scanned(self, ssm, blk, xbc, dt, scan):
        """From the convolved and activated ``xbc`` to the scan's
        result: split x, B and C, ``dt = softplus(dt + dt_bias)``, ``A =
        -exp(A_log)``, then ``scan(x, dt, A, B, C, D)`` (the chunked
        scan, or one step of the recurrence)."""
        import jax
        import jax.numpy as jnp

        lead = xbc.shape[:-1]
        x, B, C = jnp.split(
            xbc, [ssm.inner, ssm.inner + ssm.groups * ssm.state], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + blk["dt_bias"])
        return scan(x.reshape(lead + (ssm.heads, ssm.head_dim)), dt,
                    -jnp.exp(blk["A_log"].astype(jnp.float32)),
                    B.reshape(lead + (ssm.groups, ssm.state)),
                    C.reshape(lead + (ssm.groups, ssm.state)), blk["D"])

    def _ssm_out(self, ssm, blk, y, z):
        """Gate, group norm and the projection back to the stream."""
        import jax

        from fiber_tpu.ops import ssm as ops

        with jax.named_scope("gate_norm"):
            y = ops.gated_group_norm(y.reshape(z.shape), z, blk["ssm_norm"],
                                     ssm.groups, self.norm_eps)
        with jax.named_scope("out"):
            return y @ blk["out_proj"]

    def _ssm_mix(self, ssm, blk, x):
        """The state-space mixer on the stream ``x`` (S, dim) -> what it
        adds to it (S, dim): norm, in-projection, causal convolution,
        chunked scan, gated group norm, out-projection."""
        import jax
        import jax.numpy as jnp

        from fiber_tpu.ops import ssm as ops
        from fiber_tpu.telemetry import device as device_telemetry

        path = ops.scan_path(x.shape[0], ssm.heads, ssm.head_dim,
                             ssm.groups, ssm.state, ssm.chunk,
                             self.interpret)
        device_telemetry.ssm_traced(ssm.heads, ssm.state, ssm.groups,
                                    ssm.chunk, ssm.recompute, path)
        # the interpreter is asked for only where a kernel runs in it:
        # every other call of the scan is the plain form's, argument for
        # argument (a test may stand in for ``ops.ssd_scan`` under it)
        how = {"interpret": True} if (
            self.interpret and path == "kernel") else {}

        def mix(blk, x):
            z, xbc, dt = self._ssm_parts(ssm, blk, x)
            with jax.named_scope("conv"):
                # x, B and C each convolved on its own (the convolution
                # is depthwise): the scan's kernels then read three
                # arrays, not slices of one that XLA has to copy out
                cuts = [ssm.inner, ssm.inner + ssm.groups * ssm.state]
                xbc = jnp.concatenate([
                    jax.nn.silu(ops.causal_conv(v, w, b))
                    for v, w, b in zip(
                        jnp.split(xbc, cuts, axis=-1),
                        jnp.split(blk["conv_w"], cuts, axis=0),
                        jnp.split(blk["conv_b"], cuts))], axis=-1)
            with jax.named_scope("scan"):
                y = self._ssm_scanned(
                    ssm, blk, xbc, dt,
                    lambda *a: ops.ssd_scan(*a, chunk=ssm.chunk, **how))
            return self._ssm_out(ssm, blk, y, z)

        with jax.named_scope("lm.ssm"):
            return (jax.checkpoint(mix) if ssm.recompute else mix)(blk, x)

    def _block_tail(self, spec, blk, x, mixed, taps=None):
        """The mixer's residual (``mixed``: the attention's heads, flat,
        before their out-projection, or what a state-space mixer adds;
        None without a mixer) + the feed-forward, if the layer has one
        (shared like _project_qkv)."""
        import jax

        def behind(y, gain):
            """The norm behind a part, where the layer has one."""
            return self._rms(y, blk[gain]) if spec.post_norm else y

        if spec.attends:
            with jax.named_scope("lm.attn"), \
                    jax.named_scope(spec.attn_kind), jax.named_scope("out"):
                x = x + behind(mixed @ blk["wo"], "post_norm1")
        elif spec.mixer == "ssm":
            x = x + behind(mixed, "post_norm1")
        if spec.ffn is None:
            return x
        if spec.ffn == "mlp":
            with jax.named_scope("lm.mlp"):
                h = self._rms(x, blk["norm2"])
                y = jax.nn.gelu(h @ blk["w1"] + blk["b1"]) @ blk["w2"]
                if spec.post_norm:
                    return x + behind(y + blk["b2"], "post_norm2")
                return x + y + blk["b2"]
        from fiber_tpu.ops import moe

        if spec.ffn == "gated":
            with jax.named_scope("lm.mlp"):
                h = self._rms(x, blk["norm2"])
                return x + behind(
                    moe.swiglu(h, blk["wg"], blk["wu"], blk["wd"]),
                    "post_norm2")
        with jax.named_scope("lm.moe"), jax.named_scope("norm"):
            h = self._rms(x, blk["norm2"])
        e = spec.experts
        rows = h.reshape(-1, h.shape[-1])       # decode hands one vector
        y = moe.moe_ffn(
            rows, blk, total=e.total, top_k=e.top_k, scale=e.scale,
            first=moe.held_experts(e.total, e.share)[0],
            chunk_rows=e.chunk_rows, kind=e.kind, taps=taps)
        return x + behind(y.reshape(x.shape), "post_norm2")

    def apply(self, params, tokens):
        """tokens (max_seq,) int -> logits (max_seq, vocab).

        The named scopes (``lm.embed``, ``lm.attn`` with ``window`` or
        ``full`` and under it ``qkv``, ``kernel``, ``out``, or ``latent``
        and under it ``q_proj``, ``kv_proj``, ``kernel``, ``out``,
        ``lm.ssm`` with ``in_proj``, ``conv``, ``scan``, ``gate_norm``,
        ``out``, ``lm.mlp``, ``lm.moe``, ``lm.mtp``, ``lm.head_loss``) are
        metadata: every op's ``op_name`` in a profile starts with its
        phase."""
        import numpy as np

        order = self._ring_order()
        logits = self._forward(params, tokens, order=order)
        # by position again: argsort inverts the order
        return logits if order is None else logits[np.argsort(order)]

    def _forward(self, params, tokens, taps=None, order=None):
        """Logits of ``tokens`` (the last pass's). With ``order``
        (``_ring_order``) row ``r`` of everything in here, the logits
        too, is position ``order[r]``: the ids are taken in that order
        and ropes and the position table get the rows' true positions."""
        import jax

        x = self._normed_rows(params, tokens, taps, order)
        with jax.named_scope("lm.head_loss"):
            return (x if self.passes == 1 else x[-1]) @ params["out"]

    def _normed_rows(self, params, tokens, taps=None, order=None):
        """The rows the head reads: the stream after the last layer and
        the final norm, (S, dim); with ``passes > 1`` every pass's,
        (passes, S, dim): the passes are one ``lax.scan`` over the
        layers' walk, each closed by the final norm."""
        import jax

        x, ropes = self._embedded(params, tokens, order)
        if self.passes == 1:
            x = self._walk(params["blocks"], x, ropes, taps)
            with jax.named_scope("lm.head_loss"):
                return self._rms(x, params["final_norm"])

        def one_pass(x, _):
            with jax.named_scope("lm.pass"):
                x = self._rms(self._walk(params["blocks"], x, ropes, taps),
                              params["final_norm"])
            return x, x

        return jax.lax.scan(one_pass, x, None, length=self.passes)[1]

    def _embedded(self, params, tokens, order=None):
        """The stream before the first layer, (S, dim), and the ropes'
        tables at the rows' positions."""
        import jax
        import jax.numpy as jnp

        S = self.max_seq
        with jax.named_scope("lm.embed"):
            if order is not None:
                tokens = tokens[order]
            x = params["embed"][tokens]                      # (S, dim)
            if self.pos == "learned":
                x = x + (params["pos"] if order is None
                         else params["pos"][order])
            positions = jnp.arange(S) if order is None else order
            ropes = {rope: (cos[:, None, :], sin[:, None, :])  # (S, 1, r/2)
                     for rope, (cos, sin)
                     in self._rope_tables(positions).items()}
        if self.passes > 1 or self._recompute_label != "none":
            from fiber_tpu.telemetry import device as device_telemetry

            device_telemetry.passes_traced(self.passes, self.layers,
                                           self._recompute_label,
                                           self._kept_label)
        return x, ropes

    def _walk(self, blocks, x, ropes, taps=None):
        """The stream ``x`` (S, dim) through the layers once, in order."""
        for spec, blk in zip(self.blocks, blocks):
            x = self._apply_layer(spec, blk, x, ropes, taps)
        return x

    def _apply_layer(self, spec, blk, x, ropes, taps=None):
        """One application of a layer; with ``recompute="layer"`` under a
        ``jax.checkpoint`` that keeps the flash kernel's two named
        results and nothing else."""
        import jax

        from fiber_tpu.ops.pallas_attention import KEPT_NAMES

        def layer(blk, x):
            return self._layer(spec, blk, x, ropes, taps)

        if self.recompute == "layer" and taps is None:
            # (``routing`` taps the expert layers: forward only)
            keep = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)
            layer = jax.checkpoint(layer, policy=keep)
        return layer(blk, x)

    def _mtp_rows(self, params, tokens, taps=None):
        """(the rows the head reads, (S, dim); the MTP module's, (S,
        dim)): the main stack once, then the module on its stream before
        the final norm (``MTP`` says how)."""
        import jax
        import jax.numpy as jnp

        S = self.max_seq
        x, ropes = self._embedded(params, tokens)
        h = self._walk(params["blocks"], x, ropes, taps)
        with jax.named_scope("lm.head_loss"):
            rows = self._rms(h, params["final_norm"])
        m = params["mtp"]
        with jax.named_scope("lm.mtp"):
            with jax.named_scope("embed_proj"):
                # the embedding of token i + 1; the last row has none
                e = jnp.where((jnp.arange(S) < S - 1)[:, None],
                              params["embed"][jnp.roll(tokens, -1)], 0.0)
                z = jnp.concatenate(
                    [self._rms(e, m["enorm"]), self._rms(h, m["hnorm"])],
                    axis=-1) @ m["eh_proj"]
            z = self._apply_layer(self.mtp.block, m["block"], z, ropes, taps)
            with jax.named_scope("head_loss"):
                return rows, self._rms(z, m["norm"])

    def _layer(self, spec, blk, x, ropes, taps=None):
        """One layer on the stream ``x`` (S, dim)."""
        import jax

        S, Dh, KVH = self.max_seq, self.head_dim, self.kv_heads
        mixed = None
        if spec.mixer == "ssm":
            mixed = self._ssm_mix(spec.ssm, blk, x)
        elif spec.mixer == "latent":
            mixed = self._latent_mix(spec, blk, x, ropes)
        elif spec.mixer == "attention":
            with jax.named_scope("lm.attn"), \
                    jax.named_scope(spec.attn_kind):
                with jax.named_scope("qkv"):
                    h = self._rms(x, blk["norm1"])
                    q, k, v = self._project_qkv(blk, h)
                    q = q.reshape(S, spec.heads, Dh)
                    k = k.reshape(S, KVH, Dh)
                    v = v.reshape(S, KVH, Dh)
                    if spec.rope is not None:
                        turn = spec.rope.interleaved
                        q = self._rope_rotate(q, *ropes[spec.rope], turn)
                        k = self._rope_rotate(k, *ropes[spec.rope], turn)
                with jax.named_scope("kernel"):
                    mixed = self._attend(
                        q, k, v, spec.window).reshape(S, -1)
        return self._block_tail(spec, blk, x, mixed, taps)

    def _latent_mix(self, spec, blk, x, ropes):
        """Latent attention (:class:`Latent`) on the stream ``x`` (S, dim)
        -> the heads' outputs, flat (S, heads x v_dim), before their
        out-projection."""
        import jax
        import jax.numpy as jnp

        from fiber_tpu.telemetry import device as device_telemetry

        a, H, S = spec.latent, spec.heads, self.max_seq
        device_telemetry.latent_traced(H, a.q_rank, a.kv_rank, a.qk_dim,
                                       a.v_dim)
        cos, sin = ropes[self._rope_key(spec)]
        turn = spec.rope.interleaved
        with jax.named_scope("lm.attn"), jax.named_scope("latent"):
            with jax.named_scope("q_proj"):
                h = self._rms(x, blk["norm1"])
                q = (self._rms(h @ blk["wq_a"], blk["q_norm"])
                     @ blk["wq_b"]).reshape(S, H, a.qk_dim)
                q = jnp.concatenate(
                    [q[..., :a.nope],
                     self._rope_rotate(q[..., a.nope:], cos, sin, turn)],
                    axis=-1)
            with jax.named_scope("kv_proj"):
                c_kv, k_pe = jnp.split(h @ blk["wkv_a"], [a.kv_rank],
                                       axis=-1)
                kv = (self._rms(c_kv, blk["kv_norm"])
                      @ blk["wkv_b"]).reshape(S, H, a.nope + a.v_dim)
                # one rope key, shared by every head
                k_pe = self._rope_rotate(k_pe[:, None, :], cos, sin, turn)
                k = jnp.concatenate(
                    [kv[..., :a.nope],
                     jnp.broadcast_to(k_pe, (S, H, a.rope_dim))], axis=-1)
                v = kv[..., a.nope:]
            with jax.named_scope("kernel"):
                return self._attend(q, k, v).reshape(S, H * a.v_dim)

    def routing(self, params, tokens):
        """For one sequence of tokens, what the expert layers' routers
        decide: ``{"ids": (expert layers, S, top_k) int32, the experts
        each token takes (of all ``total``); "load": (expert layers,
        held) int32, the tokens each held expert gets}``. A whole
        forward pass; for set-up and checks, not for the step."""
        import jax.numpy as jnp
        import numpy as np

        taps = []
        order = self._ring_order()
        if self.mtp is not None:
            self._mtp_rows(params, tokens, taps)
        else:
            self._forward(params, tokens, taps, order=order)
        if not taps:
            raise ValueError("the model has no expert layer")
        ids = jnp.stack([ids for ids, _ in taps])
        if order is not None:
            ids = ids[:, np.argsort(order)]
        return {"ids": ids,
                "load": jnp.stack([load for _, load in taps])}

    def probe_routing(self, params, tokens) -> dict:
        """``routing`` jitted and fetched to the host (numpy arrays),
        with each held expert's load recorded in the registry's
        ``moe_expert_load_max`` / ``moe_expert_load_mean`` gauges, by
        expert layer. Host-side; call it outside a timed loop."""
        import jax

        from fiber_tpu.telemetry import device as device_telemetry

        if self._probe is None:
            self._probe = jax.jit(self.routing)
        found = jax.device_get(self._probe(params, tokens))
        device_telemetry.moe_load(found["load"])
        return found

    def loss(self, params, tokens):
        """Mean next-token cross-entropy over positions 0..S-2; with an
        exit gate the expected-exit loss, per position ``sum_t p_t CE_t
        - beta H(p)`` over the passes ``t``; with an MTP module plus its
        weight times the MTP head's mean cross-entropy against the token
        after next, over positions 0..S-3."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        if self.mtp is not None:
            from fiber_tpu.telemetry import device as device_telemetry

            device_telemetry.mtp_traced(self.mtp.depth, self.mtp.weight)
            rows, extra = self._mtp_rows(params, tokens)
            with jax.named_scope("lm.head_loss"):
                main = jnp.mean(self._head_losses(
                    rows, jnp.roll(tokens, -1), params["out"])[:-1])
            with jax.named_scope("lm.mtp"), jax.named_scope("head_loss"):
                after = jnp.mean(self._head_losses(
                    extra, jnp.roll(tokens, -2), params["out"])[:-2])
            return main + self.mtp.weight * after
        if self._loss_by_pass:
            ce, p = self.pass_losses(params, tokens)
            if p is None:
                return jnp.mean(ce[-1])
            with jax.named_scope("lm.exit_gate"):
                p = p[:, :-1]
                # p log p -> 0 with p: a pass no position leaves by
                entropy = -jnp.sum(
                    p * jnp.log(jnp.maximum(p, jnp.finfo(p.dtype).tiny)),
                    axis=0)
                return jnp.mean(jnp.sum(p * ce, axis=0)
                                - self.exit_gate.beta * entropy)
        order = self._ring_order()
        if order is not None:
            # The rows stay where the ring has them: each takes its
            # target by its true position, the row of position S-1 has
            # none and is left out; a mean over the same S-1 terms, so
            # no logits move between chips.
            S = self.max_seq
            logits = self._forward(params, tokens, order=order)
            with jax.named_scope("lm.head_loss"):
                targets = tokens[np.minimum(order + 1, S - 1)]
                picked = jnp.take_along_axis(
                    logits, targets[:, None], axis=1)[:, 0]
                losses = jax.nn.logsumexp(logits, axis=-1) - picked
                return jnp.sum(
                    jnp.where(order < S - 1, losses, 0.0)) / (S - 1)
        logits = self.apply(params, tokens)[:-1]             # (S-1, V)
        with jax.named_scope("lm.head_loss"):
            targets = tokens[1:]
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(
                jnp.take_along_axis(logp, targets[:, None], axis=1))

    def token_losses(self, params, tokens):
        """``loss`` before its mean: the next-token cross-entropy of each
        position 0..S-2, (S-1,). A whole forward pass; for checks that
        must see where in the sequence a result is wrong (the mean hides
        it), not for the step."""
        import jax
        import jax.numpy as jnp

        if self._loss_by_pass:
            return self.pass_losses(params, tokens)[0][-1]
        logits = self.apply(params, tokens)[:-1]
        with jax.named_scope("lm.head_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, tokens[1:, None], axis=1)[:, 0]

    def pass_losses(self, params, tokens):
        """What ``loss`` is formed from, pass by pass: (the next-token
        cross-entropy of each position 0..S-2 under each pass's logits,
        (passes, S-1) float32; the exit distribution of each position
        0..S-1, (passes, S) float32, which sums to 1 over the passes, or
        None without an exit gate). One pass: (1, S-1) and None."""
        import jax
        import jax.numpy as jnp

        S = self.max_seq
        x = self._normed_rows(params, tokens)
        if self.passes == 1:
            x = x[None]
        p = None
        if self.exit_gate is not None:
            with jax.named_scope("lm.exit_gate"):
                # float32 on the vector unit, not a rounded matrix
                # product: sum_t p_t = 1 rests on it
                g = jnp.sum(x.astype(jnp.float32) * params["gate_w"],
                            axis=-1) + params["gate_b"]      # (passes, S)
                # log p_t = log lambda_t + sum_(j<t) log(1 - lambda_j);
                # the last pass takes what is left
                stay = -jax.nn.softplus(g[:-1])
                before = jnp.concatenate(
                    [jnp.zeros((1, S)), jnp.cumsum(stay, axis=0)])
                leave = jnp.concatenate(
                    [-jax.nn.softplus(-g[:-1]), jnp.zeros((1, S))])
                p = jnp.exp(before + leave)
        with jax.named_scope("lm.head_loss"):
            # every pass's S rows, so that the blocks are whole; the row
            # of position S-1 has no target and is cut
            targets = jnp.tile(jnp.roll(tokens, -1), self.passes)
            ce = self._head_losses(x.reshape(-1, x.shape[-1]), targets,
                                   params["out"])
            return ce.reshape(self.passes, S)[:, :-1], p

    def _head_losses(self, rows, targets, out):
        """Cross-entropy of each of ``rows`` (N, dim) under the head
        ``out`` (dim, vocab) against ``targets`` (N,), float32 (N,). With
        ``head_block`` over blocks of that many rows (the last one
        padded), each under ``jax.checkpoint``: a block's logits live
        while it is computed, forward and backward, and no longer."""
        import jax
        import jax.numpy as jnp

        def losses(rows, targets):
            logits = (rows @ out).astype(jnp.float32)
            picked = jnp.take_along_axis(
                logits, targets[:, None], axis=1)[:, 0]
            return jax.nn.logsumexp(logits, axis=-1) - picked

        n, block = rows.shape[0], self.head_block
        if block is None or block >= n:
            return losses(rows, targets)
        pad = -n % block
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        ce = jax.lax.map(
            jax.checkpoint(lambda blk: losses(*blk)),
            (rows.reshape(-1, block, rows.shape[-1]),
             targets.reshape(-1, block)))
        return ce.reshape(-1)[:n]

    # ------------------------------------------------------------------
    # Inference: autoregressive decode with per-layer KV caches.
    # ------------------------------------------------------------------
    def _decode_step(self, params, caches, pos, tok):
        """One incremental position: returns (new_caches, logits).

        caches: per attention block {"k": (S, kv_heads, Dh), "v": same}
        — only rows [0, pos] are valid; this step writes row ``pos`` and
        attends q against the masked cache. Per state-space block
        {"conv": the convolution's last ``conv - 1`` inputs, "state":
        (heads, head_dim, state) float32}: the recurrence itself, one
        position a step (``ops.ssm.ssd_step``), not the chunked scan. A
        block without a mixer has an empty cache. With ``passes > 1``
        a list of such lists, one a pass: the vector goes through the
        stack ``passes`` times, each closed by the final norm, and pass
        ``t`` attends the keys and values pass ``t`` wrote. O(S) per
        step with static shapes (jit/scan friendly), single device —
        decode is a latency path, not a sharded-compute path.
        """
        x = params["embed"][tok]                             # (dim,)
        if self.pos == "learned":
            x = x + params["pos"][pos]
        ropes = self._rope_tables(pos)                       # (r/2,) each
        new_caches = []
        for cache in (caches if self.passes > 1 else [caches]):
            # a pass's own keys and values
            cache, x = self._decode_walk(params, cache, pos, x, ropes)
            x = self._rms(x, params["final_norm"])
            new_caches.append(cache)
        return (new_caches if self.passes > 1 else new_caches[0],
                x @ params["out"])

    def _decode_walk(self, params, caches, pos, x, ropes):
        """One position's vector ``x`` (dim,) through the layers once:
        (the layers' new caches, x)."""
        import jax
        import jax.numpy as jnp

        KVH, Dh = self.kv_heads, self.head_dim
        new_caches = []
        for spec, blk, cache in zip(self.blocks, params["blocks"], caches):
            if spec.mixer != "attention":
                mixed = None
                if spec.mixer == "ssm":
                    from fiber_tpu.ops import ssm as ops

                    z, xbc, dt = self._ssm_parts(spec.ssm, blk, x)
                    taps = jnp.concatenate([cache["conv"], xbc[None]])
                    xbc = jax.nn.silu(blk["conv_b"] + jnp.sum(
                        taps * blk["conv_w"].T, axis=0))
                    state, y = self._ssm_scanned(
                        spec.ssm, blk, xbc, dt,
                        lambda *a: ops.ssd_step(cache["state"], *a))
                    cache = {"conv": taps[1:], "state": state}
                    mixed = self._ssm_out(spec.ssm, blk, y, z)
                new_caches.append(cache)
                x = self._block_tail(spec, blk, x, mixed)
                continue
            h = self._rms(x, blk["norm1"])
            q, k, v = self._project_qkv(blk, h)
            q = q.reshape(KVH, spec.heads // KVH, Dh)
            k = k.reshape(KVH, Dh)
            if spec.rope is not None:
                # Rotate q and k at THIS position; the cache stores
                # post-rotation keys (standard RoPE decode).
                turn = spec.rope.interleaved
                q = self._rope_rotate(q, *ropes[spec.rope], turn)
                k = self._rope_rotate(k, *ropes[spec.rope], turn)
            k_cache = cache["k"].at[pos].set(k)
            v_cache = cache["v"].at[pos].set(v.reshape(KVH, Dh))
            new_caches.append({"k": k_cache, "v": v_cache})
            # (kvh, group, S) scores vs the whole cache, masked to
            # positions <= pos; f32 softmax statistics as everywhere.
            s = jnp.einsum("kgd,skd->kgs", q, k_cache,
                           preferred_element_type=jnp.float32)
            s = s / (Dh ** 0.5)
            kv_pos = jnp.arange(k_cache.shape[0])
            mask = kv_pos <= pos
            if spec.window is not None:
                # A windowed model must decode windowed, or inference
                # silently runs a different model than training.
                mask = mask & (kv_pos > pos - spec.window)
            s = jnp.where(mask[None, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            attn = jnp.einsum("kgs,skd->kgd", p.astype(v_cache.dtype),
                              v_cache, preferred_element_type=jnp.float32)
            x = self._block_tail(spec, blk, x,
                                 attn.astype(x.dtype).reshape(-1))
        return new_caches, x

    def init_caches(self, dtype) -> list:
        """Empty decode caches, one a block and, with ``passes > 1``,
        such a list a pass (``_decode_step`` says what each kind
        holds). KV caches and the convolution's inputs follow
        ``dtype`` (the params' — an f32 cache under bf16 params would
        silently double the KV-cache footprint, the very memory GQA
        exists to save); a state-space layer's state is float32."""
        import jax.numpy as jnp

        if self._latent_or_mtp:
            raise ValueError(
                "latent layers and an MTP module do not decode yet: there "
                "is no latent cache (c_kv and the rope key) and no draft "
                "step (ROADMAP.md)")
        S, KVH, Dh = self.max_seq, self.kv_heads, self.head_dim
        caches = []
        for spec in self.blocks:
            if spec.mixer == "attention":
                caches.append({"k": jnp.zeros((S, KVH, Dh), dtype),
                               "v": jnp.zeros((S, KVH, Dh), dtype)})
            elif spec.mixer == "ssm":
                m = spec.ssm
                caches.append({
                    "conv": jnp.zeros((m.conv - 1, m.conv_dim), dtype),
                    "state": jnp.zeros((m.heads, m.head_dim, m.state),
                                       jnp.float32)})
            else:
                caches.append({})
        if self.passes == 1:
            return caches
        # (arrays are immutable: the passes' lists may share the zeros)
        return [list(caches) for _ in range(self.passes)]

    def generate(self, params, prompt, steps: int, key=None,
                 temperature: float = 0.0):
        """Decode ``steps`` tokens after ``prompt`` (1-D int array).
        Greedy at temperature 0 (default); otherwise samples with
        ``key``. Returns the (len(prompt) + steps,) token array. The
        whole prefill + decode runs as two ``lax.scan``s over the
        cached single-position step — one compiled program, no
        per-token dispatch. len(prompt) + steps must be <= max_seq."""
        import jax
        import jax.numpy as jnp

        prompt = jnp.asarray(prompt, jnp.int32)
        n_prompt = int(prompt.shape[0])
        if n_prompt < 1:
            raise ValueError("prompt must have at least one token")
        if n_prompt + steps > self.max_seq:
            raise ValueError(
                f"prompt ({n_prompt}) + steps ({steps}) exceeds "
                f"max_seq ({self.max_seq})")
        if temperature > 0.0 and key is None:
            raise ValueError("sampling (temperature > 0) needs a key")
        key = key if key is not None else jax.random.PRNGKey(0)

        caches = self.init_caches(params["embed"].dtype)

        def prefill(carry, inp):
            caches = carry
            pos, tok = inp
            caches, logits = self._decode_step(params, caches, pos, tok)
            return caches, logits

        caches, logits_seq = jax.lax.scan(
            prefill, caches, (jnp.arange(n_prompt), prompt))

        def pick(logits, k):
            if temperature > 0.0:
                return jax.random.categorical(k, logits / temperature)
            return jnp.argmax(logits).astype(jnp.int32)

        def decode(carry, pos):
            caches, tok, k = carry
            k, k_step = jax.random.split(k)
            caches, logits = self._decode_step(params, caches, pos, tok)
            nxt = pick(logits, k_step).astype(jnp.int32)
            return (caches, nxt, k), nxt

        key, k_first = jax.random.split(key)  # use-once key discipline
        first = pick(logits_seq[-1], k_first).astype(jnp.int32)
        if steps <= 1:
            out = first[None][:steps]
        else:
            (_, _, _), rest = jax.lax.scan(
                decode, (caches, first, key),
                jnp.arange(n_prompt, n_prompt + steps - 1))
            out = jnp.concatenate([first[None], rest])
        return jnp.concatenate([prompt, out])


class TinyLM(BlockLM):
    """Causal byte/token LM. ``attention`` picks the plane:
    ``"ring"`` (sequence sharded via ppermute ring + online softmax),
    ``"ulysses"`` (all-to-all head/seq swap; needs
    ``heads % n_devices == 0``), ``"flash"`` (the Pallas
    flash-attention kernels, forward AND backward — single device runs
    them directly with the whole sequence in HBM and scores streamed
    through VMEM; pass a multi-device ``mesh=`` and the sequence
    shards over the ring with the kernel as every rotation's
    per-device block), or
    ``"reference"`` (full score matrix, single device — for parity
    tests).

    The flash kernels compile through Mosaic and need a TPU; on any
    other platform ``attention="flash"`` is refused at construction
    unless ``interpret=True`` asks for the Pallas interpreter (slow,
    for CPU tests). The library never picks the interpreter itself.

    ``pos`` picks the positional scheme: ``"learned"`` (absolute
    table, the default) or ``"rope"`` (rotary embeddings on q/k per
    layer — relative positions, the modern long-context choice; no
    position table in the params).

    ``apply(params, tokens (S,)) -> (S, vocab)`` logits;
    ``loss(params, tokens)`` is mean next-token cross-entropy.
    ``S`` must equal ``max_seq`` (static shapes; pad shorter text).
    """

    def __init__(
        self,
        vocab: int = 256,
        dim: int = 64,
        heads: int = 8,
        layers: int = 2,
        max_seq: int = 256,
        mlp_mult: int = 4,
        mesh=None,
        attention: str = "ring",
        kv_heads: Optional[int] = None,
        pos: str = "learned",
        window: Optional[int] = None,
        interpret: bool = False,
    ) -> None:
        if dim % heads:
            raise ValueError(f"dim {dim} not divisible by heads {heads}")
        if pos == "rope" and (dim // heads) % 2:
            raise ValueError("rope needs an even head_dim")
        if kv_heads is not None and kv_heads < 1:
            # 0 must not silently mean "full MHA" (a GQA A/B would
            # quietly measure nothing) and negatives pass Python's
            # modulo only to crash deep inside init().
            raise ValueError(f"kv_heads must be >= 1, got {kv_heads}")
        kv_heads = kv_heads or heads
        block = Block(heads=heads, window=window,
                      rope=Rope() if pos == "rope" else None,
                      ffn="mlp", width=mlp_mult * dim)
        super().__init__([block] * layers, vocab=vocab, dim=dim,
                         head_dim=dim // heads, kv_heads=kv_heads,
                         max_seq=max_seq, attention=attention, pos=pos,
                         mesh=mesh, interpret=interpret)
        self.heads = heads
        self.mlp_mult = mlp_mult
        #: causal sliding window (flash plane only; None = full causal)
        self.window = window


def make_train_step(model: BlockLM, optimizer, batched: bool = False,
                    donate: bool = False):
    """(params, opt_state, tokens) -> (params, opt_state, loss), jitted.
    ``optimizer`` is any optax-style (init, update) pair. With
    ``batched=True`` tokens is (B, max_seq) and the loss is the batch
    mean — the batch axis vmaps straight over the sequence-sharded
    attention (each sequence still spans the mesh). With
    ``donate=True`` the step donates ``params`` and ``opt_state``: the
    new state is written over the old one (which the caller must not
    touch again), so one copy of the state is live in a step, not two.

    What comes back is a plain function around the jitted step: each
    call is one ``lm.train_step`` span (docs/observability.md; compile
    spans hang from it, device idle time is charged to it) and moves
    the ``device_steps`` / ``device_step_units`` counters by one call
    and the argument's token count; the span also carries the model's
    ``span_fields`` (layer kinds, the expert share). It keeps the jitted
    step's ``lower`` and ``__name__``."""
    import math

    import jax

    from fiber_tpu.telemetry import device as device_telemetry
    from fiber_tpu.utils.jaxcompat import ensure_compile_cache

    ensure_compile_cache()
    if batched and any(b.ffn == "experts" for b in model.blocks):
        # jax.lax.ragged_dot has no batching rule over its rows; without
        # this check the mistake surfaces as a NotImplementedError deep
        # inside the first trace.
        raise ValueError(
            "batched=True cannot vmap over an expert layer's dispatch; "
            "give a model with expert layers one sequence a step")
    if batched:
        def loss_fn(params, tokens):
            import jax.numpy as jnp

            return jnp.mean(
                jax.vmap(lambda t: model.loss(params, t))(tokens))
    else:
        loss_fn = model.loss

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        with jax.named_scope("lm.optimizer"):
            updates, opt_state = optimizer.update(
                grads, opt_state, params)
            # Plain tree-map instead of optax.apply_updates: the
            # optimizer only needs the (init, update) protocol — no hard
            # optax dependency in the library (it isn't in
            # install_requires).
            params = jax.tree_util.tree_map(
                lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    fields = model.span_fields

    def spanned(call):
        def run(params, opt_state, tokens):
            n = math.prod(tokens.shape)
            with device_telemetry.step("lm.train_step", n, tokens=n,
                                       **fields):
                return call(params, opt_state, tokens)

        run.__name__ = call.__name__
        run.lower = jitted.lower
        return run

    if _needs_cpu_collective_serialization(model):
        # XLA CPU's in-process collectives can DEADLOCK when jax's
        # async dispatch interleaves two step-generations over the CPU
        # client's fixed thread pool: step k+1's per-device programs
        # park in their first rendezvous on threads step k's last
        # rendezvous still needs (core-dump-verified on a 1-core dev
        # box). Serializing steps on a CPU mesh closes the window and
        # costs nothing measurable there (compute-bound); real TPU
        # keeps full async dispatch.
        def step_sync(params, opt_state, tokens):
            out = jitted(params, opt_state, tokens)
            jax.block_until_ready(out)
            return out

        return spanned(step_sync)
    return spanned(jitted)


def _needs_cpu_collective_serialization(model) -> bool:
    """True when training steps run collectives across >1 virtual CPU
    device — the configuration where pipelined generations can
    deadlock XLA's in-process rendezvous (see make_train_step). The
    EFFECTIVE mesh matters: with ``mesh=None`` the ring/ulysses planes
    resolve the process-wide default mesh (all devices) at attend
    time, so a bare ``TinyLM(attention="ring")`` still runs 8-device
    collectives on the virtual CPU plane."""
    from fiber_tpu.parallel.mesh import default_mesh, is_multidevice_cpu

    mesh = getattr(model, "_mesh", None)
    if mesh is None and getattr(model, "attention", "") in (
            "ring", "ulysses"):
        mesh = default_mesh()
    return is_multidevice_cpu(mesh)
