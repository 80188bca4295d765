"""Model architecture configs.

The engine is first-party (the reference delegates model math to
vLLM/SGLang/TRT-LLM; here it is ours — SURVEY.md §7). One config dataclass
covers the dense Llama family (3-8B/70B), MoE (DeepSeek/gpt-oss-style), and
the tiny CPU-testable presets that fill the llama.cpp role in the
reference's zero-GPU test path (reference: lib/engines/llamacpp).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from functools import cached_property
from pathlib import Path
from typing import NamedTuple

import numpy as np


class Mixer(NamedTuple):
    """One sub-layer, ``h + mixer(norm(h))``, and where its operands lie.
    The mixers of one layer that share a stack share the place."""
    #: "*" attention, "-" a dense FFN, "E" routed experts, "M" Mamba-2: the
    #: letters of NemotronH's ``hybrid_override_pattern``. SambaY's three
    #: (``decoder_layout``): "S" a Mamba-1 mixer, "X" attention over another
    #: layer's keys and values (a query projection alone, nothing written),
    #: "G" a gated memory unit, which gates the step's memory
    kind: str
    #: the group of ``params["layers"]`` that holds its leaves: "lead" (the
    #: ``lead_`` leaves), "rep" (the repeated group) or, in a hybrid pattern,
    #: its kind's own stack
    stack: str
    #: its index in that stack; a routed mixer's place in the experts'
    #: stack and a recurrent one's layer of the state pool as well
    place: int
    #: its layer of the buffer its kind carries: the KV cache counts the
    #: model's attention layers from the first (an "X" mixer names the layer
    #: it rereads); every other kind, ``place``
    layer: int
    window: int = 0     # attention's window, 0 = full or no attention
    #: it stands beside the mixer before it: it reads the output of that
    #: mixer's norm and the two are added to the residual in one add
    #: (Falcon-H1's attention and Mamba-2 mixer); it has no norm of its own
    joined: bool = False
    #: an "S" mixer whose scan output, before its gate, is the step's memory:
    #: carried beside the hidden state to the "G" mixers behind it
    keeps: bool = False


def body_of(mixers: tuple[Mixer, ...]) -> tuple[Mixer, ...]:
    """A layer's static description, what its traced body is a function of:
    its mixers with their places taken out (``place`` 0, and ``layer`` how
    far the buffer's layer stands from the place; an "X" mixer's layer is
    another mixer's and stays what it is). Two layers of one description
    are the same equations at other places."""
    return tuple(m._replace(place=0, layer=m.layer if m.kind == "X"
                            else m.layer - m.place) for m in mixers)


class Run(NamedTuple):
    """One scanned run of a plan: ``lead`` layers traced one by one (from
    the run before it, or the model's first layer), then ``period`` layers
    as the body of a scan of ``trips`` trips (``trips`` 0: nothing is
    scanned)."""
    lead: int
    period: int
    trips: int


class LayerPlan(NamedTuple):
    """A model's layers in order, each a tuple of mixers, and how they are
    run (models/llama.py ``_run_layers``): run after run (:class:`Run`),
    and what is left behind the last of them traced one by one. A model of
    one repeated structure has one run; SambaY's two decoders have one
    each."""

    layers: tuple[tuple[Mixer, ...], ...]
    runs: tuple[Run, ...]
    #: the layers from this one on are needed for the tokens whose logits
    #: are taken alone (SambaY's cross-decoder): a step runs them over each
    #: row's last live token. None: every layer runs over every token
    last_from: int | None = None

    @property
    def split(self) -> tuple[int, ...]:
        """(lead, period, trips) of each run in turn, then ``rest``."""
        return (*(x for run in self.runs for x in run), self.rest)

    @property
    def rest(self) -> int:
        """Layers behind the last run, traced one by one."""
        return len(self.layers) - sum(
            r.lead + r.period * r.trips for r in self.runs)

    # The first run's, which is all of a one-run plan.
    lead = property(lambda self: self.runs[0].lead)
    period = property(lambda self: self.runs[0].period)
    trips = property(lambda self: self.runs[0].trips)

    @property
    def spans(self) -> tuple[tuple[int, int, int], ...]:
        """The plan in the order it is run: ``(first layer, layers, trips)``
        of each piece, ``trips`` 0 for layers traced one by one and else a
        period's layers scanned that often. ``last_from`` begins a piece (a
        period is one description, so it never stands inside one)."""
        out, at, cut = [], 0, self.last_from
        for lead, period, trips in self.runs:
            out += [(at, lead, 0), (at + lead, period, trips)]
            at += lead + period * trips
        out.append((at, len(self.layers) - at, 0))
        # (a run of no trips scans nothing: its period is no piece)
        out = [(a, n, t) for i, (a, n, t) in enumerate(out)
               if n and (t or not i % 2)]
        return tuple(piece for a, n, t in out for piece in (
            [(a, cut - a, 0), (cut, a + n - cut, 0)]
            if cut is not None and not t and a < cut < a + n else [(a, n, t)]))

    @property
    def bodies(self) -> tuple[tuple[Mixer, ...], ...]:
        """The layer bodies a program of this plan holds, by description
        (:func:`body_of`): each run's leading layers and one period where
        one is scanned, then the rest. A description that stands there more
        than once is traced and lowered once (``_run_layers``), so a program
        traces ``len(set(bodies))`` of its ``len(bodies)`` bodies."""
        return tuple(body_of(mixers) for at, n, _trips in self.spans
                     for mixers in self.layers[at:at + n])

    def stage(self, n: int) -> "LayerPlan":
        """The plan of one pipeline stage's stack: ``n`` of a model's
        identical layers (the engine gives no other model stages)."""
        return self._replace(layers=self.layers[:n], runs=(Run(0, 1, n),))


def _split(shapes: list, lead: int, period: int) -> tuple[Run, ...]:
    """The runs of layers whose static shapes are ``shapes``: where the
    scans stand in the program. One problem, leading layers + whole periods
    + what is left, asked again of what is left for as long as that holds
    another scan of two trips or more, with one difference in the first
    asking. A model that states its period (``pattern_len``, behind ``lead``
    leading layers) gets that period, scanned whenever one whole period
    fits: a depth cut to one period also fits a shorter one (L L G L is
    L L G and one more), and the stated length decides. A model that states
    none gets the split that traces the fewest layer bodies with at least
    two trips (else nothing is scanned: every layer leads)."""
    n = len(shapes)

    def fits(lead, p, count):
        return all(shapes[lead + i] == shapes[lead + i % p]
                   for i in range(count))

    if 0 < period <= n - lead and fits(lead, period, n - lead):
        return (Run(lead, period, (n - lead) // period),)
    best = (n, 0, n, 1, 0)              # (bodies, rest, lead, period, trips)
    for lead in range(n):
        for p in range(1, (n - lead) // 2 + 1):
            trips = (n - lead) // p
            while trips >= 2 and not fits(lead, p, trips * p):
                trips -= 1
            if trips >= 2:
                rest = n - lead - trips * p
                best = min(best, (lead + p + rest, rest, lead, p, trips))
    run, rest = Run(*best[2:]), best[1]
    if not run.trips:
        return (Run(n, 1, 0),)
    behind = _split(shapes[n - rest:], 0, 0) if rest else ()
    return (run, *(r for r in behind if r.trips))


@dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny-llama"
    vocab_size: int = 512
    hidden_size: int = 64
    intermediate_size: int = 128
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"
    # MoE (0 experts = dense)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0
    # The router's width where this is one chip's share of an
    # expert-parallel deployment: it routes over the published experts and
    # holds the first ``num_experts`` of them (0 = it holds them all).
    num_experts_published: int = 0
    # "softmax": softmax over the chosen logits. "sigmoid": sigmoid scores,
    # chosen by score (+ ``router_bias``, a learned selection bias that
    # weighs nothing), the chosen scores normalised to sum 1
    # (``norm_topk_prob``) and scaled by ``routed_scaling_factor``.
    router_scoring: str = "softmax"
    router_bias: bool = False
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Leading layers whose FFN is dense (width ``intermediate_size``) in a
    # model whose other layers are routed.
    first_k_dense: int = 0
    # Per-layer attention kind, "full_attention" | "sliding_attention"
    # (HF's words); () = every layer full. A sliding layer's query i sees
    # the keys j with i - j < ``sliding_window``.
    layer_types: tuple[str, ...] = ()
    sliding_window: int = 0
    # Layers in one period of the pattern where the model states it (HF's
    # ``sliding_window_pattern`` "LLLG" is 4); 0 = the shortest that fits.
    pattern_len: int = 0
    qk_norm: bool = False        # RMSNorm over each head of q and k
    # "sliding": full-attention layers carry no position; "none": no layer does
    rope_scope: str = "all"
    # "pre": h + f(norm(h)) (Llama). "post": h + norm(f(h)), the sub-layer's
    # input not normalised (EXAONE 4.0's block); the same two leaves.
    norm_placement: str = "pre"
    # A routed expert's gate activation: "silu" (SwiGLU) | "relu" (ReGLU);
    # "relu2" (squared ReLU) is the activation of an expert without a gate.
    expert_act: str = "silu"
    # False: an expert (and the shared expert) is two matrices and no gate,
    # ``down(act(up(x)))``: no ``w_gate`` / ``shared_gate`` leaf.
    expert_gated: bool = True
    # The shared expert's width where it is not ``num_shared_experts`` x
    # ``moe_intermediate_size`` (0 = it is).
    shared_expert_intermediate_size: int = 0
    # What the router reads: "mlp_norm", the expert layer's own input, or
    # "attn_norm", the state that enters attention (the attention norm's
    # output under "pre"): the choice is made before attention and carried
    # past it (SmallThinker's "router placed before attention").
    router_input: str = "mlp_norm"
    # A model whose layers are one mixer each (``h + mixer(norm(h))``), one
    # character a layer: "M" a Mamba-2 layer, "*" attention, "E" routed
    # experts (NemotronH's ``hybrid_override_pattern``). "" = every layer is
    # attention then an FFN. ``num_layers`` counts these layers; the KV cache
    # has the "*" ones alone and the state pool the "M" ones
    # (models/mamba.py).
    hybrid_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 1          # B and C are shared by the heads of a group
    conv_kernel: int = 4
    ssm_chunk: int = 128         # block of the blocked scan in a chunk step
    # The seeded init's range of dt (log-uniform, floored), as the
    # published initialisation draws it.
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # The stored recurrent state's type ("float32" alone is served); the
    # convolution tail is ``dtype``.
    ssm_state_dtype: str = "float32"
    # Every layer's attention has a Mamba-2 mixer beside it (``Mixer.joined``:
    # one norm feeds both, one add takes both), and the FFN follows: three
    # mixers a layer from one place of the repeated stack, recurrent state
    # and keys and values in every layer (Falcon-H1's block).
    ssm_beside_attention: bool = False
    # Scalars on activations, as a muP-parametrised model publishes them
    # (Falcon-H1's keys; 1 / () = none, and then no operation of a program):
    # on the embedding's rows and on the logits; on attention's input, on
    # ``k`` and on attention's output; on the Mamba mixer's input, on the
    # five slices z, x, B, C, dt of its in-projection's output and on its
    # output; on the FFN's gate (before the activation) and on its output.
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = ()
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple[float, ...] = ()
    # "sambay": SambaY's decoder-hybrid-decoder (arXiv:2507.06607). The
    # first half of the layers is a self-decoder, a Mamba-1 mixer ("S") and
    # windowed attention by turns; layer n/2 is one more Mamba-1 mixer whose
    # scan output is the step's memory and layer n/2 + 1 full attention;
    # behind them a cross-decoder, by turns a gated memory unit ("G") on
    # that memory and attention over layer n/2 + 1's keys and values ("X").
    # Every layer is its mixer, then a dense FFN. "" = none of it.
    decoder_layout: str = ""
    # d and the rank of dt's projection in a Mamba-1 mixer (0: no such mixer)
    mamba_inner: int = 0
    mamba_dt_rank: int = 0
    # "rms": RMSNorm, a weight. "layer": LayerNorm, a weight and a bias
    # (``<norm>_b`` beside every norm's leaf), eps ``rms_norm_eps``.
    norm_kind: str = "rms"
    # q, k, v and o are projected with a bias (``bq``, ``bk``, ``bv``, ``bo``)
    attention_bias: bool = False
    # Differential attention (arXiv:2410.05258) in every attention mixer:
    # heads in adjacent pairs, two softmaxes over one pair's keys each, read
    # against the pair's two value heads side by side, the second taken from
    # the first times a learned lambda, an RMSNorm over the pair's width,
    # ``1 - lambda_init``. The KV cache holds whole pairs side by side in
    # fewer, wider heads (``cache_kv_heads`` x ``cache_head_dim``).
    diff_attention: bool = False
    # Multi-head latent attention (arXiv:2405.04434) in every attention
    # mixer, ``kv_lora_rank`` > 0: the cache holds one row a token, the
    # normed latent ``c_kv`` and the one rotary key ``k_r`` all heads share
    # (``kv_lora_rank + qk_rope_head_dim`` values, no head axis), and the
    # up-projections are absorbed into the query and the output
    # (models/llama.py ``_latent_attention``). A head scores over
    # ``qk_nope_head_dim + qk_rope_head_dim`` values and reads ``v_head_dim``;
    # ``head_dim`` is the first of the two sums. ``q_lora_rank`` 0: the
    # query has no down-projection.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Multimodal (vision encoder attached)
    vision: "VisionConfig | None" = None

    def __post_init__(self):
        if self.layer_types and len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of "
                f"{self.num_layers}")
        if self.num_experts_published and (
                self.num_experts_published % self.num_experts):
            raise ValueError(
                f"{self.num_experts} experts held of "
                f"{self.num_experts_published} published: the held count "
                "has to divide the published one (equal shares of an "
                "expert-parallel deployment)")
        if self.first_k_dense and not self.is_moe:
            raise ValueError("first_k_dense leading dense layers in a model "
                             "with no routed layer: leave it 0")
        if self.expert_act not in ("silu", "relu", "relu2"):
            raise ValueError(f"expert_act {self.expert_act!r}: the routed "
                             "experts act by 'silu', 'relu' or 'relu2'")
        if self.decoder_layout not in ("", "sambay"):
            raise ValueError(f"decoder_layout {self.decoder_layout!r}: "
                             "'sambay' or none")
        if self.decoder_layout and (
                self.hybrid_pattern or self.ssm_beside_attention
                or self.is_moe or self.layer_types or self.num_layers < 4
                or self.num_layers % 2 or not self.mamba_inner
                or self.norm_placement != "pre"):
            raise ValueError(
                "decoder_layout 'sambay' gives every layer's kind (no "
                "hybrid_pattern, layer_types, routed layer, "
                "ssm_beside_attention or 'post' norm), over an even number "
                "of layers, four or more, with mamba_inner stated")
        if self.diff_attention and (
                self.num_heads % 2 or self.num_kv_heads % 2 or self.qk_norm
                or self.rope_scope != "none"):
            raise ValueError(
                "diff_attention pairs adjacent heads (even num_heads and "
                "num_kv_heads) and is implemented without positions or QK "
                "norm (rope_scope 'none': the pair view of models/llama.py "
                "_attention has no rope of a half)")
        if self.norm_kind not in ("rms", "layer"):
            raise ValueError(f"norm_kind {self.norm_kind!r}: 'rms' or 'layer'")
        if self.latent and (
                self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim
                or self.qk_rope_head_dim % 2 or not self.v_head_dim
                or self.num_kv_heads != self.num_heads
                or self.kv_lora_rank % 128
                or self.qk_norm or self.diff_attention or self.attention_bias
                or self.layer_types or self.hybrid_pattern
                or self.decoder_layout or self.ssm_beside_attention
                or self.rope_scope != "all" or self.norm_placement != "pre"
                or self.norm_kind != "rms"):
            raise ValueError(
                "latent attention (kv_lora_rank) is implemented for full "
                "attention layers under pre-norm RMSNorm with rotary on "
                "qk_rope_head_dim (even) values of a head of "
                "qk_nope_head_dim + qk_rope_head_dim = head_dim, a "
                "v_head_dim, as many KV heads as heads and a latent of whole "
                "128-lane tiles: no window, QK norm, bias, differential "
                "attention, recurrent mixer or 'post' norm beside it")
        if self.hybrid_pattern:
            odd = set(self.hybrid_pattern) - set("M*E")
            if odd or len(self.hybrid_pattern) != self.num_layers:
                raise ValueError(
                    f"hybrid_pattern {self.hybrid_pattern!r}: one of 'M', "
                    f"'*', 'E' for each of {self.num_layers} layers")
            if (self.first_k_dense or self.layer_types
                    or self.ssm_beside_attention):
                raise ValueError("a hybrid_pattern gives every layer's kind: "
                                 "no first_k_dense, no layer_types, no "
                                 "ssm_beside_attention")
        if self.ssm_beside_attention and (
                self.is_moe or self.norm_placement != "pre"):
            raise ValueError(
                "ssm_beside_attention is implemented for a model of dense "
                "FFNs under norm_placement 'pre' (models/llama.py "
                "_run_layers): no routed layer, no 'post'")
        if self.has_ssm and (
                self.ssm_state_dtype != "float32"
                or (self.mamba_num_heads % max(self.ssm_groups, 1))):
            raise ValueError(
                "the recurrent state is stored as float32 (a bfloat16 "
                "or quantized state is not implemented) and the Mamba "
                "heads divide into ssm_groups equal groups")
        if len(self.ssm_multipliers) not in (0, 5) or len(
                self.mlp_multipliers) not in (0, 2):
            raise ValueError(
                "ssm_multipliers has one scalar for each of z, x, B, C, dt "
                "and mlp_multipliers one for the gate and one for the "
                f"output: got {self.ssm_multipliers}, {self.mlp_multipliers}")
        if self.router_input not in ("mlp_norm", "attn_norm"):
            raise ValueError(f"router_input {self.router_input!r}: the "
                             "router reads 'mlp_norm' or 'attn_norm'")

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def router_width(self) -> int:
        """Experts the router scores: the published count."""
        return self.num_experts_published or self.num_experts

    @property
    def holds_share(self) -> bool:
        """Whether the expert layer holds fewer experts than it routes over."""
        return 0 < self.num_experts < self.router_width

    @property
    def latent(self) -> bool:
        """Whether attention keeps a latent row a token (``kv_lora_rank``)
        in place of keys and values by head."""
        return self.kv_lora_rank > 0

    @property
    def latent_row(self) -> int:
        """What a token's row of the latent cache holds: ``c_kv | k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def has_ssm(self) -> bool:
        """Whether some layer carries recurrent state (models/mamba.py)."""
        return self.layers_of("M") + self.layers_of("S") > 0

    @cached_property
    def layer_plan(self) -> LayerPlan:
        """The one description of the model's layers: every reader of their
        kinds, places, windows and of where the scan stands reads this. A
        hybrid pattern's layer is one mixer from its kind's stack; any
        other model's is attention then an FFN from one place of one, with
        a Mamba-2 mixer joined to attention under
        ``ssm_beside_attention``."""
        layers = []
        if self.decoder_layout:
            return self._sambay_plan()
        if self.hybrid_pattern:
            seen = dict.fromkeys("M*E", 0)
            for kind in self.hybrid_pattern:
                layers.append((Mixer(kind, kind, seen[kind], seen[kind]),))
                seen[kind] += 1
        else:
            for i in range(self.num_layers):
                leads = i < self.first_k_dense
                stack, place = ("lead", i) if leads else (
                    "rep", i - self.first_k_dense)
                beside = ((Mixer("M", stack, place, place, joined=True),)
                          if self.ssm_beside_attention else ())
                layers.append((
                    Mixer("*", stack, place, i, self.window_of(i)), *beside,
                    Mixer("E" if self.is_moe and not leads else "-", stack,
                          place, place)))
        return LayerPlan(tuple(layers), _split(
            [body_of(layer) for layer in layers], self.first_k_dense,
            self.pattern_len))

    def _sambay_plan(self) -> LayerPlan:
        """``layer_plan`` under ``decoder_layout`` "sambay": a stack a kind
        (both Mamba kinds' is "M"), every layer its mixer and then its FFN
        from place ``i`` of the FFNs' stack."""
        half = self.num_layers // 2
        layers, seen = [], dict.fromkeys("M*XG", 0)
        for i in range(self.num_layers):
            if i > half + 1:
                kind = "X" if i % 2 else "G"
            else:
                kind = "*" if i % 2 else "S"
            stack = "M" if kind == "S" else kind
            at = seen[stack]
            seen[stack] += 1
            mixer = Mixer(
                kind, stack, at,
                # (the cross layers reread layer half + 1's keys and values:
                # the last attention layer that writes any)
                half // 2 if kind == "X" else at,
                self.sliding_window if kind == "*" and i < half else 0,
                keeps=i == half)
            layers.append((mixer, Mixer("-", "-", i, i)))
        return LayerPlan(tuple(layers), _split(
            [body_of(layer) for layer in layers], 0, 0), last_from=half + 2)

    def layers_of(self, kind: str) -> int:
        """How many of the model's mixers are ``kind`` (``Mixer.kind``)."""
        return sum(m.kind == kind
                   for layer in self.layer_plan.layers for m in layer)

    @property
    def attn_layers(self) -> int:
        """Layers with attention: the KV cache's layers."""
        return self.layers_of("*")

    @property
    def attn_windows(self) -> tuple[int, ...]:
        """The window of each layer that has attention (0: full)."""
        return tuple(m.window for layer in self.layer_plan.layers
                     for m in layer if m.kind == "*")

    @property
    def cache_kv_heads(self) -> int:
        """The KV cache's heads. Under ``diff_attention`` a cache head is
        several whole pairs of the model's KV heads side by side, the same
        bytes in another view (models/llama.py ``_attention``): as many
        heads as the chip's tiles take whole, the most of 1, 2, 4 or a
        multiple of 8 that divides the pairs (a TPU array's last dimension
        but one is stored in tiles of 8: ten heads of 128 would lie as
        sixteen, and the paged kernel cannot cut them: Phi-4-mini-flash's
        20 heads of 64 are 2 of 640)."""
        if self.latent:
            return 1        # one row a token, which every head reads
        if not self.diff_attention:
            return self.num_kv_heads
        pairs = self.num_kv_heads // 2
        return max(n for n in range(1, pairs + 1)
                   if pairs % n == 0 and (n in (1, 2, 4) or n % 8 == 0))

    @property
    def cache_head_dim(self) -> int:
        """The width of a cache head. A latent row is stored at the next
        multiple of the chip's 128 lanes, zeros behind ``latent_row`` (576
        values lie as 640: a block is then whole tiles, which the paged
        kernel's copies and products take as they are)."""
        if self.latent:
            return -(-self.latent_row // 128) * 128
        return self.num_kv_heads * self.head_dim // self.cache_kv_heads

    def lambda_init(self, kind: str) -> "np.ndarray":
        """Differential attention's ``lambda_init`` at each place of the
        stack of ``kind`` ("*" or "X"), float32: ``0.8 - 0.6 exp(-0.3 i)``
        at the model's layer ``i``."""
        at = [i for i, layer in enumerate(self.layer_plan.layers)
              if any(m.kind == kind for m in layer)]
        return (0.8 - 0.6 * np.exp(-0.3 * np.asarray(at))).astype(np.float32)

    @property
    def shared_expert_width(self) -> int:
        return (self.shared_expert_intermediate_size
                or self.moe_intermediate_size * self.num_shared_experts)

    @property
    def expert_store_width(self) -> int:
        """The width an expert's matrices are stored at: the model's
        (``moe_intermediate_size``) or, where that is wider than a 128-lane
        tile and no multiple of it (1,856), the next multiple, the extra
        columns of ``w_up`` / ``w_gate`` and rows of ``w_down`` zeros, which
        add nothing to any result. A TPU keeps a matrix whose last dimension
        does not fill its tiles in another order than the grouped matmul
        takes, and the program would then copy the whole stack in every
        step (PERF.md section 6, PR 45)."""
        m = self.moe_intermediate_size
        return -(-m // 128) * 128 if m > 128 else m

    @property
    def ssm_inner(self) -> int:
        """d: the Mamba mixer's inner width, heads x head size (Mamba-2) or
        as stated (Mamba-1)."""
        return self.mamba_inner or self.mamba_num_heads * self.mamba_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """c: the channels the convolution runs over, x | B | C (Mamba-2)
        or x alone (Mamba-1)."""
        if self.mamba_inner:
            return self.mamba_inner
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size

    @cached_property
    def ssm_column_multipliers(self) -> "np.ndarray | None":
        """The multiplier of each column of the Mamba in-projection's output,
        float32 ``[d + c + heads]``: ``ssm_multipliers``' five scalars over
        the slices z, x, B, C, dt in the projection's own order; None where
        the model publishes none."""
        if not self.ssm_multipliers:
            return None
        gn = self.ssm_groups * self.ssm_state_size
        widths = (self.ssm_inner, self.ssm_inner, gn, gn, self.mamba_num_heads)
        return np.repeat(np.float32(self.ssm_multipliers), widths)

    @cached_property
    def init_gain(self) -> dict:
        """What the seeded init multiplies a matrix's ``fan_in^-0.5`` by, by
        leaf (a scalar, for ``ssm_in`` one a column; no entry where it is 1):
        1 over the multipliers that stand between the matrix's input and its
        product's use, so that a branch gives what it gives in a model
        without multipliers. At plain fan-in scale the published multipliers
        leave every logit within 0.01 of 0 and a broken mixer inside bf16's
        rounding: the comparison with a reference would see nothing."""
        a_in, columns = self.attention_in_multiplier, self.ssm_column_multipliers
        gate, down = self.mlp_multipliers or (1.0, 1.0)
        gain = {
            "embed": 1 / self.embedding_multiplier,
            "lm_head": 1 / self.lm_head_multiplier,
            "wq": 1 / a_in, "wv": 1 / a_in,
            "wk": 1 / (a_in * self.key_multiplier),
            "wo": 1 / self.attention_out_multiplier,
            "ssm_in": 1 / (self.ssm_in_multiplier
                           * (1.0 if columns is None else columns)),
            "ssm_out": 1 / self.ssm_out_multiplier,
            "w_gate": 1 / gate, "w_down": 1 / down,
        }
        return {k: g for k, g in gain.items() if np.any(g != 1.0)}

    def window_of(self, layer: int) -> int:
        """Layer ``layer``'s attention window, 0 = full."""
        if self.layer_types and self.layer_types[layer] == "sliding_attention":
            return self.sliding_window
        return 0

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        """Values a token's K (and V) has over all heads; under latent
        attention what its down-projection gives, the cached row."""
        if self.latent:
            return self.latent_row
        return self.num_kv_heads * self.head_dim

    @property
    def o_size(self) -> int:
        """The width of ``wo``'s input: the heads' values side by side."""
        return self.num_heads * (self.v_head_dim if self.latent
                                 else self.head_dim)

    @classmethod
    def from_hf_config(cls, path: str) -> "ModelConfig":
        """Read a local HF config.json (llama-family keys)."""
        cfg = json.loads((Path(path) / "config.json").read_text())
        cfg = _glm4_moe_lite_keys(_phi4flash_keys(
            _falcon_h1_keys(_nemotron_h_keys(_smallthinker_keys(cfg)))))
        if "kv_lora_rank" in cfg and not cfg.get(_OWN_FIELDS, {}).get(
                "kv_lora_rank"):
            raise ValueError(
                f"kv_lora_rank under model_type {cfg.get('model_type')!r}: a "
                "latent (MLA) cache is read for model_type 'glm4_moe_lite' "
                "alone; read as keys and values by head it would be served "
                "wrongly")
        n_heads = cfg["num_attention_heads"]
        # MoE keys across HF families: mixtral (num_local_experts),
        # deepseek/qwen-moe (n_routed_experts, num_experts).
        expert_key = next((k for k in ("num_local_experts", "n_routed_experts",
                                       "num_experts") if cfg.get(k)), None)
        n_experts = cfg[expert_key] if expert_key else 0
        # One chip's share of a deployment gives the count held under the
        # model's own key and the published one beside it
        # (chipbench/README.md): the router keeps the published width.
        published = cfg.get(f"{expert_key}_published", 0) if expert_key else 0
        n_layers = cfg["num_hidden_layers"]
        if (cfg.get("n_group") or 1) > 1 or (cfg.get("topk_group") or 1) > 1:
            raise ValueError(
                "group-limited routing (n_group / topk_group above 1) is not "
                "implemented: models/moe.py route() chooses over all experts")
        first_dense = cfg.get("first_k_dense_replace", 0) if n_experts else 0
        mlp_kinds = cfg.get("mlp_layer_types")
        if mlp_kinds and list(mlp_kinds[:n_layers]) != (
                ["dense"] * first_dense + ["sparse"] * (n_layers - first_dense)):
            raise ValueError(
                "mlp_layer_types is not first_k_dense_replace dense layers "
                "followed by sparse ones: no other pattern is implemented")
        # A window applies where layer_types names sliding layers; a bare
        # ``sliding_window`` (Mistral v0.1) stays unread, as it always was.
        kinds = tuple(cfg.get("layer_types") or ())[:n_layers]
        sliding = "sliding_attention" in kinds
        rope = cfg.get("rope_parameters") or {}
        sigmoid = cfg.get("scoring_func") == "sigmoid"
        return cls(**{**dict(
            num_experts=n_experts,
            num_experts_published=published or 0,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2 if n_experts else 0),
            moe_intermediate_size=cfg.get("moe_intermediate_size")
            or (cfg["intermediate_size"] if n_experts else 0),
            num_shared_experts=(cfg.get("n_shared_experts")
                                or cfg.get("num_shared_experts") or 0),
            router_scoring="sigmoid" if sigmoid else "softmax",
            router_bias=bool(cfg.get("router_bias", sigmoid)),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(
                cfg.get("routed_scaling_factor") or 1.0) if sigmoid else 1.0,
            first_k_dense=first_dense,
            layer_types=kinds if sliding else (),
            sliding_window=int(cfg.get("sliding_window") or 0) if sliding else 0,
            pattern_len=len(cfg.get("sliding_window_pattern") or "")
            if sliding and isinstance(cfg.get("sliding_window_pattern"), str)
            else 0,
            qk_norm=bool(cfg.get("qk_norm", False)),
            rope_scope=cfg.get("rope_scope", "all"),
            norm_placement=cfg.get("norm_placement", "pre"),
            expert_act=cfg.get("expert_act", "silu"),
            expert_gated=bool(cfg.get("expert_gated", True)),
            shared_expert_intermediate_size=int(
                cfg.get("moe_shared_expert_intermediate_size") or 0),
            router_input=cfg.get("router_input", "mlp_norm"),
            hybrid_pattern=cfg.get("hybrid_override_pattern", ""),
            mamba_num_heads=cfg.get("mamba_num_heads", 0),
            mamba_head_dim=cfg.get("mamba_head_dim", 0),
            ssm_state_size=cfg.get("ssm_state_size", 0),
            ssm_groups=cfg.get("n_groups", 1),
            conv_kernel=cfg.get("conv_kernel", 4),
            ssm_chunk=cfg.get("chunk_size", 128),
            time_step_min=cfg.get("time_step_min", 0.001),
            time_step_max=cfg.get("time_step_max", 0.1),
            time_step_floor=cfg.get("time_step_floor", 1e-4),
            ssm_state_dtype=cfg.get("ssm_state_dtype", "float32"),
            name=cfg.get("_name_or_path", Path(path).name),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            # (a model with no dense layer need state no dense width)
            intermediate_size=cfg.get("intermediate_size", 0)
            if n_experts and not first_dense else cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=n_heads,
            num_kv_heads=cfg.get("num_key_value_heads", n_heads),
            head_dim=cfg.get("head_dim", cfg["hidden_size"] // n_heads),
            rope_theta=float(cfg.get("rope_theta")
                             or rope.get("rope_theta") or 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            attention_bias=bool(cfg.get("attention_bias", False)),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            # (the fields that one family's reader alone sets)
        ), **cfg.get(_OWN_FIELDS, {})})


def _smallthinker_keys(cfg: dict) -> dict:
    """``cfg`` with SmallThinker's own keys (PowerInfer's ``config.json``:
    ``moe_num_primary_experts``, ``moe_num_active_primary_experts``,
    ``moe_ffn_hidden_size``, ``sliding_window_layout``,
    ``sliding_window_size``, ``rope_layout``) under the names
    ``from_hf_config`` reads; any other config comes back as it is. The two
    layouts give a layer's kind (1: windowed) and whether it carries rotary
    positions (1: yes); the model has them equal, which ``rope_scope:
    "sliding"`` states. What cannot be served is refused by its key."""
    if "moe_num_primary_experts" not in cfg:
        return cfg
    secondary = [k for k, v in cfg.items() if "secondary" in k and v]
    if secondary:
        raise ValueError(
            f"{secondary[0]}: secondary experts are not implemented "
            "(models/moe.py routes over one set of primary experts)")
    if not cfg.get("moe_primary_router_apply_softmax", True):
        raise ValueError(
            "moe_primary_router_apply_softmax: false (sigmoid scores "
            "normalised over the chosen) is not implemented: models/moe.py "
            "route() scores this family by softmax")
    if not cfg.get("norm_topk_prob", True):
        raise ValueError(
            "norm_topk_prob: false (the softmax over all experts, not "
            "renormalised over the chosen) is not implemented: models/moe.py "
            "route() weighs by the softmax over the chosen logits")
    if cfg.get("attention_bias"):
        raise ValueError("attention_bias: true is not implemented: "
                         "models/llama.py projects q, k, v and o without bias")
    n_layers = cfg["num_hidden_layers"]
    sliding = [int(x) for x in cfg.get("sliding_window_layout")
               or [0] * n_layers][:n_layers]
    rope = [int(x) for x in cfg.get("rope_layout") or [1] * n_layers][:n_layers]
    if rope == [1] * n_layers:
        scope = "all"
    elif rope == sliding:
        scope = "sliding"
    else:
        raise ValueError(
            "rope_layout is neither every layer nor the sliding layers of "
            "sliding_window_layout: positions by a layout of their own are "
            "not implemented (models/llama.py _attention ropes by rope_scope)")
    return {
        **cfg,
        "num_experts": cfg["moe_num_primary_experts"],
        "num_experts_per_tok": cfg["moe_num_active_primary_experts"],
        "moe_intermediate_size": cfg["moe_ffn_hidden_size"],
        "layer_types": ["sliding_attention" if s else "full_attention"
                        for s in sliding],
        "sliding_window": cfg.get("sliding_window_size", 0),
        "rope_scope": scope,
    }


def _nemotron_h_keys(cfg: dict) -> dict:
    """``cfg`` with NemotronH's keys (``model_type: "nemotron_h"``: layers of
    one mixer each by ``hybrid_override_pattern``, Mamba-2, attention without
    positions, sigmoid-routed experts of two matrices and a squared ReLU)
    under the names ``from_hf_config`` reads; any other config comes back as
    it is. What cannot be served is refused by its key."""
    if cfg.get("model_type") != "nemotron_h":
        return cfg
    for key in ("mamba_proj_bias", "use_bias", "attention_bias", "mlp_bias"):
        if cfg.get(key):
            raise ValueError(
                f"{key}: true is not implemented: models/mamba.py and "
                "models/llama.py project without bias")
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    if "-" in pattern:
        raise ValueError(
            "hybrid_override_pattern has a '-' layer (a dense FFN as a "
            "layer of its own): not implemented, models/llama.py runs "
            "'M', '*' and 'E' layers")
    act = cfg.get("mlp_hidden_act", "relu2")
    if act != "relu2" or cfg.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError(
            f"mlp_hidden_act {act!r} / mamba_hidden_act "
            f"{cfg.get('mamba_hidden_act')!r}: this family is served with "
            "relu2 experts without a gate and a silu Mamba gate")
    if cfg.get("rope_scope", "none") != "none":
        raise ValueError("rope_scope: this family's attention carries no "
                         "position (the published block applies none)")
    return {
        **cfg,
        "hybrid_override_pattern": pattern,
        "scoring_func": "sigmoid",       # the family's router, no key for it
        "router_bias": True,             # e_score_correction_bias
        "expert_act": "relu2",
        "expert_gated": False,
        "rope_scope": "none",
        "rms_norm_eps": cfg.get("layer_norm_epsilon",
                                cfg.get("norm_eps", 1e-5)),
    }


#: where a family's reader puts the ``ModelConfig`` fields that no key of
#: the common reader stands for
_OWN_FIELDS = "model_config_fields"


def _falcon_h1_keys(cfg: dict) -> dict:
    """``cfg`` with Falcon-H1's keys (``model_type: "falcon_h1"``: in every
    block attention and a Mamba-2 mixer side by side under one norm, then a
    gated MLP; muP multipliers on the activations) under the names
    ``from_hf_config`` reads, the multipliers as the fields they are; any
    other config comes back as it is. What cannot be served is refused by
    its key."""
    if cfg.get("model_type") != "falcon_h1":
        return cfg
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias",
                "projectors_bias"):
        if cfg.get(key):
            raise ValueError(
                f"{key}: true is not implemented: models/mamba.py and "
                "models/llama.py project without bias")
    refused = {
        "attn_layer_indices": (
            cfg.get("attn_layer_indices") is not None,
            "attention in some blocks alone is not implemented: every block "
            "has both mixers"),
        "rope_scaling": (cfg.get("rope_scaling") is not None,
                         "models/llama.py rope() scales no frequency"),
        "mamba_norm_before_gate": (
            bool(cfg.get("mamba_norm_before_gate", False)),
            "models/mamba.py gates first and norms then"),
        "mamba_rms_norm": (not cfg.get("mamba_rms_norm", True),
                           "models/mamba.py norms the gated output"),
        "mamba_use_mlp": (not cfg.get("mamba_use_mlp", True),
                          "a block without its MLP is not implemented"),
        "hidden_act": (cfg.get("hidden_act", "silu") != "silu",
                       "the MLP and the Mamba gate act by silu"),
    }
    for key, (hit, why) in refused.items():
        if hit:
            raise ValueError(f"{key}: {cfg.get(key)!r} is refused: {why}")
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    inner = cfg.get("mamba_d_ssm") or cfg["mamba_expand"] * cfg["hidden_size"]
    if inner != heads * p:
        raise ValueError(
            f"mamba_d_ssm {inner} is not mamba_n_heads x mamba_d_head "
            f"({heads} x {p}): the mixer's inner width is its heads'")
    return {
        **cfg,
        "mamba_num_heads": heads,
        "mamba_head_dim": p,
        "ssm_state_size": cfg["mamba_d_state"],
        "n_groups": cfg["mamba_n_groups"],
        "conv_kernel": cfg["mamba_d_conv"],
        "chunk_size": cfg["mamba_chunk_size"],
        _OWN_FIELDS: {
            "ssm_beside_attention": True,
            **{k: float(cfg.get(k, 1.0)) for k in (
                "embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "key_multiplier",
                "attention_out_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier")},
            **{k: tuple(float(x) for x in cfg.get(k) or ())
               for k in ("ssm_multipliers", "mlp_multipliers")},
        },
    }


def _phi4flash_keys(cfg: dict) -> dict:
    """``cfg`` with Phi-4-mini-flash's keys (``model_type: "phi4flash"``:
    SambaY with differential attention, arXiv:2507.06607; Mamba-1 and
    windowed attention by turns, then a cross-decoder) under the names
    ``from_hf_config`` reads; any other config comes back as it is. The
    Mamba-1 sizes a config leaves out are the configuration class's
    defaults. What cannot be served is refused by its key."""
    if cfg.get("model_type") != "phi4flash":
        return cfg
    n, h = cfg["num_hidden_layers"], cfg["hidden_size"]
    refused = {
        "mb_per_layer": (
            cfg.get("mb_per_layer", 2) != 2,
            "a Mamba mixer in every second layer is what is implemented"),
        "num_hidden_layers": (
            n < 4 or n % 2, "the two decoders are half the layers each: an "
            "even number, four or more"),
        "sliding_window": (
            not isinstance(cfg.get("sliding_window"), int)
            or cfg["sliding_window"] <= 0,
            "one window for the self-decoder's attention layers"),
        "mamba_proj_bias": (bool(cfg.get("mamba_proj_bias", False)),
                            "models/mamba.py projects without bias"),
        "mamba_conv_bias": (not cfg.get("mamba_conv_bias", True),
                            "the convolution is served with its bias"),
        "mlp_bias": (bool(cfg.get("mlp_bias", False)),
                     "the MLP projects without bias"),
        "lm_head_bias": (bool(cfg.get("lm_head_bias", False)),
                         "the head has no bias"),
        "attention_bias": (not cfg.get("attention_bias", True),
                           "the family's attention projects with bias"),
        "hidden_act": (cfg.get("hidden_act", "silu") != "silu",
                       "the MLP, the Mamba gate and the memory unit act by "
                       "silu"),
        "rope_scope": (cfg.get("rope_scope", "none") != "none",
                       "no layer of this family carries a position"),
        "ssm_state_dtype": (cfg.get("ssm_state_dtype", "float32") != "float32",
                            "the recurrent state is stored as float32"),
        "tie_word_embeddings": (not cfg.get("tie_word_embeddings", True),
                                "the head is the embedding's table"),
    }
    for key, (hit, why) in refused.items():
        if hit:
            raise ValueError(f"{key}: {cfg.get(key)!r} is refused: {why}")
    return {
        **cfg,
        "rope_scope": "none",
        "attention_bias": True,
        "rms_norm_eps": cfg.get("layer_norm_eps", 1e-5),
        "ssm_state_size": cfg.get("mamba_d_state", 16),
        "conv_kernel": cfg.get("mamba_d_conv", 4),
        _OWN_FIELDS: {
            "decoder_layout": "sambay",
            "norm_kind": "layer",
            "diff_attention": True,
            "sliding_window": cfg["sliding_window"],
            "mamba_inner": cfg.get("mamba_expand", 2) * h,
            "mamba_dt_rank": cfg.get("mamba_dt_rank") or -(-h // 16),
        },
    }


def _glm4_moe_lite_keys(cfg: dict) -> dict:
    """``cfg`` with GLM-4.7-Flash's keys (``model_type: "glm4_moe_lite"``:
    DeepSeek-V2's multi-head latent attention, arXiv:2405.04434, over
    DeepSeek-V3's bias-corrected sigmoid routing, arXiv:2412.19437, a
    leading dense layer and a shared expert) under the names
    ``from_hf_config`` reads, the latent ranks and the three head sizes as
    the fields they are; any other config comes back as it is. What cannot
    be served is refused by its key."""
    if cfg.get("model_type") != "glm4_moe_lite":
        return cfg
    refused = {
        "rope_scaling": (cfg.get("rope_scaling") is not None,
                         "models/llama.py rope() scales no frequency"),
        "attention_bias": (bool(cfg.get("attention_bias", False)),
                           "the latent projections have no bias"),
        "topk_method": (cfg.get("topk_method", "noaux_tc") != "noaux_tc",
                        "the family's router is the bias-corrected sigmoid "
                        "choice"),
        "scoring_func": (cfg.get("scoring_func", "sigmoid") != "sigmoid",
                         "the family's router scores by sigmoid"),
        "num_nextn_predict_layers": (
            bool(cfg.get("num_nextn_predict_layers", 0)),
            "multi-token-prediction (drafting) blocks are not implemented: "
            "state 0"),
        "hidden_act": (cfg.get("hidden_act", "silu") != "silu",
                       "the MLP and the experts act by silu"),
        "num_key_value_heads": (
            cfg.get("num_key_value_heads", cfg["num_attention_heads"])
            != cfg["num_attention_heads"],
            "every head has its own up-projection of the one latent"),
    }
    for key, (hit, why) in refused.items():
        if hit:
            raise ValueError(f"{key}: {cfg.get(key)!r} is refused: {why}")
    nope, rot = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return {
        **cfg,
        "head_dim": nope + rot,
        "num_key_value_heads": cfg["num_attention_heads"],
        "scoring_func": "sigmoid",      # topk_method noaux_tc states both
        "router_bias": True,            # e_score_correction_bias
        _OWN_FIELDS: {
            "kv_lora_rank": cfg["kv_lora_rank"],
            "q_lora_rank": cfg.get("q_lora_rank") or 0,
            "qk_nope_head_dim": nope,
            "qk_rope_head_dim": rot,
            "v_head_dim": cfg["v_head_dim"],
        },
    }


@dataclass(frozen=True)
class VisionConfig:
    """ViT encoder config for multimodal models (reference role:
    multimodal encode workers, components/src/dynamo/sglang multimodal)."""

    image_size: int = 224
    patch_size: int = 14
    hidden_size: int = 64
    num_layers: int = 2
    num_heads: int = 4
    intermediate_size: int = 128
    projector_hidden: int = 64


MODEL_PRESETS: dict[str, ModelConfig] = {
    # CPU-testable tiny models (the llama.cpp-of-this-repo).
    "tiny-llama": ModelConfig(),
    "tiny-moe": ModelConfig(
        name="tiny-moe",
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=64,
        num_shared_experts=1,
    ),
    # Real targets (shapes only; weights load from local checkpoints).
    "llama-3-8b": ModelConfig(
        name="llama-3-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position_embeddings=8192,
        tie_word_embeddings=False,
    ),
    # 8-layer cut of llama-3-8b: real layer shapes, fits one v5e chip with
    # ample KV cache headroom — used by chip_smoke.py and the compile-check entry.
    "llama-3-8b-lite": ModelConfig(
        name="llama-3-8b-lite",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=8,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position_embeddings=8192,
        tie_word_embeddings=True,
    ),
    "llama-3-70b": ModelConfig(
        name="llama-3-70b",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position_embeddings=8192,
        tie_word_embeddings=False,
    ),
}


def resolve_model_config(name_or_path: str) -> ModelConfig:
    if name_or_path in MODEL_PRESETS:
        return MODEL_PRESETS[name_or_path]
    p = Path(name_or_path)
    if p.is_file() and p.suffix == ".gguf":
        from dynamo_tpu.models.gguf import GGUFReader

        return GGUFReader(p).config()
    if p.is_dir() and (p / "config.json").exists():
        return ModelConfig.from_hf_config(name_or_path)
    raise ValueError(f"unknown model: {name_or_path!r} (presets: {sorted(MODEL_PRESETS)})")
