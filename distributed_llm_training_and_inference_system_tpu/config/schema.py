"""Typed configuration schemas with validation.

The reference declares an (empty) ``llmctl/config`` package whose docstring
promises "schema validation, presets" (reference llmctl/config/__init__.py:1)
and parses TOML/JSON ad-hoc at each call site with zero validation
(reference plan.py:220-237, train_script.py:100-131). This module is the real
thing: every config is a dataclass with types, defaults, ``validate()``, and
tolerant ``from_dict`` constructors that accept the reference's on-disk file
shapes (configs/models/llama-7b.json, configs/presets/llama-7b-a100x8.toml).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


class ConfigError(ValueError):
    """Raised when a config file or value fails validation."""


def _take(d: dict, *names, default=None):
    """First present key among *names* (tolerates schema synonyms)."""
    for n in names:
        if n in d and d[n] is not None:
            return d[n]
    return default


def _parse_bool(name: str, v: Any) -> bool:
    """Strict bool parsing: ``bool("false")`` is True, which silently enabled
    features the operator disabled via env/string-sourced configs (ADVICE r2).
    Accepts real bools and the usual string/int spellings; rejects the rest."""
    if isinstance(v, bool):
        return v
    if isinstance(v, int) and v in (0, 1):
        return bool(v)
    if isinstance(v, str):
        s = v.strip().lower()
        if s in ("true", "1", "yes", "on"):
            return True
        if s in ("false", "0", "no", "off"):
            return False
    raise ConfigError(f"{name} must be a boolean (got {v!r})")


@dataclass
class RopeConfig:
    base: float = 10000.0
    scaling: str = "none"       # none | linear | ntk | yarn
    scaling_factor: float = 1.0
    # YaRN alone (a published ``rope_scaling`` of ``type: yarn``): each
    # frequency is blended between f and f / factor by a linear ramp between
    # the correction dims of ``beta_fast`` and ``beta_slow`` rotations over
    # ``original_max_position``; ``mscale_all_dim`` enters the softmax scale
    # (``softmax_mscale``) and mscale / mscale_all_dim the cos / sin
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    # what cos and sin are multiplied by (a published ``rope_parameters``
    # group's ``attention_factor``: the scores then carry its square); 1:
    # the plain rotation
    attention_factor: float = 1.0

    @property
    def softmax_mscale(self) -> float:
        """m of YaRN's softmax scale (the scores are multiplied by m^2):
        0.1 * mscale_all_dim * ln(factor) + 1, and 1 without YaRN."""
        import math
        if self.scaling != "yarn" or self.scaling_factor <= 1 \
                or not self.mscale_all_dim:
            return 1.0
        return 0.1 * self.mscale_all_dim * math.log(self.scaling_factor) + 1.0

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None,
                  published: dict[str, Any] | None = None) -> "RopeConfig":
        """The nested ``rope`` table ``d``; ``published`` is a config.json's
        ``rope_scaling`` group (``type: yarn`` is the one form read)."""
        d = dict(d or {})
        if published:
            kind = str(_take(published, "type", "rope_type", default="none"))
            if "rope_theta" in published:   # a ``rope_parameters`` group
                d["base"] = published["rope_theta"]
            if kind == "yarn":
                d.update(scaling="yarn", **{
                    k: published[k] for k in (
                        "factor", "beta_fast", "beta_slow", "mscale",
                        "mscale_all_dim", "attention_factor")
                    if k in published})
                d["original_max_position"] = published.get(
                    "original_max_position_embeddings", 4096)
            elif kind != "default":     # (a group's plain rope: its theta)
                raise ConfigError(f"rope_scaling type {kind!r}: yarn is the "
                                  "one published form read")
        if not d:
            return cls()
        return cls(
            base=float(_take(d, "base", "theta", default=10000.0)),
            scaling=str(_take(d, "scaling", default="none")),
            scaling_factor=float(_take(d, "scaling_factor", "factor", default=1.0)),
            original_max_position=int(_take(d, "original_max_position",
                                            default=4096)),
            beta_fast=float(_take(d, "beta_fast", default=32.0)),
            beta_slow=float(_take(d, "beta_slow", default=1.0)),
            mscale=float(_take(d, "mscale", default=1.0)),
            mscale_all_dim=float(_take(d, "mscale_all_dim", default=0.0)),
            attention_factor=float(_take(d, "attention_factor", default=1.0)),
        )


@dataclass
class MLAConfig:
    """Multi-head latent attention (the published ``q_lora_rank`` ...
    ``v_head_dim`` keys): queries through a low-rank bottleneck, keys and
    values expanded from ONE compressed row of ``kv_lora_rank`` values a
    token, plus ``qk_rope_head_dim`` rotated values shared by every head.
    That row (``latent_size`` values) is all the cache keeps.
    ``q_lora_rank`` 0 (the published ``null``) is a DIRECT query projection,
    hidden -> heads x (nope + rope), with no bottleneck and no query norm
    (``kimi_linear``)."""
    q_lora_rank: int = 0
    kv_lora_rank: int = 0           # 0 = the model has plain q / k / v
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    @property
    def latent_size(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def page_width(self) -> int:
        """The latent row as a page stores it: ``latent_size`` padded with
        zeros to whole 128-lane tiles (576 -> 640). The chip's tiled layout
        pads a 576-wide minor dimension to 640 whatever the program says;
        stated here, the kernel's copies and matmuls are whole tiles and the
        bytes a token are counted as they are moved."""
        return -(-self.latent_size // 128) * 128

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "MLAConfig":
        d = d or {}
        return cls(**{f.name: int(d.get(f.name) or 0)
                      for f in dataclasses.fields(cls)})


@dataclass
class MoEConfig:
    """Mixture-of-experts settings (expert parallelism axis).

    Absent from the reference entirely (SURVEY §2.2 row EP); present here
    because the mesh has a first-class expert axis.
    """
    num_experts: int = 0            # 0 = dense model
    experts_per_token: int = 2
    router_aux_loss_weight: float = 0.01
    # training's capacity dispatch only (models/layers.py
    # moe_block_capacity); serving is dropless and never reads it
    capacity_factor: float = 1.25
    # True: a token's top-k router probabilities are renormalised to sum
    # to 1 (Mixtral, the gpt-moe-* templates). False: they weigh the
    # experts as the softmax over ALL experts gave them (OLMoE's
    # published ``norm_topk_prob: false``)
    norm_topk_prob: bool = True
    # ``num_experts`` counts the experts HELD here: this chip's share of a
    # layer that several chips divide. The router keeps its published
    # width ``router_experts`` (0 = ``num_experts``: every expert is
    # here) and the held experts are ``first_expert ..< first_expert +
    # num_experts`` of it. A token's choices that fall on absent experts
    # are computed by nobody here (models/layers.py moe_block): no code
    # stands in for the absent chips or their exchange.
    router_experts: int = 0
    first_expert: int = 0
    # how the router scores: "softmax" over all experts (OLMoE, Mixtral),
    # or "sigmoid" of each logit (``n_routed_experts`` models): there the
    # top-k is taken of score + ``selection_bias`` (a per-expert vector,
    # ``e_score_correction_bias``, that picks and does not weigh) and the
    # weights are the chosen scores, renormalised iff ``norm_topk_prob``,
    # times ``routed_scaling_factor``
    router_score: str = "softmax"
    selection_bias: bool = False
    routed_scaling_factor: float = 1.0
    # width of the ONE shared expert every token also takes (0 = none):
    # a plain dense branch beside the routed ones
    shared_expert_size: int = 0

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def holds_all(self) -> bool:
        return self.router_width == self.num_experts

    @property
    def stats_size(self) -> int:
        """Length of a block's ``moe_stats`` vector (models/layers.py):
        choices per held expert, experts hit and, where not every expert
        is held, the live choices over ALL experts."""
        return self.num_experts + (1 if self.holds_all else 2)

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "MoEConfig":
        """``d`` is the nested ``moe`` table, or a model's published
        ``config.json`` keys (``num_experts`` / ``n_routed_experts``,
        ``num_experts_per_tok``, ``norm_topk_prob``,
        ``routed_scaling_factor``, ``moe_shared_expert_intermediate_size``)
        at the top level of the model dict. ``n_routed_experts`` marks the
        sigmoid router with a selection bias (DeepSeek-V3's form, which
        ``nemotron_h`` takes)."""
        if not d:
            return cls()
        # ``kimi_linear`` spells the same router ``moe_router_activation_func``
        # / ``moe_renormalize`` / ``num_experts_per_token`` /
        # ``num_shared_experts``; ``lfm2_moe`` has no key for the score (its
        # ``use_expert_bias`` and ``routed_scaling_factor`` are the sigmoid
        # lineage's) and states the bias by ``use_expert_bias``
        lfm2 = d.get("model_type") == "lfm2_moe"
        routed = lfm2 or ("n_routed_experts" in d or str(d.get(
            "moe_router_activation_func", "")) == "sigmoid")
        shared = int(_take(d, "n_shared_experts", "num_shared_experts",
                           default=0) or 0)
        return cls(
            num_experts=int(_take(d, "num_experts", "experts",
                                  "n_routed_experts", default=0)),
            router_experts=int(_take(d, "router_experts", default=0)),
            first_expert=int(_take(d, "first_expert", default=0)),
            router_score=str(_take(d, "router_score", default=(
                "sigmoid" if routed else "softmax"))),
            selection_bias=_parse_bool("selection_bias", _take(
                d, "selection_bias", default=(
                    d.get("use_expert_bias", False) if lfm2 else routed))),
            routed_scaling_factor=float(_take(
                d, "routed_scaling_factor", default=1.0)),
            # (``n_shared_experts`` alone: each is one routed expert wide)
            shared_expert_size=int(_take(
                d, "shared_expert_size",
                "moe_shared_expert_intermediate_size", default=(
                    d.get("moe_intermediate_size", 0) if shared else 0)))
            * max(shared, 1),
            experts_per_token=int(_take(d, "experts_per_token", "top_k",
                                        "num_experts_per_tok",
                                        "num_experts_per_token", default=2)),
            router_aux_loss_weight=float(_take(d, "router_aux_loss_weight", default=0.01)),
            capacity_factor=float(_take(d, "capacity_factor", default=1.25)),
            norm_topk_prob=_parse_bool("norm_topk_prob", _take(
                d, "norm_topk_prob", "moe_renormalize", default=True)),
        )


@dataclass(frozen=True)     # hashable: a jitted function's static
class SSMConfig:
    """Mamba-2 state-space mixer sizes (the ``M`` layers of a layer table).

    ``num_heads`` heads of ``head_dim`` channels (inner width ``num_heads
    * head_dim``: NOT ``expand * hidden_size``), a state of ``state_size``
    a channel, B and C shared by the heads of each of ``n_groups`` groups,
    a depthwise causal conv of width ``conv_kernel`` over x, B and C, and
    the chunk length of the prefill scan. The recurrent state is cached
    between decode steps in float32, by construction (serve/kv_cache.py:
    at bfloat16 a sequence's logits drift; tests/test_hybrid.py)."""
    num_heads: int = 0              # 0 = the model has no such layer
    head_dim: int = 64
    state_size: int = 128
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 128

    @property
    def inner_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return self.inner_size + 2 * self.n_groups * self.state_size

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None,
                  published: dict[str, Any] | None = None) -> "SSMConfig":
        """The nested ``ssm`` table ``d``, else the ``nemotron_h`` keys of
        a ``published`` config.json (``mamba_num_heads``, ``mamba_head_dim``,
        ``ssm_state_size``, ``n_groups``, ``conv_kernel``, ``chunk_size``)
        at the top level of the model dict. A file that states the dtype
        the state is cached in (``ssm_state_dtype``) may only state
        float32."""
        if d:
            return cls(**{f.name: type(f.default)(d[f.name])
                          for f in dataclasses.fields(cls) if f.name in d})
        p = published or {}
        if "mamba_num_heads" not in p and "mamba_n_heads" not in p:
            return cls()
        if p.get("ssm_state_dtype", "float32") != "float32":
            raise ConfigError(
                f"ssm_state_dtype {p['ssm_state_dtype']!r}: the recurrent "
                "state is cached in float32 and in nothing else")
        # (``falcon_h1`` spells them ``mamba_n_heads`` / ``mamba_d_head`` /
        # ``mamba_d_state`` / ``mamba_n_groups`` / ``mamba_d_conv`` /
        # ``mamba_chunk_size``; its ``mamba_expand`` is not read: the inner
        # width is ``mamba_d_ssm`` = heads x head size)
        cfg = cls(
            num_heads=int(_take(p, "mamba_num_heads", "mamba_n_heads")),
            head_dim=int(_take(p, "mamba_head_dim", "mamba_d_head",
                               default=64)),
            state_size=int(_take(p, "ssm_state_size", "mamba_d_state",
                                 default=128)),
            n_groups=int(_take(p, "n_groups", "mamba_n_groups", default=1)),
            conv_kernel=int(_take(p, "conv_kernel", "mamba_d_conv",
                                  default=4)),
            chunk_size=int(_take(p, "chunk_size", "mamba_chunk_size",
                                 default=128)),
        )
        if int(p.get("mamba_d_ssm", cfg.inner_size)) != cfg.inner_size:
            raise ConfigError(
                f"mamba_d_ssm {p['mamba_d_ssm']} is not mamba_n_heads x "
                f"mamba_d_head = {cfg.inner_size}")
        return cfg


@dataclass(frozen=True)     # hashable: a jitted function's static
class KDAConfig:
    """Kimi Delta Attention sizes (the ``K`` layers of a layer table; the
    published ``linear_attn_config``): ``num_heads`` heads, each a
    ``head_dim`` x ``head_dim`` float32 state moved by the gated delta rule
    with one decay a CHANNEL of the key (ops/kda.py), a depthwise causal
    conv of width ``conv_kernel`` over q, k and v. The two low-rank pairs (decay and output gate) have
    the rank ``head_dim``. The state is cached in float32, by construction
    (serve/kv_cache.py), as ``SSMConfig``'s. ``allow_neg_eigval``
    (``solar_open2``'s ``kda_allow_neg_eigval``): ``beta = 2 sigmoid(b)``,
    so that the Householder factor ``I - beta k k^T`` has eigenvalues in
    [-1, 1] and the rule may reflect."""
    num_heads: int = 0              # 0 = the model has no such layer
    head_dim: int = 128
    conv_kernel: int = 4
    allow_neg_eigval: bool = False

    @property
    def inner_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        """q, k and v side by side: one conv window a slot."""
        return 3 * self.inner_size

    @property
    def in_proj_size(self) -> int:
        """[q | k | v | decay low-rank | gate low-rank | beta]."""
        return self.conv_channels + 2 * self.head_dim + self.num_heads

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "KDAConfig":
        """The nested ``kda`` table, or the published
        ``linear_attn_config`` group (``num_heads``, ``head_dim``,
        ``short_conv_kernel_size``; its layer lists make the table,
        ``ModelConfig.from_dict``)."""
        if not d:
            return cls()
        return cls(
            num_heads=int(d["num_heads"]),
            head_dim=int(d.get("head_dim", 128)),
            conv_kernel=int(_take(d, "conv_kernel",
                                  "short_conv_kernel_size", default=4)),
            allow_neg_eigval=_parse_bool("allow_neg_eigval", d.get(
                "allow_neg_eigval", False)),
        )


REMASKING_STRATEGIES = ("low_confidence_dynamic", "low_confidence_static",
                        "sequential")


@dataclass
class DiffusionConfig:
    """Generation by diffusion over blocks (``model_type: sdar_moe``): the
    reply is made ``block_length`` positions at a time. A block starts as
    mask tokens; every denoise forward sees the whole block (rows of a
    block see each other, blocks are causal among themselves) and FIXES
    some of its masked rows by ``remasking_strategy``: the schedule's
    count (``block_length / denoising_steps``, the remainder on the first
    steps) of most confident rows (``low_confidence_static``), those and
    every row whose confidence passes ``confidence_threshold``
    (``low_confidence_dynamic``), or the leftmost (``sequential``). A
    block with no mask left is final, and the next block's first denoise
    forward carries it along and stores its K/V (the commit:
    serve/decode.py ``denoise_scan``). ``block_length`` 0: an
    autoregressive model."""
    block_length: int = 0
    denoising_steps: int = 4
    mask_token_id: int = 0
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9

    @property
    def transfer_schedule(self) -> tuple[int, ...]:
        """Rows fixed at denoise step 0, 1, ...: an even split of the
        block, the remainder on the first steps."""
        base, rem = divmod(self.block_length, self.denoising_steps)
        return tuple(base + (s < rem) for s in range(self.denoising_steps))

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None,
                  published: bool = False) -> "DiffusionConfig":
        """The nested ``diffusion`` table or the file's top-level keys.
        ``published`` (``model_type: sdar_moe``, whose config.json states
        none of these): the defaults of the published generation loop as
        the benchmark's configuration assumes them."""
        d = d or {}
        if not published and not d.get("block_length"):
            return cls()
        return cls(
            block_length=int(d.get("block_length", 4)),
            denoising_steps=int(d.get("denoising_steps", 4)),
            mask_token_id=int(d.get("mask_token_id", 151669)),
            remasking_strategy=str(d.get("remasking_strategy",
                                         "low_confidence_dynamic")),
            confidence_threshold=float(d.get("confidence_threshold", 0.9)),
        )


@dataclass(frozen=True)     # hashable: a jitted function's static
class MupConfig:
    """The muP multipliers of ``model_type: falcon_h1``, one a branch, each
    under the published key's name less ``_multiplier(s)``. Scalars the
    model was TRAINED with and applies in its forward pass: on the embedding's
    rows, on the attention branch's input, keys and output, on the
    state-space branch's input, on the five parts of its in-projection's
    output (``ssm``: z, x, B, C, dt) and on its output, on the MLP's gate
    pre-activation and its output (``mlp``), and on the logits. All 1: a
    model without them, whose programs hold no multiply for them."""
    embedding: float = 1.0
    lm_head: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp: tuple = (1.0, 1.0)

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None,
                  published: dict[str, Any] | None = None) -> "MupConfig":
        """The nested ``mup`` table, else the ``falcon_h1`` keys of a
        ``published`` config.json (``embedding_multiplier``, ...,
        ``ssm_multipliers`` [5], ``mlp_multipliers`` [2])."""
        if d:
            return cls(**{k: tuple(float(x) for x in v)
                          if isinstance(v, (list, tuple)) else float(v)
                          for k, v in d.items()})
        p = published or {}
        got = {}
        for f in dataclasses.fields(cls):
            plural = isinstance(f.default, tuple)
            key = f.name + ("_multipliers" if plural else "_multiplier")
            if p.get(key) is None:
                continue
            if plural and len(p[key]) != len(f.default):
                raise ConfigError(f"{key} has {len(p[key])} entries, not "
                                  f"{len(f.default)}")
            got[f.name] = (tuple(float(x) for x in p[key]) if plural
                           else float(p[key]))
        return cls(**got)


# what a layer of a layer table may be (``nemotron_h``'s own letters)
# ``D`` (this repo's letter): a dense gated MLP as a layer of its own, the
# feed-forward of a leading dense layer before the expert layers
# ``K`` (this repo's letter): a Kimi Delta Attention mixer
# ``P`` (this repo's letter): attention AND a Mamba-2 mixer side by side
# under ONE norm (``falcon_h1``): both read the same normed stream, their
# outputs are summed, and the layer keeps K/V pages and a recurrent state
# ``C`` (this repo's letter): a gated short-convolution mixer (``lfm2_moe``):
# a depthwise causal conv of ``shortconv_kernel`` taps between two gates,
# whose whole state is the ``shortconv_kernel - 1`` rows before the window
LAYER_KINDS = {"M": "ssm", "*": "attn", "E": "moe", "D": "mlp", "K": "kda",
               "P": "par", "C": "conv"}


@dataclass
class ModelConfig:
    """Decoder-only transformer architecture.

    Field names follow the reference's model JSON
    (reference configs/models/llama-7b.json:1-24): layers/hidden/ffn/heads/
    head_dim/vocab_size/..., with TPU-relevant additions (num_kv_heads for
    GQA, dtype, MoE).
    """
    name: str = "gpt-125m"
    arch: str = "decoder-only"
    num_layers: int = 12
    hidden_size: int = 768
    ffn_size: int = 3072
    num_heads: int = 12
    num_kv_heads: int = 12          # < num_heads ⇒ grouped-query attention
    head_dim: int = 64
    vocab_size: int = 50304         # padded to a multiple of 128 for the MXU
    max_position_embeddings: int = 2048
    rope: RopeConfig = field(default_factory=RopeConfig)
    activation: str = "silu"        # silu (SwiGLU) | gelu (GeGLU) | relu
    norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    dropout: float = 0.0
    dtype: str = "bfloat16"         # activations/weights compute dtype
    moe: MoEConfig = field(default_factory=MoEConfig)
    # RMSNorm on the query and key projections before rope. "projection":
    # one norm over the WHOLE [Nq*D] (and [Nkv*D]) projection, before the
    # split into heads (OLMoE; its config.json has no key for it, it
    # follows from ``model_type: olmoe``). "head": one norm over EACH head's
    # ``head_dim`` values with a learned [head_dim] scale (``sdar_moe``, as
    # the Qwen3-MoE attention it derives from). "none": llama-style.
    qk_norm: str = "none"
    # generation by diffusion over blocks (``block_length`` > 0)
    diffusion: DiffusionConfig = field(default_factory=DiffusionConfig)
    # The LAYER TABLE: one letter a layer (``hybrid_override_pattern``):
    # ``M`` a Mamba-2 state-space mixer, ``*`` attention, ``E`` sparse
    # experts; each such layer is ONE norm and ONE mixer on the residual
    # stream. "" = the uniform stack (every layer attention THEN
    # feed-forward under two norms).
    layer_pattern: str = ""
    ssm: SSMConfig = field(default_factory=SSMConfig)
    kda: KDAConfig = field(default_factory=KDAConfig)
    # taps of a ``C`` layer's depthwise causal conv (``conv_L_cache``): a
    # slot keeps the ``shortconv_kernel - 1`` rows before its next token
    shortconv_kernel: int = 3
    # "rope" | "none": ``nemotron_h``'s attention applies no position
    # embedding (positions come from the state-space layers), and
    # ``kimi_linear``'s latent attention carries and scores its ``pe``
    # values without rotating them (``mla_use_nope``: positions come from
    # the ``K`` layers)
    position_embedding: str = "rope"
    # ``solar_open2``'s ``use_gqa_gate``: a ``*`` layer's attention output
    # is multiplied by sigmoid(x W_g) (one projection of the layer's normed
    # input, H -> num_heads * head_dim, elementwise) before ``o``
    attention_gate: bool = False
    # False: the feed-forward is down(act(up(x))), two kernels (no gate)
    mlp_gated: bool = True
    # latent attention (``*`` layers keep ONE compressed row a token)
    mla: MLAConfig = field(default_factory=MLAConfig)
    # width of a ``D`` layer's MLP (``intermediate_size`` beside
    # ``moe_intermediate_size``); 0 = ``ffn_size``
    dense_ffn_size: int = 0
    # manifold-constrained hyper-connections: ``hc_mult`` residual streams
    # (1 = the plain residual), each sub-layer reading a mix of them and
    # writing back through three input-dependent maps, the stream-to-stream
    # one made doubly stochastic by ``hc_sinkhorn_iters`` Sinkhorn-Knopp
    # iterations of exp(clip(., ``hc_clamp_min``, ``hc_clamp_max``));
    # ``hc_eps`` is the maps' RMSNorm's and the Sinkhorn denominators'
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    # ``falcon_h1``'s muP multipliers (all 1: none)
    mup: MupConfig = field(default_factory=MupConfig)
    # next-token prediction modules behind the main stack (the published
    # ``num_nextn_predict_layers``; 0 or 1): each one more decoder layer
    # (latent attention + experts) over [norm(embedding of the NEXT token) |
    # norm(the main stack's stream)] through a 2H -> H projection, with its
    # own final norm and the main model's embedding and head. Its layer is
    # the LAST entry of the ``attn`` and ``moe`` stacks and of the latent
    # pool (``kv_layers`` / ``moe_layers`` count it; the layer table does
    # not: the main stack's walk ends before it). It serves as the drafter
    # of ``ServeConfig.speculative: mtp`` (serve/decode.py
    # ``draft_verify_scan``)
    mtp_layers: int = 0
    # a LOOPED stack (``model_type: ouro``): the uniform stack is walked
    # ``num_passes`` times (the published ``total_ut_steps``) over ONE set
    # of weights; the final norm closes every pass and its output is what
    # the next pass reads; an exit gate (one output, a bias) reads each
    # pass's normed state; K and V are kept per (pass, layer), so a pool has
    # ``num_passes x num_layers`` planes (``kv_layers``), pass t's layer l
    # at ``t * num_layers + l``. A token leaves at the first pass where the
    # exit distribution's running sum reaches ``exit_threshold`` (the
    # published ``early_exit_threshold``; 1: after the last pass)
    num_passes: int = 1
    exit_threshold: float = 1.0
    # a second RMSNorm on the attention's and the feed-forward's OUTPUT,
    # before the residual takes it (``ouro``'s ``input_layernorm_2`` and
    # ``post_attention_layernorm_2``)
    sandwich_norm: bool = False
    # WINDOW layers beside full ones (``model_type: mellum``): a layer of
    # the uniform stack whose ``layer_types`` entry is "sliding" sees, of the
    # keys at or before it, the last ``sliding_window`` alone (itself
    # included) and rotates by ``window_rope`` (the published
    # ``rope_parameters`` are keyed by layer kind); a "full" layer sees
    # every earlier key and rotates by ``rope``. () = every layer full. A
    # window layer KEEPS only what it can see: its K/V live in a ring of
    # pages a slot (serve/kv_cache.py), the full layers' in a growing chain
    sliding_window: int = 0
    layer_types: tuple = ()
    window_rope: RopeConfig = field(default_factory=RopeConfig)

    @property
    def is_looped(self) -> bool:
        return self.num_passes > 1

    @property
    def has_window(self) -> bool:
        """Some layer of the uniform stack is a window layer."""
        return "sliding" in self.layer_types

    @property
    def window_layers(self) -> int:
        return sum(t == "sliding" for t in self.layer_types)

    @property
    def window_period(self) -> tuple:
        """The shortest run of layer kinds that ``layer_types`` repeats
        (("sliding", "sliding", "sliding", "full") x 7): the serve programs
        scan over periods, a layer's kind static inside one."""
        types = tuple(self.layer_types)
        for p in range(1, len(types) + 1):
            if len(types) % p == 0 and types == types[:p] * (len(types) // p):
                return types[:p]
        return types

    def layer_rope(self, kind: str) -> RopeConfig:
        """The rope of a layer of ``kind`` ("sliding" | "full")."""
        return self.window_rope if kind == "sliding" else self.rope

    @property
    def is_moe(self) -> bool:
        return self.moe.num_experts > 0

    @property
    def is_latent(self) -> bool:
        return self.mla.kv_lora_rank > 0

    @property
    def is_diffusion(self) -> bool:
        return self.diffusion.block_length > 0

    @property
    def attention_block(self) -> int:
        """The attention mask's block: rows of one block of this many
        positions see each other (0: plain causal attention)."""
        return self.diffusion.block_length

    @property
    def rope_dim(self) -> int:
        """Values of a head that rope rotates."""
        return self.mla.qk_rope_head_dim if self.is_latent else self.head_dim

    @property
    def softmax_scale(self) -> float:
        """What attention scores are multiplied by: head_dim^-0.5, times
        YaRN's m^2 where the rope has it."""
        return self.head_dim ** -0.5 * self.rope.softmax_mscale ** 2

    def kv_bytes_per_token(self, itemsize: int = 2,
                           kind: str = "") -> int:
        """Cache bytes one token costs over all the layers that keep any:
        K and V of every kv head, or ONE padded latent row. ``kind``
        ("sliding" | "full"): over the layers of that kind alone (a window
        layer keeps a token's rows only while a query can see them)."""
        if self.is_latent:
            return self.kv_layers * self.mla.page_width * itemsize
        layers = {"": self.kv_layers, "sliding": self.window_layers,
                  "full": self.kv_layers - self.window_layers}[kind]
        return 2 * layers * self.num_kv_heads * self.head_dim * itemsize

    def layers_of(self, kind: str) -> int:
        """How many layers of the table are ``kind`` (M | * | E)."""
        return self.layer_pattern.count(kind)

    @property
    def kv_layers(self) -> int:
        """Layers that keep K and V: the attention layers of a table (a
        ``P`` layer is one, and a state-space layer too), every layer of a
        uniform stack, once a pass of a looped one."""
        if not self.layer_pattern:
            return self.num_passes * self.num_layers
        return self.layers_of("*") + self.layers_of("P") + self.mtp_layers

    @property
    def moe_layers(self) -> int:
        if self.layer_pattern:
            return self.layers_of("E") + self.mtp_layers
        return self.num_layers if self.is_moe else 0

    @property
    def ssm_layers(self) -> int:
        """Layers that keep a Mamba-2 state a slot (``M``, and ``P``)."""
        return self.layers_of("M") + self.layers_of("P")

    @property
    def kda_layers(self) -> int:
        return self.layers_of("K")

    @property
    def conv_layers(self) -> int:
        """Layers that keep a short-convolution window a slot (``C``)."""
        return self.layers_of("C")

    @property
    def recurrent_kind(self) -> str:
        """The letter of this model's recurrent layers (a table has one
        kind: ``K``, ``C``, or ``M`` / ``P``), "" without any."""
        return ("K" if self.kda_layers else "C" if self.conv_layers
                else "M" if self.ssm_layers else "")

    @property
    def recurrent_name(self) -> str:
        """What a refusal calls this model's recurrent layers."""
        return {"K": "delta-rule linear-attention (K) layers",
                "C": "gated short-convolution (C) layers"}.get(
                    self.recurrent_kind, "state-space layers")

    @property
    def is_recurrent(self) -> bool:
        """Some layer keeps a fixed-size state a sequence beside (or in
        place of) K/V or latent pages."""
        return bool(self.recurrent_kind)

    def validate(self) -> None:
        # hidden_size need not equal num_heads*head_dim (projections go
        # hidden -> q_dim and back), but every dimension must be positive
        # and heads must group evenly over kv heads.
        if self.num_kv_heads < 1 or self.num_heads < 1 or self.head_dim < 1:
            raise ConfigError("num_heads, num_kv_heads, head_dim must be >= 1")
        if self.num_heads % self.num_kv_heads != 0:
            raise ConfigError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({self.num_kv_heads})")
        if self.vocab_size <= 0 or self.num_layers <= 0:
            raise ConfigError("vocab_size and num_layers must be positive")
        if self.activation not in ("silu", "gelu", "relu", "relu2"):
            raise ConfigError(f"unknown activation {self.activation!r}")
        if self.position_embedding not in ("rope", "none"):
            raise ConfigError("position_embedding must be rope|none (got "
                              f"{self.position_embedding!r})")
        if self.layer_pattern:
            unknown = sorted(set(self.layer_pattern) - set(LAYER_KINDS))
            if unknown:
                raise ConfigError(
                    f"layer_pattern {self.layer_pattern!r}: no layer kind "
                    f"{unknown} (known: M state-space, * attention, E "
                    "experts, D dense MLP, K delta-rule linear attention, "
                    "P attention and state-space side by side, C gated "
                    "short convolution)")
            if len(self.layer_pattern) != self.num_layers:
                raise ConfigError(
                    f"layer_pattern has {len(self.layer_pattern)} layers, "
                    f"num_layers is {self.num_layers}")
            if self.layers_of("E") and not self.is_moe:
                raise ConfigError("layer_pattern has E layers and the "
                                  "model has no experts")
            s = self.ssm
            if self.ssm_layers and (
                    s.num_heads < 1 or s.num_heads % max(s.n_groups, 1)
                    or s.conv_kernel < 2 or s.chunk_size < 1):
                raise ConfigError(
                    "layer_pattern has M or P layers: ssm.num_heads must be "
                    "a positive multiple of ssm.n_groups, conv_kernel >= 2 "
                    f"(got {s})")
            kinds = [k for k, n in (("M", self.ssm_layers),
                                    ("K", self.kda_layers),
                                    ("C", self.conv_layers)) if n]
            if len(kinds) > 1:
                raise ConfigError(
                    f"layer_pattern has {' and '.join(kinds)} layers (a P "
                    "layer holds an M mixer): one recurrent kind a model "
                    "(the state pools hold one kind's rows)")
            if self.conv_layers and (
                    self.shortconv_kernel < 2 or self.is_latent
                    or self.hc_mult > 1):
                raise ConfigError(
                    "layer_pattern has C layers: shortconv_kernel must be "
                    f">= 2 (got {self.shortconv_kernel}), beside K/V pages "
                    "and over one residual stream")
            if self.layers_of("P") and (
                    self.layers_of("*") or self.layers_of("M")
                    or self.is_latent):
                raise ConfigError(
                    "layer_pattern has P layers beside * or M layers (or "
                    "latent attention): a P layer's index addresses the K/V "
                    "pools AND the state pools, which then hold the P "
                    "layers alone")
            k = self.kda
            if self.layers_of("K") and (
                    k.num_heads < 1 or k.head_dim < 1 or k.conv_kernel < 2):
                raise ConfigError(
                    "layer_pattern has K layers: kda.num_heads and head_dim "
                    f"must be >= 1, conv_kernel >= 2 (got {k})")
        if self.attention_gate and (not self.layer_pattern or self.is_latent):
            raise ConfigError(
                "attention_gate is carried by the layer table's K/V "
                "attention (``*`` layers of a model without latent "
                "attention)")
        if self.is_latent or self.hc_mult > 1:
            a = self.mla
            if not self.layer_pattern:
                raise ConfigError(
                    "latent attention and hyper-connections live on the "
                    "layer table: give layer_pattern (or the published "
                    "num_hidden_layers / first_k_dense_replace)")
            if self.is_latent and (
                    min(a.qk_nope_head_dim, a.v_head_dim) < 1
                    or a.q_lora_rank < 0
                    or a.qk_rope_head_dim < 2 or a.qk_rope_head_dim % 2
                    or self.head_dim != a.qk_nope_head_dim
                    + a.qk_rope_head_dim
                    or self.num_kv_heads != self.num_heads):
                raise ConfigError(
                    "latent attention needs qk_nope_head_dim, v_head_dim "
                    ">= 1, q_lora_rank >= 0 (0: a direct query projection), "
                    "an even qk_rope_head_dim, head_dim = "
                    "nope + rope and num_kv_heads = num_heads (got "
                    f"{a}, head_dim {self.head_dim})")
            if self.hc_mult < 1 or self.hc_sinkhorn_iters < 1:
                raise ConfigError("hc_mult and hc_sinkhorn_iters must be "
                                  ">= 1")
        if self.rope.scaling not in ("none", "linear", "ntk", "yarn"):
            raise ConfigError(f"rope scaling {self.rope.scaling!r}: "
                              "none|linear|ntk|yarn")
        m = self.moe
        if self.is_moe and (
                m.router_score not in ("softmax", "sigmoid")
                or m.first_expert < 0
                or m.first_expert + m.num_experts > m.router_width):
            raise ConfigError(
                f"moe: router_score softmax|sigmoid, and the held experts "
                f"{m.first_expert}..<{m.first_expert + m.num_experts} must "
                f"lie inside the router's {m.router_width} (got {m})")
        if self.layer_types:
            kinds = sorted(set(self.layer_types) - {"sliding", "full"})
            if kinds or len(self.layer_types) != self.num_layers:
                raise ConfigError(
                    f"layer_types must name each of the {self.num_layers} "
                    "layers sliding or full (got "
                    f"{len(self.layer_types)} entries, unknown {kinds})")
        if self.has_window:
            if self.sliding_window < 1:
                raise ConfigError(
                    f"sliding_window = {self.sliding_window}: a window "
                    "layer sees at least itself")
            for what, has in (("a layer table (layer_pattern)",
                               self.layer_pattern),
                              ("latent attention", self.is_latent),
                              ("a looped stack", self.is_looped),
                              ("generation by diffusion", self.is_diffusion)):
                if has:
                    raise ConfigError(
                        f"window layers beside {what} are refused: the "
                        "window's ring of pages and its term in the mask "
                        "are carried by the uniform stack over K/V pages, "
                        "walked once, under the causal rule (ROADMAP B3)")
        if self.num_passes < 1:
            raise ConfigError(
                f"total_ut_steps = {self.num_passes}: a stack is walked at "
                "least once")
        if self.is_looped or self.sandwich_norm:
            if self.layer_pattern or self.is_moe or self.is_diffusion:
                raise ConfigError(
                    "a looped stack (total_ut_steps > 1) and sandwich norms "
                    "are carried by the dense uniform stack: a layer table's "
                    "pools are addressed by the kind's layer alone, the "
                    "expert statistics count a layer once, and a denoise "
                    "window has no pass")
            if self.exit_threshold != 1.0:
                raise ConfigError(
                    f"early_exit_threshold = {self.exit_threshold}: a "
                    "threshold below 1 is refused (a token that leaves at "
                    "pass t writes no K/V in the planes of passes t+1.."
                    f"{self.num_passes}, and which rows later tokens then "
                    "attend there is a rule the published config does not "
                    "give): state 1, every token runs every pass")
        if self.arch != "decoder-only":
            raise ConfigError(f"unsupported arch {self.arch!r} (decoder-only only)")
        if self.qk_norm not in ("none", "projection", "head"):
            raise ConfigError(f"qk_norm must be none|projection|head (got "
                              f"{self.qk_norm!r})")
        if self.is_diffusion:
            f = self.diffusion
            if self.layer_pattern:
                raise ConfigError(
                    "generation by diffusion over blocks runs on the uniform "
                    "layer stack (a latent or recurrent layer has no block "
                    "rule): layer_pattern must be empty")
            if not (1 <= f.denoising_steps <= f.block_length
                    and 0 <= f.mask_token_id < self.vocab_size
                    and f.remasking_strategy in REMASKING_STRATEGIES
                    and 0.0 < f.confidence_threshold <= 1.0):
                raise ConfigError(
                    "diffusion: 1 <= denoising_steps <= block_length, "
                    "mask_token_id inside the vocabulary, remasking_strategy "
                    f"one of {REMASKING_STRATEGIES}, 0 < "
                    f"confidence_threshold <= 1 (got {f})")
        if self.is_moe and not (
                1 <= self.moe.experts_per_token <= self.moe.router_width):
            raise ConfigError(
                f"experts_per_token ({self.moe.experts_per_token}) must lie "
                f"in 1..num_experts ({self.moe.router_width})")

    @property
    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head).

        Mirrors the planner's estimate_parameters
        (reference plan.py:40-58) but exact for this architecture.
        """
        h, f, v = self.hidden_size, self.ffn_size, self.vocab_size
        kv_dim = self.num_kv_heads * self.head_dim
        q_dim = self.num_heads * self.head_dim
        attn = h * q_dim + 2 * h * kv_dim + q_dim * h
        if self.layer_pattern:
            # one norm and one mixer a layer; the experts HELD here
            s, m, kd = self.ssm, self.moe, self.kda
            per_expert = (3 if self.mlp_gated else 2) * h
            a, n = self.mla, self.num_heads
            if self.is_latent:
                # q_a, its norm, q_b (or the one direct q), kv_a, the
                # latent's norm, kv_b, o
                attn = ((h * a.q_lora_rank + a.q_lora_rank
                         + a.q_lora_rank * n * self.head_dim
                         if a.q_lora_rank else h * n * self.head_dim)
                        + h * a.latent_size + a.kv_lora_rank
                        + a.kv_lora_rank * n
                        * (a.qk_nope_head_dim + a.v_head_dim)
                        + n * a.v_head_dim * h)
            elif self.attention_gate:
                attn += h * q_dim
            mamba = (h * (2 * s.inner_size + 2 * s.n_groups * s.state_size
                          + s.num_heads)
                     + (s.conv_kernel + 1) * s.conv_channels + 3 * s.num_heads
                     + s.inner_size + s.inner_size * h)
            mixer = {
                "M": mamba,
                "*": attn,
                "P": attn + mamba,      # both mixers under the one norm
                "E": h * m.router_width
                + (m.router_width if m.selection_bias else 0)
                + m.num_experts * per_expert * f
                + per_expert * m.shared_expert_size,
                "D": per_expert * (self.dense_ffn_size or f),
                # the one input projection, the conv, the two low-rank
                # pairs' second halves, A_log, dt_bias, the head norm, o
                "K": h * kd.in_proj_size + kd.conv_kernel * kd.conv_channels
                + 2 * kd.head_dim * kd.inner_size + kd.num_heads
                + kd.inner_size + kd.head_dim + kd.inner_size * h,
                # [B | C | u] in, the taps, out
                "C": 3 * h * h + self.shortconv_kernel * h + h * h,
            }
            if self.qk_norm == "head":
                mixer["*"] += 2 * self.head_dim
            # a hyper-connection a sub-layer: the maps' norm, phi, three
            # scalars, two bias vectors and a bias matrix
            nc, k = self.hc_mult * h, self.hc_mult
            hc = (nc + nc * (2 * k + k * k) + 3 + 2 * k + k * k
                  if k > 1 else 0)
            # a prediction module: one more attention and expert layer,
            # the [embedding | stream] -> hidden projection, three norms
            mtp = self.mtp_layers * (2 * h + mixer["*"] + mixer["E"]
                                     + 2 * h * h + 3 * h)
            return (v * h + sum(h + hc + mixer[k_] for k_ in self.layer_pattern)
                    + mtp + h + (0 if self.tie_word_embeddings else v * h))
        if self.activation in ("silu", "gelu"):    # gated: w_gate, w_up, w_down
            mlp_dense = 3 * h * f
        else:
            mlp_dense = 2 * h * f
        if self.is_moe:
            mlp = self.moe.num_experts * mlp_dense + h * self.moe.num_experts
        else:
            mlp = mlp_dense
        norms = (4 if self.sandwich_norm else 2) * h
        if self.qk_norm == "projection":
            norms += q_dim + kv_dim
        elif self.qk_norm == "head":
            norms += 2 * self.head_dim
        per_layer = attn + mlp + norms
        emb = v * h
        head = 0 if self.tie_word_embeddings else v * h
        final_norm = h
        # the exit gate: one output and its bias
        gate = h + 1 if self.is_looped else 0
        return emb + self.num_layers * per_layer + final_norm + gate + head

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ModelConfig":
        attn = d.get("attention", {}) or {}
        num_heads = int(_take(d, "heads", "num_heads", "num_attention_heads", default=12))
        hidden = int(_take(d, "hidden", "hidden_size", "d_model", default=768))
        activation = str(_take(d, "activation", "hidden_act",
                               "mlp_hidden_act", default="silu"))
        mla = MLAConfig.from_dict(d.get("mla") or d)
        latent = mla.kv_lora_rank > 0
        pattern = str(_take(d, "layer_pattern", "hybrid_override_pattern",
                            default=""))
        layers = int(_take(d, "layers", "num_layers", "num_hidden_layers",
                           default=12))
        linear = d.get("linear_attn_config") or {}
        solar = d.get("model_type") == "solar_open2"
        if solar and not pattern:
            # ``solar_open2`` lists, 0-INDEXED, the decoder layers that mix
            # by softmax attention (``gqa_layers``); every other one mixes
            # by delta-rule linear attention (``K``). A decoder layer is a
            # mixer entry then a feed-forward entry, as above
            gqa_at = [int(i) for i in d.get("gqa_layers") or []]
            if sorted(set(gqa_at)) != gqa_at or any(
                    not 0 <= i < layers for i in gqa_at):
                raise ConfigError(
                    f"gqa_layers {gqa_at} must name decoder layers of the "
                    f"{layers} once each, ascending (0-indexed)")
            if d.get("kda_use_full_proj"):
                raise ConfigError(
                    "kda_use_full_proj: the K layer's decay and output gate "
                    "are the two low-rank pairs; full projections are not "
                    "carried")
            if linear.get("num_kv_heads") not in (None, linear.get("num_heads")):
                raise ConfigError(
                    "linear_attn_config.num_kv_heads: the K layer keeps one "
                    "key and value head a query head (null)")
            dense = int(_take(d, "first_k_dense_replace", default=0))
            pattern = "".join(
                ("*" if i in gqa_at else "K") + ("D" if i < dense else "E")
                for i in range(layers))
            layers = len(pattern)
            linear = dict(linear, allow_neg_eigval=d.get(
                "kda_allow_neg_eigval", False))
        if latent and not pattern and "num_hidden_layers" in d:
            # a published config.json counts decoder layers: each is an
            # attention sub-layer then a feed-forward one, two entries of
            # the table; the first ``first_k_dense_replace`` feed-forwards
            # are dense MLPs, the rest experts. ``kimi_linear`` lists,
            # 1-indexed, which decoder layers mix by delta-rule linear
            # attention (``K``) and which by latent attention (``*``)
            dense = int(_take(d, "first_k_dense_replace", default=0))
            mixers = ["*"] * layers
            if linear:
                kda_at = [int(i) for i in linear.get("kda_layers", [])]
                full_at = [int(i) for i in linear.get("full_attn_layers", [])]
                if sorted(kda_at + full_at) != list(range(1, layers + 1)):
                    raise ConfigError(
                        f"linear_attn_config: kda_layers {kda_at} and "
                        f"full_attn_layers {full_at} must between them name "
                        f"each of the {layers} decoder layers once "
                        "(1-indexed)")
                mixers = ["K" if i + 1 in kda_at else "*"
                          for i in range(layers)]
            pattern = "".join(mixers[i] + ("D" if i < dense else "E")
                              for i in range(layers))
            layers = len(pattern)
        if d.get("model_type") == "falcon_h1" and not pattern:
            # every published layer is attention AND a Mamba-2 mixer under
            # one norm (``P``), then the gated MLP under a second (``D``)
            carried = {"mamba_rms_norm": True, "mamba_norm_before_gate": False,
                       "mamba_conv_bias": True, "mamba_proj_bias": False,
                       "mamba_use_mlp": True, "mlp_bias": False,
                       "projectors_bias": False, "attn_layer_indices": None}
            for key, value in carried.items():
                if d.get(key, value) != value:
                    raise ConfigError(
                        f"{key} = {d[key]!r}: falcon_h1 is carried with "
                        f"{key} {value!r} alone (the gated RMS norm gates "
                        "BEFORE it norms, the conv has a bias and no "
                        "projection has, every layer has both mixers and "
                        "the MLP)")
            pattern = "PD" * layers
            layers = len(pattern)
        lfm2 = d.get("model_type") == "lfm2_moe"
        if lfm2 and not pattern:
            # ``lfm2_moe`` names each decoder layer's mixer in ``layer_types``
            # (``conv``: the gated short convolution, ``C``;
            # ``full_attention``: ``*``); the first ``num_dense_layers``
            # feed-forwards are dense MLPs, the rest experts. A decoder
            # layer is a mixer entry then a feed-forward entry, as above
            letters = {"conv": "C", "full_attention": "*"}
            types = list(d.get("layer_types") or [])
            unknown = sorted(set(types) - set(letters))
            if unknown or len(types) != layers:
                raise ConfigError(
                    f"layer_types must name each of the {layers} decoder "
                    f"layers conv or full_attention (got {len(types)} "
                    f"entries, unknown {unknown})")
            if _parse_bool("conv_bias", d.get("conv_bias", False)):
                raise ConfigError(
                    "conv_bias = true: the C layer's conv and its two "
                    "projections are carried without a bias")
            dense = int(_take(d, "num_dense_layers", default=0))
            pattern = "".join(letters[t] + ("D" if i < dense else "E")
                              for i, t in enumerate(types))
            layers = len(pattern)
        for key in ("n_group", "topk_group", "num_expert_group"):
            if latent and int(d.get(key, 1)) != 1:
                raise ConfigError(
                    f"{key} = {d[key]}: group-limited routing is not "
                    "carried (the router takes its top-k over all experts)")
        sdar = d.get("model_type") == "sdar_moe"
        ouro = d.get("model_type") == "ouro"
        mellum = d.get("model_type") == "mellum"
        rope, window_rope = d.get("rope"), d.get("window_rope")
        rope_published = d.get("rope_scaling")
        window, layer_types = int(_take(d, "sliding_window", default=0) or 0), ()
        if "layer_types" in d and not lfm2:
            # ``mellum`` names each layer ``sliding_attention`` or
            # ``full_attention`` and keys ``rope_parameters`` by those names
            names = {"sliding_attention": "sliding", "sliding": "sliding",
                     "full_attention": "full", "full": "full"}
            unknown = sorted({str(t) for t in d["layer_types"]} - set(names))
            if unknown:
                raise ConfigError(
                    f"layer_types {unknown}: a layer of the uniform stack is "
                    "sliding_attention or full_attention")
            layer_types = tuple(names[str(t)] for t in d["layer_types"])
            if not _parse_bool("use_sliding_window",
                               d.get("use_sliding_window", True)):
                layer_types = ("full",) * len(layer_types)
        if mellum:
            if set(d.get("mlp_layer_types") or ["sparse"]) != {"sparse"}:
                raise ConfigError(
                    "mlp_layer_types: every layer of a mellum stack is "
                    "carried sparse (dense layers among a uniform stack's "
                    "expert layers are not carried)")
            by_kind = d.get("rope_parameters") or {}
            if set(by_kind) != {"full_attention", "sliding_attention"}:
                raise ConfigError(
                    "rope_parameters must hold a full_attention and a "
                    f"sliding_attention group (got {sorted(by_kind)})")
            rope_published = by_kind["full_attention"]
            window_rope = RopeConfig.from_dict(
                None, by_kind["sliding_attention"])
        if int(d.get("decoder_sparse_step", 1)) != 1:
            raise ConfigError(
                f"decoder_sparse_step = {d['decoder_sparse_step']}: every "
                "layer of the uniform stack is an expert layer here (1)")
        if d.get("mlp_only_layers"):
            raise ConfigError(
                f"mlp_only_layers = {d['mlp_only_layers']}: dense layers "
                "among a uniform stack's expert layers are not carried "
                "(state [] or give a layer_pattern)")
        mtp = int(_take(d, "mtp_layers", "num_nextn_predict_layers",
                        default=0) or 0)
        if mtp > 1:
            raise ConfigError(
                f"num_nextn_predict_layers = {mtp}: ONE next-token "
                "prediction module is served (a draft-and-verify window of "
                "two rows a slot); a chain of modules is not carried "
                "(ROADMAP B7): state 1, or 0 to serve the model without")
        if mtp and not (latent and pattern and set(pattern) <= set("*DE")
                        and "E" in pattern
                        and int(_take(d, "hc_mult", default=1)) == 1
                        and not sdar):
            raise ConfigError(
                f"num_nextn_predict_layers = {mtp}: the next-token "
                "prediction module is served for a layer table of latent "
                "attention, dense and expert layers over ONE residual "
                "stream (its layer is one more layer of the latent pool "
                "and of the expert stacks; ROADMAP B7): state 0 to serve "
                "this model without it")
        if latent:
            d = dict(d, head_dim=mla.qk_nope_head_dim + mla.qk_rope_head_dim)
        cfg = cls(
            name=str(_take(d, "name", default="custom")),
            arch=str(_take(d, "arch", default="decoder-only")),
            num_layers=layers,
            hidden_size=hidden,
            # (a model with ``moe_intermediate_size`` states ONE expert's
            # width under it)
            ffn_size=int(_take(d, "ffn", "ffn_size", "moe_intermediate_size",
                               "intermediate_size", default=4 * hidden)),
            num_heads=num_heads,
            num_kv_heads=int(_take(d, "kv_heads", "num_kv_heads", "num_key_value_heads",
                                   default=num_heads)),
            head_dim=int(_take(d, "head_dim", default=hidden // num_heads)),
            vocab_size=int(_take(d, "vocab_size", default=50304)),
            max_position_embeddings=int(_take(d, "max_position_embeddings", "max_seq_len",
                                              default=2048)),
            rope=RopeConfig.from_dict(rope, rope_published),
            sliding_window=window,
            layer_types=layer_types,
            window_rope=(window_rope if isinstance(window_rope, RopeConfig)
                         else RopeConfig.from_dict(window_rope)),
            activation=activation,
            norm_eps=float(_take(d, "layer_norm_eps", "norm_eps", "rms_norm_eps",
                                 "layer_norm_epsilon", default=1e-5)),
            tie_word_embeddings=_parse_bool("tie_word_embeddings", _take(d, "tie_word_embeddings", default=False)),
            attention_bias=_parse_bool("attention_bias", attn.get("bias", _take(d, "attention_bias", default=False))),
            dropout=float(attn.get("dropout", _take(d, "dropout", default=0.0))),
            dtype=str(_take(d, "dtype", default="bfloat16")),
            # the nested table, else the published top-level keys: a
            # config.json with ``num_experts: 64`` is never loaded dense
            # (its ``intermediate_size`` is then ONE expert's width)
            moe=MoEConfig.from_dict(d.get("moe") or d),
            # (``sdar_moe``'s config.json has no key for its per-head norms)
            # (nor has ``lfm2_moe``'s for its own)
            qk_norm=str(_take(d, "qk_norm",
                              default=("head" if sdar or lfm2 or mellum
                                       else "none"))),
            diffusion=DiffusionConfig.from_dict(d.get("diffusion") or d,
                                                published=sdar),
            layer_pattern=pattern,
            ssm=SSMConfig.from_dict(d.get("ssm"), published=d),
            kda=KDAConfig.from_dict(d.get("kda") or linear),
            shortconv_kernel=int(_take(d, "shortconv_kernel", "conv_L_cache",
                                       default=3)),
            mla=mla,
            # (a leading dense layer's width: ``intermediate_size`` beside
            # ``moe_intermediate_size`` where the file has such layers)
            dense_ffn_size=int(_take(d, "dense_ffn_size", default=(
                d.get("intermediate_size", 0)
                if d.get("first_k_dense_replace")
                or d.get("num_dense_layers") else 0))),
            hc_mult=int(_take(d, "hc_mult", default=1)),
            hc_sinkhorn_iters=int(_take(d, "hc_sinkhorn_iters", default=20)),
            hc_eps=float(_take(d, "hc_eps", default=1e-6)),
            hc_clamp_min=float(_take(d, "hc_clamp_min",
                                     "mhc_h_res_clamp_min", default=-30.0)),
            hc_clamp_max=float(_take(d, "hc_clamp_max",
                                     "mhc_h_res_clamp_max", default=30.0)),
            mup=MupConfig.from_dict(d.get("mup"), published=d),
            mtp_layers=mtp,
            num_passes=int(_take(d, "num_passes", "total_ut_steps",
                                 default=1)),
            exit_threshold=float(_take(d, "exit_threshold",
                                       "early_exit_threshold", default=1.0)),
            # (``ouro``'s config.json has no key for its four norms a layer)
            sandwich_norm=_parse_bool("sandwich_norm", _take(
                d, "sandwich_norm", default=ouro)),
            position_embedding=str(_take(
                d, "position_embedding", default=(
                    "none" if latent and d.get("mla_use_nope")
                    or d.get("use_rope") is False else "rope"))),
            attention_gate=_parse_bool("attention_gate", _take(
                d, "attention_gate", "use_gqa_gate", default=False)),
            # squared ReLU comes without a gate (``nemotron_h``'s
            # ``mlp_hidden_act: relu2``) unless the dict says otherwise
            mlp_gated=_parse_bool("mlp_gated", _take(
                d, "mlp_gated", default=activation != "relu2")),
        )
        cfg.validate()
        return cfg

    @classmethod
    def from_published(cls, config: dict[str, Any]) -> "ModelConfig":
        """A model's published ``config.json`` (as the files under
        ``benchmark/configs/`` hold it): its scalar keys, ``rope_theta`` and
        the ``rope_scaling`` group; every other group (``serve``, ``reduced``
        ...) is not the model's."""
        d = {k: v for k, v in config.items()
             if not isinstance(v, (dict, list))}
        d["rope"] = {"base": config.get("rope_theta", 10000.0)}
        for group in ("rope_scaling", "linear_attn_config",
                      "mlp_only_layers", "gqa_layers", "ssm_multipliers",
                      "mlp_multipliers", "attn_layer_indices",
                      "layer_types", "mlp_layer_types", "rope_parameters"):
            if config.get(group):
                d[group] = config[group]
        return cls.from_dict(d)

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        return d


@dataclass
class SchedulerConfig:
    type: str = "cosine"            # cosine | linear | constant
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "SchedulerConfig":
        if not d:
            return cls()
        return cls(
            type=str(_take(d, "type", default="cosine")),
            warmup_steps=int(_take(d, "warmup_steps", "warmup", default=100)),
            total_steps=int(_take(d, "total_steps", default=10000)),
            min_lr_ratio=float(_take(d, "min_lr_ratio", default=0.1)),
        )


@dataclass
class OptimizerConfig:
    """AdamW + schedule (parity: reference engine.py:217-256, preset [optimizer])."""
    type: str = "adamw"
    lr: float = 3e-4
    betas: tuple[float, float] = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    # dtype of Adam's first moment (mu). bfloat16 halves that buffer
    # (~1.5 GB freed at gpt-750m) — mu is a smoothed gradient, bf16's ~3
    # decimal digits suffice; the variance (nu) stays fp32 (rsqrt is
    # precision-sensitive). Measured +0.035 MFU at gpt-750m b4 (BASELINE.md
    # round-2 sweep; batch 6 still OOMs by ~632 MB even with bf16 mu).
    moment_dtype: str = "float32"
    # dtype of Adam's second moment (nu). bf16 frees another ~1.45 GB at
    # gpt-750m — HBM that buys less rematerialisation or a bigger batch.
    # Unlike mu, nu feeds an rsqrt, so bf16 storage costs ~0.4% relative
    # error on the adaptive scale; the update still COMPUTES in fp32 and
    # only stores rounded (loss-trajectory equivalence asserted in
    # tests/test_exec.py). Requires fused=True (optax scale_by_adam has no
    # nu_dtype; only the fused kernel controls nu storage).
    nu_dtype: str = "float32"
    # fused clip+update (exec/fused_update.py): one pass over HBM per leaf
    # instead of optax's materialised clipped-grads + updates trees.
    # Numerically identical to the optax chain (tests/test_exec.py);
    # applies to adamw/adam only, other types fall back to optax.
    fused: bool = True
    # dtype of the gradient-accumulation carry (train_step's scanned
    # grads_acc — a full params-sized tree resident for the whole step
    # whenever gradient_accumulation_steps > 1). bfloat16 halves it
    # (~2.45 GB at the gpt-7b-4l shape, where the fp32 carry OOM'd the
    # b2 x accum rows by 3.85 GB). Cost: summing N microbatch grads in
    # bf16 loses ~log2(N)/256 relative precision on the mean — the same
    # concession as moment_dtype, applied one stage earlier; clip and
    # the optimizer update still COMPUTE in fp32.
    accum_dtype: str = "float32"
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)

    def validate(self) -> None:
        if self.accum_dtype not in ("float32", "bfloat16"):
            raise ConfigError("accum_dtype must be float32|bfloat16")
        if self.type not in ("adamw", "adam", "sgd", "adafactor", "lion"):
            raise ConfigError(f"unknown optimizer {self.type!r}")
        if not (0 < self.lr < 1):
            raise ConfigError(f"suspicious learning rate {self.lr}")
        if self.moment_dtype not in ("float32", "bfloat16"):
            raise ConfigError("moment_dtype must be float32|bfloat16")
        if self.nu_dtype not in ("float32", "bfloat16"):
            raise ConfigError("nu_dtype must be float32|bfloat16")
        if self.nu_dtype != "float32" and not (
                self.fused and self.type in ("adamw", "adam")):
            raise ConfigError(
                "nu_dtype=bfloat16 requires fused adamw/adam (the optax "
                "chain cannot store nu in bf16)")

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "OptimizerConfig":
        if not d:
            return cls()
        betas = _take(d, "betas", default=(0.9, 0.95))
        cfg = cls(
            type=str(_take(d, "type", default="adamw")),
            lr=float(_take(d, "lr", "learning_rate", default=3e-4)),
            betas=(float(betas[0]), float(betas[1])),
            eps=float(_take(d, "eps", default=1e-8)),
            weight_decay=float(_take(d, "weight_decay", default=0.1)),
            grad_clip=float(_take(d, "grad_clip", "gradient_clipping", default=1.0)),
            moment_dtype=str(_take(d, "moment_dtype", default="float32")),
            nu_dtype=str(_take(d, "nu_dtype", default="float32")),
            fused=_parse_bool("fused", _take(d, "fused", default=True)),
            accum_dtype=str(_take(d, "accum_dtype", default="float32")),
            scheduler=SchedulerConfig.from_dict(d.get("scheduler")),
        )
        cfg.validate()
        return cfg


@dataclass
class ParallelConfig:
    """Parallelism plan — the mesh axes.

    Mirrors the reference's ``[parallel]`` table
    (reference init.py:132-141, preset llama-7b-a100x8.toml:32-41) but every
    field here is *executed* (mesh construction in parallel/mesh.py), not
    planned-only. ``sequence_parallel`` is an int degree (the reference's
    dead bool, SURVEY §5.7, becomes a real context-parallel axis).
    """
    strategy: str = "auto"          # auto | manual
    data_parallel: int = 1
    fsdp: int = 1                   # ZeRO-3-style param sharding axis
    tensor_parallel: int = 1
    pipeline_parallel: int = 1
    sequence_parallel: int = 1      # context parallel (ring attention) degree
    expert_parallel: int = 1
    zero_stage: int = 0             # 0..3 (1 = shard optimizer state only)
    activation_checkpoint: str = "selective"   # none | selective | full
    micro_batch_size: int = 1
    global_batch_size: int = 8
    gradient_accumulation_steps: int = 1
    num_microbatches: int = 1       # pipeline microbatches per step
    # gpipe: autodiff-through-scan (activation memory grows with
    # num_microbatches); 1f1b: interleaved fwd/bwd schedule with a
    # constant-size stage-input ring (memory independent of M) — the
    # BASELINE config-3 schedule
    pipeline_schedule: str = "1f1b"

    def validate(self) -> None:
        for f_ in ("data_parallel", "fsdp", "tensor_parallel", "pipeline_parallel",
                   "sequence_parallel", "expert_parallel"):
            if getattr(self, f_) < 1:
                raise ConfigError(f"{f_} must be >= 1")
        if self.zero_stage not in (0, 1, 2, 3):
            raise ConfigError("zero_stage must be 0..3")
        if self.activation_checkpoint not in ("none", "selective",
                                              "selective_attn", "full"):
            raise ConfigError(
                "activation_checkpoint must be none|selective|selective_attn|full")
        if self.pipeline_parallel > 1 and self.num_microbatches < self.pipeline_parallel:
            raise ConfigError(
                "num_microbatches must be >= pipeline_parallel for a full pipeline")
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            raise ConfigError("pipeline_schedule must be gpipe|1f1b")
        if self.zero_stage == 3 and self.fsdp <= 1:
            # stage-3 (fully-sharded params) IS the fsdp mesh axis here; a
            # bare zero_stage=3 would silently behave as stage 1
            raise ConfigError(
                "zero_stage=3 means fully-sharded parameters, which this "
                "framework expresses as the fsdp mesh axis: set fsdp>1 "
                "(optimizer-state sharding alone is zero_stage=1; gradient "
                "reduce-scatter (stage 2) is inserted by XLA from the "
                "stage-1 shardings)")

    @property
    def total_devices(self) -> int:
        return (self.data_parallel * self.fsdp * self.tensor_parallel *
                self.pipeline_parallel * self.sequence_parallel * self.expert_parallel)

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "ParallelConfig":
        if not d:
            return cls()
        sp = _take(d, "sequence_parallel", "context_parallel", default=1)
        if isinstance(sp, bool):    # reference's dead bool flag
            sp = 1
        cfg = cls(
            strategy=str(_take(d, "strategy", default="auto")),
            data_parallel=int(_take(d, "data_parallel", "dp", default=1)),
            fsdp=int(_take(d, "fsdp", default=1)),
            tensor_parallel=int(_take(d, "tensor_parallel", "tp", default=1)),
            pipeline_parallel=int(_take(d, "pipeline_parallel", "pp", default=1)),
            sequence_parallel=int(sp),
            expert_parallel=int(_take(d, "expert_parallel", "ep", default=1)),
            zero_stage=int(_take(d, "zero_stage", default=0)),
            activation_checkpoint=str(_take(d, "activation_checkpoint", default="selective")),
            micro_batch_size=int(_take(d, "micro_batch_size", default=1)),
            global_batch_size=int(_take(d, "global_batch_size", default=8)),
            gradient_accumulation_steps=int(_take(d, "gradient_accumulation_steps", default=1)),
            num_microbatches=int(_take(d, "num_microbatches",
                                       default=_take(d, "pipeline_parallel", "pp", default=1))),
        )
        cfg.validate()
        return cfg


@dataclass
class DataConfig:
    """Dataset streaming (reference's [data] table, preset :16-22).

    The reference ignores dataset_path and trains on a hardcoded dummy
    (defect SURVEY §2.4.4, engine.py:147-171); here train/val paths point at
    tokenized .bin shards consumed by io/data.py, with a synthetic fallback.
    """
    train: str = "synthetic"
    val: str = "synthetic"
    tokenizer: str = "gpt2"
    max_length: int = 2048
    pack_sequences: bool = True
    num_workers: int = 2
    prefetch_factor: int = 2
    seed: int = 0

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "DataConfig":
        if not d:
            return cls()
        return cls(
            train=str(_take(d, "train", "train_path", "dataset_path", default="synthetic")),
            val=str(_take(d, "val", "val_path", default="synthetic")),
            tokenizer=str(_take(d, "tokenizer", default="gpt2")),
            max_length=int(_take(d, "max_length", "seq_len", default=2048)),
            pack_sequences=_parse_bool("pack_sequences", _take(d, "pack_sequences", default=True)),
            num_workers=int(_take(d, "num_workers", default=2)),
            prefetch_factor=int(_take(d, "prefetch_factor", default=2)),
            seed=int(_take(d, "seed", default=0)),
        )


@dataclass
class CheckpointConfig:
    """Sharded/async checkpointing — real, unlike the reference's aspiration
    (init.py:147-152 promises sharded/async; engine.py:363-394 is sync
    whole-model; defect SURVEY §2.4.9)."""
    path: str = "checkpoints"
    interval_steps: int = 1000
    sharded: bool = True
    async_save: bool = True
    keep_latest: int = 5

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "CheckpointConfig":
        if not d:
            return cls()
        return cls(
            path=str(_take(d, "path", default="checkpoints")),
            interval_steps=int(_take(d, "interval_steps", "save_interval", default=1000)),
            sharded=_parse_bool("sharded", _take(d, "sharded", default=True)),
            async_save=_parse_bool("async_save", _take(d, "async", "async_save", default=True)),
            keep_latest=int(_take(d, "keep_latest", "save_total_limit", default=5)),
        )


@dataclass
class TrainingConfig:
    """Top-level training run config (reference TrainingConfig engine.py:30-70
    + [training] table preset :55-61)."""
    max_steps: int = 1000
    eval_interval: int = 500
    save_interval: int = 1000
    log_interval: int = 10
    seed: int = 42
    mixed_precision: str = "bf16"   # bf16 | fp32
    deterministic: bool = False
    profile: bool = False
    profile_dir: str = "traces"
    eval_steps: int = 20            # batches per eval
    attn_impl: str = "auto"         # auto | xla | flash | ring | ulysses

    def validate(self) -> None:
        if self.mixed_precision not in ("bf16", "fp32", "no"):
            raise ConfigError("mixed_precision must be bf16|fp32|no")
        if self.attn_impl not in ("auto", "xla", "flash", "ring", "ulysses"):
            raise ConfigError("attn_impl must be auto|xla|flash|ring|ulysses")

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "TrainingConfig":
        if not d:
            return cls()
        cfg = cls(
            max_steps=int(_take(d, "max_steps", default=1000)),
            eval_interval=int(_take(d, "eval_interval", default=500)),
            save_interval=int(_take(d, "save_interval", default=1000)),
            log_interval=int(_take(d, "log_interval", default=10)),
            seed=int(_take(d, "seed", default=42)),
            mixed_precision=str(_take(d, "mixed_precision", default="bf16")),
            deterministic=_parse_bool("deterministic", _take(d, "deterministic", default=False)),
            profile=_parse_bool("profile", _take(d, "profile", default=False)),
            profile_dir=str(_take(d, "profile_dir", default="traces")),
            eval_steps=int(_take(d, "eval_steps", default=20)),
            attn_impl=str(_take(d, "attn_impl", "attention_impl", default="auto")),
        )
        cfg.validate()
        return cfg


@dataclass
class HardwareConfig:
    """A hardware profile (reference [hardware]/[limits] + hw probe output,
    reference hw.py:133-282) reshaped for TPU: chips not GPUs, ICI/DCN not
    NVLink/IB."""
    platform: str = "tpu"           # tpu | cpu (fake mesh)
    chip_type: str = "v5e"
    num_chips: int = 1
    num_hosts: int = 1
    hbm_gb_per_chip: float = 16.0
    peak_bf16_tflops: float = 197.0     # v5e MXU peak
    hbm_bw_gbps: float = 819.0          # v5e HBM bandwidth GB/s
    ici_bw_gbps: float = 186.0          # per-link ICI bandwidth GB/s (v5e 1.86e11 * ?)
    dcn_bw_gbps: float = 25.0
    topology: str = ""                  # e.g. "2x4", "16x16"

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "HardwareConfig":
        if not d:
            return cls()
        return cls(
            platform=str(_take(d, "platform", default="tpu")),
            chip_type=str(_take(d, "chip_type", "chip", "gpu", default="v5e")),
            num_chips=int(_take(d, "num_chips", "chips", "gpu_count", "gpus_per_node", default=1)),
            num_hosts=int(_take(d, "num_hosts", "nodes", default=1)),
            hbm_gb_per_chip=float(_take(d, "hbm_gb_per_chip", "memory_gb", default=16.0)),
            peak_bf16_tflops=float(_take(d, "peak_bf16_tflops", default=197.0)),
            hbm_bw_gbps=float(_take(d, "hbm_bw_gbps", default=819.0)),
            ici_bw_gbps=float(_take(d, "ici_bw_gbps", "intra_node_bw", default=186.0)),
            dcn_bw_gbps=float(_take(d, "dcn_bw_gbps", "inter_node_bw", default=25.0)),
            topology=str(_take(d, "topology", default="")),
        )


@dataclass
class ServeConfig:
    """Inference server config (reference serve/server.py:253-284 ctor args,
    plus paged-KV parameters the reference lacks)."""
    model: str = "gpt-125m"
    artifact: str = ""              # checkpoint dir
    host: str = "0.0.0.0"
    port: int = 8080
    max_batch_size: int = 8
    max_seq_len: int = 2048
    # the finest step of the prefill bucket ladder: a cold prompt is padded
    # to the next of c, 2c, 4c, then two rungs an octave (6c, 8c, 12c, ...),
    # c being this rounded up to a page (serve/engine.py _bucket)
    prefill_chunk: int = 256
    # max prompt tokens ADMITTED between two decode dispatches. Under half
    # occupancy they are prefilled there, by programs of their own, and
    # this bounds the inter-token stall resident streams see during a
    # long-prompt burst. While at least half the slots are resident the
    # admitted prompts are not prefilled between dispatches at all: their
    # rows ride the decode steps (serve/engine.py _rides), and this then
    # only bounds how many prompts one admission round queues to ride.
    prefill_budget_tokens: int = 2048
    # chunked prefill: prompts longer than this prefill in chunks of this
    # many tokens, one chunk per engine step, interleaved with decode — a
    # single 32k prompt can no longer stall resident streams for its whole
    # prefill. 0 disables (whole-prompt single-dispatch prefill). It
    # governs the prompts that take a program of their own: an idle or
    # lightly loaded engine, and the engines that never ride (a layer
    # table, quantized weights, tensor_parallel > 1, speculation, the
    # static scheduler). A prompt that rides is chunked by construction,
    # InferenceEngine.RIDE_PAGES pages a decode step, whatever this says.
    chunked_prefill_tokens: int = 0
    # decode iterations fused into one device dispatch (lax.scan): each
    # dispatch pays one host round trip for K tokens. Finished requests
    # waste at most K-1 trailing iterations; admission happens between
    # dispatches, so K also bounds admission latency in decode steps.
    decode_steps_per_dispatch: int = 8
    # latency-adaptive dispatch: while an ADMISSIBLE request waits in the
    # queue, the next decode dispatch is ONE unit of min(this, K-1)
    # steps so a prefill slot opens sooner — an arrival landing just
    # after a K=8 dispatch began otherwise waits out the whole
    # ~K*step_time window. Splitting a dispatch is bitwise-identical
    # output (same per-step program, PRNG folded by position). 0
    # disables; values >= K clamp to K-1 (never a silent no-op); K = 1
    # has nothing to shrink.
    #
    # ROUND-5 REDESIGN: there is no second compiled program. The decode
    # executable is one L-step unit; a full dispatch chains ceil(K/L)
    # units on the device-resident carry with a single batched fetch.
    # The round-4 "-18% goodput with zero short dispatches firing" tax
    # was executable switching (274 XLA recompile events caught in one
    # diagnosed run) and is structurally gone (re-measured: ON runs
    # show compiles_in_run == 0). The REMAINING cost of enabling is
    # real per-unit launch overhead at saturation: ceil(K/L) device
    # program launches per group instead of one (measured ~20% at the
    # 1B c8 cell with L=2 -> 4 units). Pick L >= K/2 (2 units) to bound
    # it; the feature's regime is LIGHT-load TTFT on long-dispatch-
    # window models (7B: K=8 windows are ~300 ms device), where the
    # occupancy gate fires shortening and per-unit overhead is noise.
    # DEFAULT OFF: saturation-focused deployments lose, light-load
    # 7B-class deployments should enable with L = K/2.
    latency_dispatch_steps: int = 0
    # pipelined decode: keep ONE un-fetched dispatch group in flight and
    # chain the next dispatch on its device-resident scan carry, so the
    # per-dispatch host round trip overlaps device execution instead of
    # serialising with it (dispatch + sync cost per dispatch; not
    # re-measured on a directly attached chip). Engages only at
    # >= half-full batches (chained pairs delay an arrival's prefill
    # window by up to 2K steps — the light-load TTFT regime belongs to
    # latency_dispatch_steps, the saturation regime to this). Chains
    # break on any slot (re)arm; output is bitwise identical (same
    # per-step program, same PRNG fold). DEFAULT ON since round 5:
    # measured +20% saturation goodput at gpt-1b (171.9/183.0 vs
    # 141.6/154.4 tok/s interleaved), +25% at gpt-7b int8 (145.3 vs
    # 116.4), with light-load p50 TTFT unchanged (the occupancy gate —
    # 185.3 ms device vs 182-184 unpipelined at 7B) and p99 improved.
    pipelined_decode: bool = True
    # tokens per KV-cache page. 0 = by the model's rows, as kv_num_blocks 0
    # is "from the HBM budget": the smallest power of two, at least 64 (a
    # [64, D] DMA tile a head; 16-token pages measured 2.4x slower), whose
    # K + V copy for ONE layer, as stored (kv heads x head width x item
    # size x 2; a quantised pool's values and scales; a latent pool's one
    # row), reaches serve/kv_cache.py PAGE_COPY_BYTES (512 KB: a page
    # kernel's copy costs max(~0.47 us, bytes / ~750 GB/s), so a smaller
    # page pays for bytes it does not move: PERF.md 6, PR 58), and at most
    # a decode step's carry (InferenceEngine.RIDE_ROWS, 128). bf16 pages of
    # 128-wide heads: up to 8 kv heads 128 tokens, 16 and over 64. The
    # engine resolves it once and writes the size back here. A stated size
    # is used as it is. What follows from a larger page: the prefix cache
    # hashes WHOLE pages, so a hit re-computes up to page-1 tokens of a
    # shared prefix (127, not 63), and a sequence's last page wastes page/2
    # tokens of the pool on average (64, not 32)
    kv_block_size: int = 0
    kv_num_blocks: int = 0          # 0 = auto-size from HBM budget
    kv_hbm_budget_gb: float = 4.0
    max_queue: int = 256
    dtype: str = "bfloat16"
    scheduler: str = "continuous"   # continuous | static
    # CORS for browser clients (reference serve/server.py:276-282 installs
    # an allow-all CORSMiddleware): "*" = any origin, a comma-separated
    # origin list restricts, "" disables the middleware entirely
    cors_origins: str = "*"
    temperature: float = 1.0
    # speculative decoding: "off" | "ngram" (host prompt-lookup drafts,
    # device verification — serve/speculative.py) | "mtp" (a model with a
    # next-token prediction module, ``ModelConfig.mtp_layers``, drafts for
    # itself: every decode step verifies the slot's draft and makes the
    # next one, 1 or 2 tokens a slot a step; ``speculative_tokens`` is then
    # 1 + the module count whatever is stated, and a
    # ``speculative_min_acceptance`` of 0 keeps the mechanism on whatever
    # the acceptance; serve/decode.py ``draft_verify_scan``). Greedy
    # requests under "ngram" accept
    # up to speculative_tokens-1 drafts + 1 bonus token per dispatch; the
    # acceptance rule is draft == argmax of the verify-pass logits, so the
    # output is always a valid greedy chain regardless of draft quality
    # (bitwise-identical to plain decode up to bf16 tiling ties — see
    # serve/speculative.py module docstring).
    speculative: str = "off"
    speculative_tokens: int = 8     # verify window T (drafts = T-1)
    speculative_ngram: int = 3      # longest n-gram tried by the proposer
    # adaptive kill switch: after 64 dispatches, if the measured draft
    # acceptance is below this, the engine falls back to plain multi-step
    # decode for the rest of its life (the verify window costs ~9
    # decode-steps, BASELINE.md round 2 — low acceptance means the spec
    # path is a pure loss)
    speculative_min_acceptance: float = 0.05
    # automatic prefix caching: full prompt pages are content-hashed and
    # shared read-only between requests (refcounted, LRU-evicted when the
    # allocator runs dry). A hit skips that prefix's prefill compute —
    # shared-system-prompt workloads see near-zero marginal TTFT.
    prefix_caching: bool = True
    # prefix reuse THROUGH a recurrent state (a model with ``K`` layers):
    # entries of the snapshot pool, each a slot's rows of the state pools
    # as they stood at a prompt's last whole page boundary. A page hit is
    # followed as far as a snapshot stands; the slot is armed from the
    # entry and the prompt prefilled from there. 0: no pool, and such a
    # model's prefix reuse stays off (serve/kv_cache.py ``REFUSED``)
    state_snapshot_entries: int = 0
    # Megatron-style tensor-parallel serving over a tp mesh axis: params
    # shard per parallel.sharding.PARAM_RULES, KV pages shard over the
    # kv-head axis, GSPMD inserts the per-layer collectives. Requires
    # num_kv_heads % tensor_parallel == 0 and that many local devices.
    tensor_parallel: int = 1
    # weight-only quantized serving: block kernels are stored int8
    # (W8A16, ~2x block memory freed) or group-wise int4 / int4-awq
    # (W4A16, ~4x; awq = activation-aware channel scaling from a
    # synthetic calibration pass) and dequantized one layer at a time
    # inside the forward scan. Embeddings and lm_head stay bf16
    # (quantizing the tied unembed costs the most output quality for the
    # least memory). Composes with tensor_parallel (param_specs shards
    # the quantized leaves like the kernels they replace).
    quantization: str = "none"      # none | int8 | int4 | int4-awq
    # route int8 decode matmuls through the in-kernel-dequant Pallas
    # kernel (ops.int8_matmul_pallas) instead of XLA's fused dequant.
    # DEFAULT OFF: unlike int4 (whose unpack chain defeats XLA fusion —
    # the Pallas kernel is a measured 12x win, battery 13), int8 dequant
    # DOES fuse (int8-xla streamed 384 GB/s vs bf16's 555 in the same
    # battery), so the kernel must beat fused-XLA on chip before it can
    # default on. Single-device only (Pallas is opaque to GSPMD — the
    # tp>1 engine forces the dequant path like it does for attention).
    int8_pallas_matmul: bool = False
    # quantized KV cache: "int8" stores pages int8 with per-token absmax
    # scales (~3% overhead at D=128) — 2x KV capacity per HBM byte and
    # half the decode-attention KV streaming; "int4" packs two page
    # slots per byte along the slot axis with the SAME per-token scale
    # tile — 4x capacity / quarter the streaming (2x decode slots per
    # HBM byte over int8), at a larger quality cost (see USER_GUIDE "KV
    # quantization: int8 vs int4"). Dequant happens in VMEM inside the
    # paged-attention kernels. int4 needs an even kv_block_size.
    kv_quantization: str = "none"   # none | int8 | int4
    # KV admission policy:
    #   ondemand — reserve only the prompt (+ one dispatch of decode
    #     lookahead) at admission; grow the page chain as decode advances
    #     and PREEMPT the newest resident request (vLLM-style recompute,
    #     re-prefilling from prefix-cached pages where possible) when the
    #     pool runs dry. Strictly higher sustained concurrency for the
    #     same KV budget (BASELINE.md round-3 load table).
    #   reserve — round-2 policy: reserve prompt+max_tokens up front;
    #     decode can never OOM, but worst-case-sized reservations strand
    #     capacity that requests finishing early never use.
    admission: str = "ondemand"
    # what eviction does with a preempted request's KV (ondemand only):
    #   recompute — drop the pages and re-prefill prompt+generated on
    #     readmission (cheap when prefix caching still holds the pages;
    #     zero host memory)
    #   swap — copy the slot's pages to HOST memory and write them back on
    #     readmission: no re-prefill compute at all. Wins when
    #     host<->device bandwidth beats re-prefill FLOPs (co-located
    #     hosts, long contexts); falls back to recompute if the pool
    #     can't hold the restore.
    preemption: str = "recompute"
    # host-memory budget for swapped-out KV (preemption=swap): above it,
    # further evictions fall back to recompute (vLLM's swap_space analog
    # — unbounded host copies would grow with queue depth x context)
    swap_space_gb: float = 4.0
    # single-server SSE: when the client disconnects mid-stream, abort
    # the orphaned request (free its slot + KV pages) instead of letting
    # it decode to max_tokens for nobody. Off = old behavior (the
    # request runs to completion; only the stream entry is dropped).
    # The FLEET front never aborts on disconnect — its stream log keeps
    # the tail replayable for a Last-Event-ID reconnect instead.
    stream_abort_on_disconnect: bool = True

    def validate(self) -> None:
        if self.kv_quantization not in ("none", "int8", "int4"):
            raise ConfigError("kv_quantization must be none|int8|int4")
        if self.kv_block_size < 0:
            raise ConfigError("kv_block_size must be >= 0 (0 = by the "
                              "model's rows)")
        if self.kv_quantization == "int4" and self.kv_block_size % 2:
            raise ConfigError(
                f"kv_quantization=int4 packs two page slots per byte; "
                f"kv_block_size {self.kv_block_size} must be even")
        if self.tensor_parallel < 1:
            raise ConfigError("tensor_parallel must be >= 1")
        if self.quantization not in ("none", "int8", "int4", "int4-awq"):
            raise ConfigError("quantization must be none|int8|int4|int4-awq")
        if self.chunked_prefill_tokens < 0:
            raise ConfigError("chunked_prefill_tokens must be >= 0")
        if self.state_snapshot_entries < 0:
            raise ConfigError("state_snapshot_entries must be >= 0")
        if self.latency_dispatch_steps < 0:
            raise ConfigError("latency_dispatch_steps must be >= 0")
        # quantized + tensor_parallel is supported for int8 AND int4:
        # param_specs shards Quant[4]Tensor leaves like the kernels they
        # replace (the int4 packed layout is kernel-oriented [L, in/2, out]
        # and takes the kernel spec directly) — equivalence in
        # tests/test_tp_serve.py
        # the engine checks `speculative == "ngram"`, so a config-file typo
        # ("n-gram", "medusa") would otherwise silently disable speculation
        if self.speculative not in ("off", "ngram", "mtp"):
            raise ConfigError(
                f"speculative must be off|ngram|mtp, got "
                f"{self.speculative!r}")
        if self.speculative != "off" and self.speculative_tokens < 2:
            raise ConfigError("speculative_tokens must be >= 2")
        if self.scheduler not in ("continuous", "static"):
            raise ConfigError("scheduler must be continuous|static")
        if self.admission not in ("ondemand", "reserve"):
            raise ConfigError("admission must be ondemand|reserve")
        if self.preemption not in ("recompute", "swap"):
            raise ConfigError("preemption must be recompute|swap")

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "ServeConfig":
        if not d:
            return cls()
        kw = {}
        for f_ in dataclasses.fields(cls):
            if f_.name in d:
                if isinstance(f_.default, bool):
                    # bool before the generic coercion: bool is an int
                    # subclass and type(True)("false") is True (ADVICE r2)
                    kw[f_.name] = _parse_bool(f_.name, d[f_.name])
                elif f_.default is not None:
                    kw[f_.name] = type(f_.default)(d[f_.name])
                else:
                    kw[f_.name] = d[f_.name]
        cfg = cls(**kw)
        cfg.validate()
        return cfg


def parse_fleet_endpoints(value) -> dict[int, str]:
    """Normalize a fleet endpoint map to {replica_id: base_url}.

    Accepts the three spellings operators actually produce: a dict with
    string or int keys (the TOML table ``[fleet.fleet_endpoints]``), a
    sequence of ``"id=url"`` strings (the repeated ``--fleet-endpoint``
    CLI flag), or one comma-separated ``"id=url,id=url"`` string. Raises
    :class:`ConfigError` (a ValueError) on malformed entries so a typo
    fails at config time, not at first KV ship."""
    if not value:
        return {}
    items: list[tuple[object, object]] = []
    if isinstance(value, dict):
        items = list(value.items())
    else:
        if isinstance(value, str):
            value = [p for p in value.split(",") if p.strip()]
        for entry in value:
            if not isinstance(entry, str) or "=" not in entry:
                raise ConfigError(
                    f"fleet endpoint entries must be 'replica=url', "
                    f"got {entry!r}")
            rid, _, url = entry.partition("=")
            items.append((rid, url))
    out: dict[int, str] = {}
    for rid, url in items:
        rid_s = str(rid).strip()
        if rid_s.lower() == "store":
            # the networked KV store service rides the endpoint map
            # under the KV_STORE_OWNER sentinel (-1) — "store=URL" is
            # the operator spelling (serve/fleet/store_service.py)
            key = -1
        else:
            try:
                key = int(rid_s)
            except ValueError:
                raise ConfigError(
                    f"fleet endpoint replica id must be an integer "
                    f"or 'store', got {rid!r}")
        url = str(url).strip().rstrip("/")
        if not url.startswith(("http://", "https://")):
            raise ConfigError(
                f"fleet endpoint for replica {key} must be an http(s) "
                f"base URL, got {url!r}")
        if key in out:
            raise ConfigError(
                f"duplicate fleet endpoint for replica {key}")
        out[key] = url
    return out


@dataclasses.dataclass
class FleetConfig:
    """Serve-fleet control plane (serve/fleet/): N engine replicas behind a
    router + supervisor. The per-replica engine is configured by ServeConfig;
    this layer only decides WHERE a request runs and what happens when a
    replica dies (Llumnix-style request-level rerouting above Orca-style
    iteration-level scheduling — PAPERS.md)."""
    replicas: int = 1
    # -- supervisor ----------------------------------------------------------
    probe_interval_s: float = 0.5   # health-probe cadence
    probe_failures: int = 3         # consecutive probe misses before the
    #                                 replica is declared dead and drained
    restart_backoff_s: float = 0.5  # first restart delay; doubles per
    #                                 consecutive restart of the same replica
    restart_backoff_max_s: float = 30.0
    max_restarts: int = 0           # 0 = unlimited
    # -- router --------------------------------------------------------------
    # prefix-affinity: requests whose first `affinity_prefix_tokens` tokens
    # hash to the same digest route to the same replica (consistent hashing
    # over `affinity_vnodes` ring points per replica), so each replica's
    # prefix cache stays hot for its share of the prompt population. 0
    # disables affinity (pure least-outstanding-tokens).
    affinity_prefix_tokens: int = 64
    affinity_vnodes: int = 32
    # affinity yields to load balance once the ring owner's queue is this
    # many requests deeper than the least-loaded replica's (a hot prefix
    # must not melt one replica while others idle)
    affinity_max_imbalance: int = 4
    # -- admission / backpressure -------------------------------------------
    # fleet-wide bound on queued-but-not-resident requests (sum over
    # replica queues + parked requeues). Above it, submissions are
    # rejected with 429 + Retry-After instead of growing tail latency.
    max_pending: int = 512
    retry_after_s: float = 1.0      # Retry-After hint on 429
    # per-request requeue budget (crash/drain rerouting); above it the
    # request fails loudly instead of ping-ponging between dying replicas
    max_requeues: int = 3
    # -- KV migration (serve/fleet/migration.py) ------------------------------
    # drain moves resident sequences to survivors WITH their paged KV
    # (two-phase copy: full pages pre-copied while decode continues, only
    # the partial tail stop-and-copied), so the destination restores pages
    # and resumes decode — zero re-prefill. Off = PR-2 behaviour: victims
    # re-prefill prompt+generated on the survivor.
    migrate_on_drain: bool = True
    # proactive rebalancing: when (hottest - coldest) outstanding tokens
    # exceed this fraction of the hottest replica's load for
    # `rebalance_poll_hysteresis` consecutive supervisor polls, the
    # longest-remaining resident sequences migrate hot -> cold. 0 disables
    # (placement bias on new requests remains the only balancing force).
    rebalance_imbalance_ratio: float = 0.0
    rebalance_poll_hysteresis: int = 3
    # fleet-wide bound on concurrently in-flight migrations: each one
    # holds a host-side page copy and steals a source step boundary, so
    # unbounded migration under churn would thrash instead of balance
    max_concurrent_migrations: int = 2
    # -- disaggregated prefill/decode (DistServe/Splitwise — PAPERS.md) ------
    # comma-separated per-replica roles, e.g. "prefill,decode" (must name
    # one role per replica). Empty = every replica "mixed" (classic
    # fleet). New requests route only to prefill-capable replicas
    # (prefill|mixed); when a prefill-role replica finishes a prompt's
    # prefill, the sequence leaves WITH its KV over the migration courier
    # to the least-outstanding-tokens decode-capable replica — the
    # degenerate one-phase migration (every page full and immutable) —
    # and decodes there with zero prefill compute. When no decode pool
    # has room the source decodes locally instead (handoff is an
    # optimization, never a correctness dependency). Needs at least one
    # prefill-capable replica: a decode-only fleet could admit nothing.
    roles: str = ""
    # role balancer: when the average prefill-replica queue depth exceeds
    # ratio * (average decode-replica queue depth + 1) for `hysteresis`
    # consecutive supervisor polls — or vice versa (decode-slot pressure
    # shows up as handoff backlog in decode queues: handoffs only queue
    # when every slot is busy) — the least-loaded replica of the
    # over-provisioned class is drained (with migration, so its
    # residents move out losslessly) and re-roled. 0 disables. The
    # floors keep at least this many replicas per role class so the
    # balancer can never starve a phase entirely.
    role_balance_ratio: float = 0.0
    role_balance_poll_hysteresis: int = 3
    role_min_prefill: int = 1
    role_min_decode: int = 1
    # crash-promoted mixed replicas (role-aware health) demote back to
    # their provisioned role once the crashed class is healthy again for
    # this many consecutive supervisor polls. 0 disables auto-demotion
    # (promotions then stay until the operator re-splits, PR-4 behavior).
    role_restore_hysteresis: int = 3
    # -- courier transport (serve/fleet/transport.py) ------------------------
    # every migration / handoff / salvaged-partial payload crosses the
    # courier: framed into <= courier_chunk_bytes chunks (CRC32 each,
    # whole-payload CRC verified end-to-end), per-chunk deadline, lost or
    # corrupt chunks retried with doubling backoff for up to
    # courier_max_retries resend rounds (ONLY missing chunks resend —
    # resumable transfer). A transfer that exhausts the budget drops the
    # payload and the destination re-prefills from tokens: degraded,
    # never wrong. "inproc" delivers within this process (threaded
    # replicas, byte-for-byte what PR-3/4 shipped); "http" POSTs chunks
    # to courier_endpoint's /fleet/courier/chunk (cross-host movement).
    courier_transport: str = "inproc"
    # wire codec for courier payloads (CacheGen-style, PAPERS.md):
    # "none" ships raw bytes (wire-compatible with prior PRs); "zlib"
    # deflates each chunk; "delta-zlib" additionally delta-encodes
    # quantized KV page planes along the token axis before deflate
    # (adjacent tokens' int8/int4 values are strongly correlated, so
    # deltas compress 2-4x where raw pages barely deflate). Compression
    # is per-chunk and pipelined (chunk k+1 deflates while k is on the
    # wire), decode-side CRCs verify the compressed frame AND the raw
    # payload, and a receiver that does not speak the declared codec
    # rejects the transfer loudly — a codec bug degrades to re-prefill,
    # never wrong KV. Fewer wire bytes directly shrink migration pause,
    # handoff stall, and prefix-fetch latency (Mooncake economics).
    courier_codec: str = "none"
    # zlib compression level for the compressing codecs (-1 = zlib's
    # library default, the historical behavior; 1 = fastest, 9 =
    # smallest). Recorded in each transfer's frame manifest, so
    # receivers stay level-agnostic and mixed-level fleets interoperate;
    # the tiered KV store encodes its at-rest frames at this level too.
    courier_zlib_level: int = -1
    courier_chunk_bytes: int = 256 * 1024
    courier_max_retries: int = 4
    courier_retry_backoff_ms: float = 2.0
    courier_retry_backoff_max_ms: float = 100.0
    courier_chunk_deadline_ms: float = 100.0
    courier_endpoint: str = ""      # http transport: dest fleet base URL
    # destination-side reassembly buffers and attached-but-unclaimed
    # payloads are evicted after this TTL (a sender that died mid-push,
    # or a placement that never submitted, must not leak host memory
    # forever). Evictions count in llmctl_fleet_courier_expired_total.
    # 0 disables expiry.
    courier_ticket_ttl_ms: float = 60_000.0
    # -- cross-host fleet (serve/fleet/remote.py + worker.py) ----------------
    # per-replica courier endpoint map: replica id -> base URL of the host
    # front that runs that replica's CourierReceiver (`llmctl fleet
    # worker` for remote replicas; this process's own fleet front for
    # in-proc replicas that must RECEIVE payloads pushed by remote
    # workers). Accepts a dict ({"0": "http://hostA:9000"}, the TOML
    # table form), a sequence of "id=url" strings (the repeated
    # `--fleet-endpoint` CLI flag), or one comma-separated string.
    fleet_endpoints: dict = dataclasses.field(default_factory=dict)
    # comma-separated replica ids served by a remote `llmctl fleet
    # worker` process instead of an in-process engine thread. Every id
    # listed here MUST have an entry in fleet_endpoints — that is
    # validated at fleet build time, not at first ship.
    remote_replicas: str = ""
    # per-call HTTP timeout for remote-replica control RPCs
    # (submit/probe/outbox/drain); failed calls reconnect under a
    # doubling backoff and probe misses tear the replica down exactly
    # like an engine-thread crash.
    remote_timeout_s: float = 5.0
    # upper bound on one worker->worker payload ship command (the
    # chunked push inside it already has per-chunk deadlines + retry
    # budget; this bounds the whole RPC so a hung worker can't wedge
    # placement).
    courier_ship_timeout_s: float = 30.0
    # -- fleet-global prefix cache (Mooncake-style KV reuse) -----------------
    # A placement that lands off the prefix-affinity owner (load bound,
    # role filter, drain, requeue) normally re-prefills a prefix whose
    # KV already exists somewhere in the fleet. With prefix_fetch on,
    # the router attaches a `prefix_owner` hint (from per-replica
    # prefix-page inventories) and the destination FETCHES the shared
    # full pages over the courier instead of recomputing them,
    # prefilling only the uncovered tail. Every failure mode (owner
    # evicted the pages, transfer aborted, timeout) degrades to plain
    # prefill — fetch is an optimization, never a correctness
    # dependency. Fetched pages credit reprefill_tokens_avoided.
    prefix_fetch: bool = True
    # don't bother fetching fewer than this many full pages (a one-page
    # fetch rarely beats just computing it; raise on slow links)
    prefix_fetch_min_pages: int = 1
    # bound on one fetch round trip (owner-side extract waits at most
    # one engine dispatch; the push inside has its own chunk deadlines)
    prefix_fetch_timeout_s: float = 5.0
    # newest prefix-page hashes each replica advertises in its probe /
    # inventory (bounds probe payloads and router hint work; 0 disables
    # the inventory and therefore all fetch hints)
    prefix_inventory_max: int = 512
    # TTL on the router's per-placement inventory reads (the PR-7 named
    # gap: every needs-prefill placement re-read every replica's
    # inventory). > 0 caches the {replica: hashes} map for that long —
    # invalidated outright on replica teardown/drain/undrain/restart,
    # so a dead owner's pages never outlive it in the hint path; a
    # within-TTL stale entry only costs a counted fetch miss. 0 = read
    # fresh every placement (exact hints; fine at small fleets).
    prefix_inventory_ttl_ms: float = 0.0
    # -- pipelined multi-replica prefill (serve/fleet/pipeline.py) -----------
    # needs-prefill prompts at least this many tokens long are split
    # into page-aligned chunks and streamed through the prefill pool as
    # a chunk pipeline (Mooncake-style chunked pipeline parallelism):
    # stage k computes chunk k against the shipped-in KV of chunks < k
    # while its finished pages pre-ship to stage k+1 over the courier —
    # transfer hides behind compute. Token-identical to single-replica
    # prefill (greedy and seeded); any stage failure collapses to a
    # counted single-replica prefill. 0 disables pipelining. Requires
    # prefix_fetch (stages import shipped chunks through the fetch
    # plane).
    pipeline_prefill_min_tokens: int = 0
    # most stages one prompt is split across (also bounded by the number
    # of accepting prefill-capable in-process replicas)
    pipeline_prefill_max_stages: int = 4
    # a stage that neither finishes nor reports chunk progress within
    # this window collapses the pipeline to single-replica prefill
    pipeline_prefill_stage_timeout_ms: float = 30_000.0
    # -- tiered fleet KV store (serve/fleet/kv_store.py) ---------------------
    # host-tier page cache behind the prefix inventory (Mooncake's
    # cluster-cache claim): replicas DEMOTE evicted/retired prefix pages
    # here in their compressed courier-frame form (encoded once, stored
    # as frames, replayed byte-identical on fetch), the store advertises
    # its holdings through the same hint path replica inventories use,
    # and a returning conversation whose pages left every HBM pool
    # restores from the store at wire speed instead of re-prefilling.
    # Requires prefix_fetch (the fetch plane IS the restore path).
    kv_store: bool = False
    # bounded DRAM ring capacity, in MB of COMPRESSED frames (LRU;
    # overflow spills to kv_store_dir when set, else drops the oldest)
    kv_store_dram_mb: float = 256.0
    # optional disk-spill directory ("" = DRAM only); also LRU-bounded
    kv_store_dir: str = ""
    kv_store_disk_mb: float = 1024.0
    # entries nobody fetched for this long are expired (0 = keep until
    # capacity pressure evicts them)
    kv_store_ttl_ms: float = 0.0
    # networked store backend (serve/fleet/store_service.py): base URL
    # of a standalone `llmctl fleet store` service. When set, this
    # front/worker uses a StoreClient against that service instead of
    # an in-proc FleetKVStore — N fronts and every remote worker then
    # share ONE logical store (demotions upload the already-encoded
    # frames; fetches replay them locally through the courier
    # receiver). "" = in-proc store (kv_store=true) or none.
    kv_store_endpoint: str = ""
    # -- replicated store tier (serve/fleet/store_tier.py) -------------------
    # comma-separated member URLs of a REPLICATED store tier: N
    # `llmctl fleet store` processes behind the one logical
    # KV_STORE_OWNER. Demotions/retire-flushes/ship-weights replicate
    # to every live member (kv_store_write_ack of them synchronously,
    # the rest async-mirrored) and the client fails over across members
    # on fetch — a SIGKILLed member costs zero counted misses while a
    # survivor holds the pages. Overrides kv_store_endpoint when set.
    kv_store_endpoints: str = ""
    # transient-error budget BEFORE a store RPC failure is surfaced:
    # each member gets up to this many retries with doubling backoff
    # (first wait kv_store_retry_backoff_ms) on connection
    # refused/reset/timeout; only after every live member exhausts its
    # budget does a fetch count a remote miss. Applies in single-store
    # mode too (the PR-16 behavior was miss-on-first-refusal).
    kv_store_retry_max: int = 2
    kv_store_retry_backoff_ms: float = 10.0
    # write-ack floor: a demotion/retire-flush/weight ship is
    # acknowledged once this many members durably hold it; remaining
    # live members are mirrored in the background. Must be <= the
    # member count; raise it to the member count for synchronous full
    # replication (what the chaos dryrun uses so a kill can never lose
    # the only copy).
    kv_store_write_ack: int = 1
    # hedged fetch: > 0 races a second member when the first has not
    # answered within this many ms (tail-latency insurance, Mooncake's
    # "fetch from any holder"); 0 disables hedging.
    kv_store_hedge_ms: float = 0.0
    # -- fleet SSE streaming (serve/fleet/streams.py) ------------------------
    # finished stream logs stay replayable (Last-Event-ID reconnect) for
    # this long before the hub GCs them; live logs never expire. 0 keeps
    # finished logs forever (tests only — production would leak).
    stream_log_ttl_ms: float = 60_000.0
    # per-subscriber backpressure bound: a subscriber holding more than
    # this many delivered-but-unconsumed token batches (a slow SSE
    # client buffering in its response queue) is DISCONNECTED by the hub
    # (counted in llmctl_fleet_stream_backpressure_drops_total) instead
    # of buffering without bound — the log keeps growing, so the client
    # reconnects with Last-Event-ID and replays exactly the unacked
    # tail. 0 disables the cap (PR-8 behavior).
    stream_max_buffered_batches: int = 256
    # -- HA front tier (serve/fleet/state.py + front.py) ---------------------
    # where the front-affine mutable state (stream logs, router ledger,
    # parked queue) lives. "memory" = this process's heap, the
    # single-front default, byte-for-byte the pre-store behavior.
    # "file" = a shared, fenced, append-only journal under
    # state_store_dir — N stateless fronts over the same directory and
    # the same remote workers serve ONE fleet, and a front's SIGKILL
    # mid-SSE is healed by the client reconnecting to any survivor with
    # Last-Event-ID (zero gaps, zero duplicates).
    state_store: str = "memory"
    state_store_dir: str = ""
    # snapshot+truncate compaction cadence for the file store's journal
    # (records written between compaction attempts; 0 disables). The
    # journal otherwise grows unboundedly — compaction folds the prefix
    # every attached front has already consumed into snapshot.jsonl
    # (terminal request groups collapsed to put+pop, counter records
    # aggregated, finished stream groups dropped) and truncates the
    # journal, flock-serialized and fencing-aware.
    state_compact_every: int = 1024
    # how many front processes `llmctl serve start` runs (via the
    # FleetFrontTier babysitter, each a `llmctl fleet front` child on
    # its own port, surfaced in `fleet status`). > 1 requires
    # state_store=file and all replicas remote — a front holding
    # in-process engines would not be stateless.
    fronts: int = 1
    # -- elastic autoscaling (serve/fleet/autoscaler.py) ---------------------
    # react to load: the supervisor-driven FleetAutoscaler adds replicas
    # when the fleet queues (spawning `llmctl fleet worker` processes
    # when a spawner is wired, in-proc engine replicas otherwise) and
    # retires the least-loaded replica when load fades — through the
    # lossless drain-with-migration + store-flush path, so scale-down
    # never destroys cached prefixes or in-flight tokens.
    autoscale: bool = False
    # hard floor/ceiling on live replicas (ceiling 0 = 2x provisioned)
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 0
    # scale up when queued-but-not-resident requests per healthy replica
    # exceed this for `autoscale_hysteresis_polls` consecutive polls
    autoscale_up_queue_per_replica: float = 4.0
    # scale down when the per-replica queue falls below this AND at
    # least one replica is fully idle, held for the same hysteresis
    autoscale_down_queue_per_replica: float = 0.5
    # consecutive over/under-threshold polls before a decision fires
    # (one bursty poll must not thrash the fleet)
    autoscale_hysteresis_polls: int = 2
    # polls to sit out after ANY scale action before the next one —
    # lets spawned replicas warm and drained load settle
    autoscale_cooldown_polls: int = 10
    # how long a spawned worker process gets to print its ready line
    # (LLMCTL_WORKER_READY port=N) before the spawn is rolled back
    autoscale_spawn_timeout_s: float = 30.0
    # what a scale-up actually spawns: "" / "engine" = an in-proc
    # engine replica (warm-spare pool); "worker" = a `llmctl fleet
    # worker` OS process whose argv `llmctl serve start` synthesizes
    # from its own model/config flags (including --weights-from-store
    # when kv_store_endpoint is set — a bare host bootstraps weights
    # over the courier, no shared artifact path needed)
    autoscale_spawn: str = ""
    # pool-pressure scale-up signal: when > 0 and the minimum
    # free-page ratio (pool_free_pages / pool_total_pages, from the
    # probes) across healthy replicas falls BELOW this, the fleet
    # scales up under the same hysteresis as queue pressure — KV
    # capacity exhausts before queues form under long-context load.
    # 0 disables (queue-depth-only, the PR-16 behavior).
    autoscale_up_free_page_ratio: float = 0.0
    # -- SLO priority classes (router admission + preemption) ----------------
    # queue slots (out of max_pending) held back from standard and
    # best-effort admission so interactive requests are still admissible
    # at saturation; 0 = single-class admission (pre-tier behavior)
    priority_headroom_requests: int = 0
    # preempt a best-effort resident (KV migrated, never dropped) when
    # an interactive request has been queued longer than this TTFT
    # target; 0 disables preemption
    interactive_ttft_target_ms: float = 0.0

    def role_list(self) -> list[str]:
        """Per-replica role assignment; empty config = all mixed."""
        if not self.roles:
            return ["mixed"] * self.replicas
        return [s.strip().lower() for s in self.roles.split(",")]

    def endpoint_map(self) -> dict[int, str]:
        """Normalized {replica_id: base_url} courier endpoint map."""
        return parse_fleet_endpoints(self.fleet_endpoints)

    def kv_store_endpoint_list(self) -> list:
        """Ordered store-tier member URLs: ``kv_store_endpoints``
        (comma-separated) when set, else the single
        ``kv_store_endpoint``, else empty. The first entry is the
        preferred member; clients rotate from it on failure."""
        eps = [e.strip().rstrip("/")
               for e in str(self.kv_store_endpoints or "").split(",")
               if e.strip()]
        if not eps and self.kv_store_endpoint:
            eps = [str(self.kv_store_endpoint).rstrip("/")]
        return eps

    def remote_replica_ids(self) -> set[int]:
        """Replica ids fronted by a remote `llmctl fleet worker`."""
        if not self.remote_replicas:
            return set()
        try:
            return {int(s) for s in
                    str(self.remote_replicas).split(",") if s.strip()}
        except ValueError:
            raise ConfigError(
                f"remote_replicas must be comma-separated replica ids, "
                f"got {self.remote_replicas!r}")

    def validate(self) -> None:
        if self.replicas < 1:
            raise ConfigError("fleet replicas must be >= 1")
        if self.probe_interval_s <= 0:
            raise ConfigError("probe_interval_s must be > 0")
        if self.probe_failures < 1:
            raise ConfigError("probe_failures must be >= 1")
        if self.restart_backoff_s < 0 or self.restart_backoff_max_s < 0:
            raise ConfigError("restart backoff values must be >= 0")
        if self.affinity_prefix_tokens < 0:
            raise ConfigError("affinity_prefix_tokens must be >= 0")
        if self.affinity_vnodes < 1:
            raise ConfigError("affinity_vnodes must be >= 1")
        if self.max_pending < 1:
            raise ConfigError("max_pending must be >= 1")
        if self.max_requeues < 0:
            raise ConfigError("max_requeues must be >= 0")
        if not 0.0 <= self.rebalance_imbalance_ratio < 1.0:
            raise ConfigError(
                "rebalance_imbalance_ratio must be in [0, 1) (0 disables)")
        if self.rebalance_poll_hysteresis < 1:
            raise ConfigError("rebalance_poll_hysteresis must be >= 1")
        if self.max_concurrent_migrations < 1:
            raise ConfigError("max_concurrent_migrations must be >= 1")
        if self.roles:
            rl = self.role_list()
            if len(rl) != self.replicas:
                raise ConfigError(
                    f"fleet roles names {len(rl)} replicas but the fleet "
                    f"has {self.replicas}")
            bad = sorted(set(rl) - {"prefill", "decode", "mixed"})
            if bad:
                raise ConfigError(
                    f"unknown fleet role(s) {bad}; each must be "
                    "prefill|decode|mixed")
            if not any(r in ("prefill", "mixed") for r in rl):
                raise ConfigError(
                    "fleet roles need at least one prefill-capable "
                    "(prefill or mixed) replica — a decode-only fleet "
                    "could never admit a new request")
        if self.role_balance_ratio < 0:
            raise ConfigError("role_balance_ratio must be >= 0 (0 disables)")
        if self.role_balance_poll_hysteresis < 1:
            raise ConfigError("role_balance_poll_hysteresis must be >= 1")
        if self.role_min_prefill < 1 or self.role_min_decode < 1:
            raise ConfigError("role_min_prefill/role_min_decode must be >= 1")
        if self.role_restore_hysteresis < 0:
            raise ConfigError(
                "role_restore_hysteresis must be >= 0 (0 disables)")
        if self.courier_transport not in ("inproc", "http"):
            raise ConfigError(
                f"unknown courier_transport "
                f"{self.courier_transport!r} (inproc|http)")
        if self.courier_transport == "http" and not self.courier_endpoint:
            raise ConfigError(
                "courier_transport=http needs courier_endpoint (the "
                "destination fleet front's base URL)")
        if self.courier_codec not in ("none", "zlib", "delta-zlib"):
            raise ConfigError(
                f"unknown courier_codec {self.courier_codec!r} "
                f"(none|zlib|delta-zlib)")
        if not -1 <= self.courier_zlib_level <= 9:
            raise ConfigError(
                f"courier_zlib_level {self.courier_zlib_level} outside "
                f"[-1, 9] (-1 = zlib default)")
        if self.courier_chunk_bytes < 1024:
            raise ConfigError("courier_chunk_bytes must be >= 1024")
        if self.courier_ticket_ttl_ms < 0:
            raise ConfigError(
                "courier_ticket_ttl_ms must be >= 0 (0 disables expiry)")
        if self.remote_timeout_s <= 0 or self.courier_ship_timeout_s <= 0:
            raise ConfigError(
                "remote_timeout_s / courier_ship_timeout_s must be > 0")
        if self.prefix_fetch_min_pages < 1:
            raise ConfigError("prefix_fetch_min_pages must be >= 1")
        if self.prefix_fetch_timeout_s <= 0:
            raise ConfigError("prefix_fetch_timeout_s must be > 0")
        if self.pipeline_prefill_min_tokens < 0:
            raise ConfigError(
                "pipeline_prefill_min_tokens must be >= 0 (0 disables "
                "pipelined prefill)")
        if self.pipeline_prefill_min_tokens > 0 and not self.prefix_fetch:
            raise ConfigError(
                "pipeline_prefill_min_tokens requires prefix_fetch "
                "(pipeline stages import shipped chunks through the "
                "prefix-fetch plane)")
        if self.pipeline_prefill_max_stages < 2:
            raise ConfigError("pipeline_prefill_max_stages must be >= 2 "
                              "(one stage is just a plain prefill)")
        if self.pipeline_prefill_stage_timeout_ms <= 0:
            raise ConfigError(
                "pipeline_prefill_stage_timeout_ms must be > 0")
        if self.prefix_inventory_max < 0:
            raise ConfigError(
                "prefix_inventory_max must be >= 0 (0 disables the "
                "inventory and therefore all prefix-fetch hints)")
        if self.prefix_inventory_ttl_ms < 0:
            raise ConfigError(
                "prefix_inventory_ttl_ms must be >= 0 (0 = read fresh "
                "per placement)")
        if self.kv_store:
            if not self.prefix_fetch:
                raise ConfigError(
                    "kv_store needs prefix_fetch — the fetch plane is "
                    "how store-held pages restore to a replica")
            if self.kv_store_dram_mb <= 0:
                raise ConfigError("kv_store_dram_mb must be > 0")
        if self.kv_store_disk_mb < 0:
            raise ConfigError("kv_store_disk_mb must be >= 0")
        if self.kv_store_ttl_ms < 0:
            raise ConfigError(
                "kv_store_ttl_ms must be >= 0 (0 = no expiry)")
        if self.kv_store_endpoint and not str(
                self.kv_store_endpoint).startswith(
                    ("http://", "https://")):
            raise ConfigError(
                f"kv_store_endpoint must be an http(s) base URL, got "
                f"{self.kv_store_endpoint!r}")
        if self.kv_store_endpoint and not self.prefix_fetch:
            raise ConfigError(
                "kv_store_endpoint needs prefix_fetch — the fetch "
                "plane is how store-held pages restore to a replica")
        members = self.kv_store_endpoint_list()
        for ep in ([] if not self.kv_store_endpoints else members):
            if not ep.startswith(("http://", "https://")):
                raise ConfigError(
                    f"kv_store_endpoints entries must be http(s) base "
                    f"URLs, got {ep!r}")
        if self.kv_store_endpoints and not self.prefix_fetch:
            raise ConfigError(
                "kv_store_endpoints needs prefix_fetch — the fetch "
                "plane is how store-held pages restore to a replica")
        if self.kv_store_retry_max < 0:
            raise ConfigError(
                "kv_store_retry_max must be >= 0 (0 = fail on the "
                "first refusal, the PR-16 behavior)")
        if self.kv_store_retry_backoff_ms < 0:
            raise ConfigError("kv_store_retry_backoff_ms must be >= 0")
        if self.kv_store_hedge_ms < 0:
            raise ConfigError(
                "kv_store_hedge_ms must be >= 0 (0 disables hedged "
                "fetches)")
        if self.kv_store_write_ack < 1:
            raise ConfigError(
                "kv_store_write_ack must be >= 1 (at least one member "
                "must durably hold a write before it is acknowledged)")
        if members and self.kv_store_write_ack > len(members):
            raise ConfigError(
                f"kv_store_write_ack ({self.kv_store_write_ack}) "
                f"exceeds the store-tier member count ({len(members)})")
        if self.state_compact_every < 0:
            raise ConfigError(
                "state_compact_every must be >= 0 (0 disables journal "
                "compaction)")
        if self.stream_log_ttl_ms < 0:
            raise ConfigError(
                "stream_log_ttl_ms must be >= 0 (0 keeps finished "
                "stream logs forever)")
        if self.stream_max_buffered_batches < 0:
            raise ConfigError(
                "stream_max_buffered_batches must be >= 0 (0 disables "
                "the per-subscriber backpressure cap)")
        if self.state_store not in ("memory", "file"):
            raise ConfigError(
                f"unknown state_store {self.state_store!r} "
                f"(memory|file)")
        if self.state_store == "file" and not self.state_store_dir:
            raise ConfigError(
                "state_store=file needs state_store_dir (the shared "
                "directory every front folds the journal from)")
        if self.fronts < 1:
            raise ConfigError("fleet fronts must be >= 1")
        if self.fronts > 1:
            if self.state_store != "file":
                raise ConfigError(
                    "fronts > 1 needs state_store=file — stateless "
                    "fronts must share the stream log and ledger")
            if len(self.remote_replica_ids()) < self.replicas:
                raise ConfigError(
                    "fronts > 1 needs every replica remote "
                    "(remote_replicas) — a front holding in-process "
                    "engines is not stateless")
        endpoints = self.endpoint_map()       # raises on malformed entries
        for rid in endpoints:
            # -1 is the KV_STORE_OWNER sentinel: the networked store
            # service's endpoint rides the same map ("store=URL")
            if rid != -1 and not 0 <= rid < self.replicas:
                raise ConfigError(
                    f"fleet endpoint names replica {rid} but the fleet "
                    f"has replicas 0..{self.replicas - 1}")
        remote = self.remote_replica_ids()
        for rid in sorted(remote):
            if not 0 <= rid < self.replicas:
                raise ConfigError(
                    f"remote_replicas names replica {rid} but the fleet "
                    f"has replicas 0..{self.replicas - 1}")
            if rid not in endpoints:
                raise ConfigError(
                    f"remote replica {rid} has no fleet endpoint — every "
                    f"remote replica needs a fleet_endpoints entry "
                    f"(--fleet-endpoint {rid}=http://host:port)")
        if self.courier_max_retries < 0:
            raise ConfigError("courier_max_retries must be >= 0")
        if self.courier_retry_backoff_ms < 0 \
                or self.courier_retry_backoff_max_ms < 0:
            raise ConfigError("courier retry backoff values must be >= 0")
        if self.courier_chunk_deadline_ms <= 0:
            raise ConfigError("courier_chunk_deadline_ms must be > 0")
        if self.autoscale_min_replicas < 1:
            raise ConfigError(
                "autoscale_min_replicas must be >= 1 — the scale-down "
                "floor keeps at least one replica serving")
        if self.autoscale_max_replicas and \
                self.autoscale_max_replicas < self.autoscale_min_replicas:
            raise ConfigError(
                "autoscale_max_replicas must be >= autoscale_min_replicas "
                "(0 = default ceiling of 2x the provisioned fleet)")
        if self.autoscale_up_queue_per_replica <= 0 \
                or self.autoscale_down_queue_per_replica < 0:
            raise ConfigError(
                "autoscale_up_queue_per_replica must be > 0 and "
                "autoscale_down_queue_per_replica >= 0")
        if self.autoscale_down_queue_per_replica \
                >= self.autoscale_up_queue_per_replica:
            raise ConfigError(
                "autoscale_down_queue_per_replica must be below "
                "autoscale_up_queue_per_replica — overlapping scale "
                "thresholds would oscillate the fleet")
        if self.autoscale_hysteresis_polls < 1:
            raise ConfigError("autoscale_hysteresis_polls must be >= 1")
        if self.autoscale_cooldown_polls < 0:
            raise ConfigError(
                "autoscale_cooldown_polls must be >= 0 (0 = no cooldown)")
        if self.autoscale_spawn_timeout_s <= 0:
            raise ConfigError("autoscale_spawn_timeout_s must be > 0")
        if self.autoscale_spawn not in ("", "engine", "worker"):
            raise ConfigError(
                f"unknown autoscale_spawn {self.autoscale_spawn!r} "
                f"(engine|worker; empty = engine)")
        if not 0 <= self.autoscale_up_free_page_ratio < 1:
            raise ConfigError(
                "autoscale_up_free_page_ratio must be in [0, 1) — it "
                "is a fraction of the KV pool (0 disables pool-"
                "pressure scale-up)")
        if self.autoscale and self.fronts > 1:
            raise ConfigError(
                "autoscale with fronts > 1 is not supported yet — each "
                "front would scale the shared worker set independently")
        if self.priority_headroom_requests < 0:
            raise ConfigError("priority_headroom_requests must be >= 0")
        if self.priority_headroom_requests >= self.max_pending:
            raise ConfigError(
                "priority_headroom_requests must be below max_pending — "
                "reserving every queue slot for interactive traffic "
                "would reject all standard requests")
        if self.interactive_ttft_target_ms < 0:
            raise ConfigError(
                "interactive_ttft_target_ms must be >= 0 (0 disables "
                "TTFT-driven preemption)")

    @classmethod
    def from_dict(cls, d: dict[str, Any] | None) -> "FleetConfig":
        if not d:
            return cls()
        kw = {}
        for f_ in dataclasses.fields(cls):
            if f_.name in d:
                if f_.name == "fleet_endpoints":
                    # dict field (default_factory): accepts the TOML
                    # table, the repeated-CLI-flag list, or one string
                    kw[f_.name] = parse_fleet_endpoints(d[f_.name])
                elif isinstance(f_.default, bool):
                    # bool("false") is True — string configs need the shared
                    # parser, same as ServeConfig
                    kw[f_.name] = _parse_bool(f_.name, d[f_.name])
                else:
                    kw[f_.name] = type(f_.default)(d[f_.name])
        cfg = cls(**kw)
        cfg.validate()
        return cfg


# alias -> canonical field name for ModelConfig dict keys (the _take
# alias groups in ModelConfig.from_dict, inverted). Used when overlaying
# user keys onto a template's canonical dict — see RunConfig.from_dict.
_MODEL_KEY_ALIASES: dict[str, str] = {
    "layers": "num_layers", "num_hidden_layers": "num_layers",
    "hidden": "hidden_size", "d_model": "hidden_size",
    "ffn": "ffn_size", "intermediate_size": "ffn_size",
    "heads": "num_heads", "num_attention_heads": "num_heads",
    "kv_heads": "num_kv_heads", "num_key_value_heads": "num_kv_heads",
    "max_seq_len": "max_position_embeddings",
    "hidden_act": "activation",
    "layer_norm_eps": "norm_eps", "rms_norm_eps": "norm_eps",
}


@dataclass
class RunConfig:
    """The full training-run preset: everything in one file.

    Matches the shape generated by ``llmctl init scaffold``
    (reference init.py:104-163) and the shipped preset
    (reference configs/presets/llama-7b-a100x8.toml).
    """
    model: ModelConfig = field(default_factory=ModelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    data: DataConfig = field(default_factory=DataConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    training: TrainingConfig = field(default_factory=TrainingConfig)
    hardware: HardwareConfig = field(default_factory=HardwareConfig)

    @classmethod
    def from_dict(cls, d: dict[str, Any], base_dir=None) -> "RunConfig":
        model_d = d.get("model", {}) or {}
        # Presets may point at an external model JSON via config_file
        # (reference preset llama-7b-a100x8.toml:5 uses a repo-root-relative
        # path from a file in configs/presets/, so search upward from the
        # preset's own directory too). A declared-but-missing file is an
        # error, never a silent fallback to defaults.
        if "config_file" in model_d:
            from pathlib import Path
            from ..utils.tomlio import load_config_file
            rel = Path(model_d["config_file"])
            candidates = [rel] if rel.is_absolute() else []
            if base_dir is not None and not rel.is_absolute():
                b = Path(base_dir).resolve()
                candidates += [b / rel, b.parent / rel, b.parent.parent / rel]
            if not rel.is_absolute():
                candidates.append(Path.cwd() / rel)
            found = next((p for p in candidates if p.exists()), None)
            if found is None:
                raise ConfigError(
                    f"model.config_file {model_d['config_file']!r} not found "
                    f"(searched {[str(c) for c in candidates]})")
            loaded = load_config_file(found)
            loaded.update({k: v for k, v in model_d.items() if k != "config_file"})
            model_d = loaded
        # A known template NAME seeds the architecture, explicit keys
        # override it. Without this, `[model] name = "gpt-7b"` in a run
        # config silently trained the 125m DEFAULT dims under a 7b label
        # (the CLI --model flag resolved templates; config files did not).
        name = model_d.get("name")
        if name:
            from .presets import MODEL_TEMPLATES, TEST_TEMPLATES
            tmpl = MODEL_TEMPLATES.get(name) or TEST_TEMPLATES.get(name)
            if tmpl is not None:
                import dataclasses as _dc
                base = _dc.asdict(tmpl)
                for k, v in model_d.items():
                    # user keys overlay under their CANONICAL names —
                    # otherwise the template's canonical key shadows a
                    # user value written under an HF-style alias (e.g.
                    # num_hidden_layers) and _take silently prefers the
                    # template's dims
                    k = _MODEL_KEY_ALIASES.get(k, k)
                    if isinstance(v, dict) and isinstance(base.get(k), dict):
                        base[k] = {**base[k], **v}
                    else:
                        base[k] = v
                model_d = base
        return cls(
            model=ModelConfig.from_dict(model_d) if model_d else ModelConfig(),
            optimizer=OptimizerConfig.from_dict(d.get("optimizer")),
            data=DataConfig.from_dict(d.get("data")),
            parallel=ParallelConfig.from_dict(d.get("parallel")),
            checkpoint=CheckpointConfig.from_dict(d.get("checkpoint")),
            training=TrainingConfig.from_dict(d.get("training")),
            hardware=HardwareConfig.from_dict(d.get("hardware")),
        )

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)
