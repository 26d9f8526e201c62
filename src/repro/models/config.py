"""Model configuration for the assigned architecture pool.

One ``ModelConfig`` drives every family (dense / MoE / SSM / hybrid /
enc-dec / VLM) through the same block-stack builder.  Dimensions that
must divide the mesh's model axis are padded at construction
(``pad_to``) — vocab padding is standard practice and noted in
DESIGN.md.
"""

from __future__ import annotations

from dataclasses import dataclass, replace


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    expert_d_ff: int
    # the experts this chip holds, experts [first_held, first_held +
    # n_held) of ``n_experts``; 0 holds every one.  Only the layer-pattern
    # path (``layer_types``) computes a share: it routes over all
    # ``n_experts`` and adds the held experts' part of the result
    first_held: int = 0
    n_held: int = 0

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class SSMConfig:
    state_dim: int
    # 1 = Mamba (the ``ssm`` and ``hybrid`` families); 2 = Mamba-2 (SSD),
    # the mixer of the layer-pattern path (``layer_types``)
    version: int = 1
    conv_dim: int = 4
    expand: int = 2
    # Mamba-2 only: channels per head (mamba_d_head; heads are
    # expand * d_model / headdim), B/C groups (mamba_n_groups), positions
    # per chunk of the SSD scan (mamba_chunk_size), a bias on the conv
    # (mamba_conv_bias)
    headdim: int = 64
    n_groups: int = 1
    chunk: int = 256
    conv_bias: bool = False


@dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str               # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0         # 0 -> d_model // n_heads
    qkv_bias: bool = False
    mlp_type: str = "swiglu"  # swiglu (3 mats) | gelu (2 mats, whisper)
    rope_style: str = "full"  # full | half (chatglm 2d RoPE) | none (NoPE)
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: MoEConfig | None = None
    moe_group_routing: bool = True   # per-sequence capacity (shardable)
    sharded_decode: bool = False     # shard_map flash-decode (seq-sharded KV)
    ssm_scan_dtype: str = "float32"  # "bfloat16": halve scan HBM traffic
    ssm: SSMConfig | None = None
    # hybrid (zamba2-style): one shared attention block applied every
    # ``shared_attn_every`` ssm layers
    shared_attn_every: int = 0
    # layer pattern (granite-4.0-h-style): the mixer of each layer,
    # "mamba" (Mamba-2) or "attention" (layer_types); each layer's second
    # half is the MoE plus a shared SwiGLU MLP of width ``shared_d_ff``
    # (shared_intermediate_size).  Non-empty, it replaces the family's
    # stack.
    layer_types: tuple[str, ...] = ()
    shared_d_ff: int = 0
    # granite's multipliers: input embedding (embedding_multiplier),
    # each half-layer's residual branch (residual_multiplier), the
    # attention score scale, 0 for 1/sqrt(hd) (attention_multiplier), and
    # the divisor of the logits (logits_scaling)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # enc-dec (whisper-style)
    enc_layers: int = 0
    enc_seq: int = 0          # stubbed frontend sequence length (frames)
    # vlm (llama-3.2-vision-style): one cross-attention layer every
    # ``cross_attn_every`` self-attention layers
    cross_attn_every: int = 0
    img_tokens: int = 0       # stubbed patch-embedding count
    # numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # padding granularity for shardable dims
    pad_to: int = 256

    def __post_init__(self):
        # a pattern given as a list (a JSON file's) is held as a tuple
        object.__setattr__(self, "layer_types", tuple(self.layer_types))

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return round_up(self.vocab, self.pad_to)

    @property
    def is_causal_lm(self) -> bool:
        return self.family in ("dense", "moe", "ssm", "hybrid", "vlm")

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    def params_dense_layer(self) -> int:
        """Approximate parameter count of one transformer layer."""
        hd = self.hd
        attn = (self.d_model * self.n_heads * hd          # q
                + 2 * self.d_model * self.n_kv_heads * hd  # k, v
                + self.n_heads * hd * self.d_model)        # o
        if self.moe is not None:
            mlp = (self.moe.n_experts * 3 * self.d_model * self.moe.expert_d_ff
                   + self.d_model * self.moe.n_experts)    # router
        else:
            n_mats = 2 if self.mlp_type == "gelu" else 3
            mlp = n_mats * self.d_model * self.d_ff
        return attn + mlp

    def param_count(self) -> int:
        """Approximate total parameters (for 6ND roofline math)."""
        n = self.padded_vocab * self.d_model
        if not self.tie_embeddings:
            n += self.padded_vocab * self.d_model
        if self.layer_types:
            return n + self.d_model + sum(
                self._pattern_layer_params(t) for t in self.layer_types)
        if self.family == "ssm":
            s = self.ssm
            d_in = s.expand * self.d_model
            per = (2 * self.d_model * d_in        # in_proj (x, z)
                   + d_in * s.conv_dim
                   + d_in * (2 * s.state_dim + 1)  # B, C, dt per-dim-ish
                   + d_in * self.d_model)          # out_proj
            n += self.n_layers * per
        elif self.family == "hybrid":
            s = self.ssm
            d_in = s.expand * self.d_model
            per = (2 * self.d_model * d_in + d_in * s.conv_dim
                   + d_in * (2 * s.state_dim + 1) + d_in * self.d_model)
            n += self.n_layers * per
            n += self.params_dense_layer()  # one shared attn+mlp block
        elif self.family == "encdec":
            n += (self.enc_layers + self.n_layers) * self.params_dense_layer()
            # decoder cross-attention
            hd = self.hd
            n += self.n_layers * 2 * self.d_model * self.n_kv_heads * hd
        else:
            n += self.n_layers * self.params_dense_layer()
        return n

    def _pattern_layer_params(self, kind: str) -> int:
        """One layer of the layer pattern: its mixer, two norms, the
        router, the held experts and the shared MLP."""
        d = self.d_model
        if kind == "mamba":
            s = self.ssm
            din = s.expand * d
            heads = din // s.headdim
            conv_ch = din + 2 * s.n_groups * s.state_dim
            mixer = (d * (din + conv_ch + heads)        # in_proj: z, xBC, dt
                     + conv_ch * (s.conv_dim + s.conv_bias)
                     + 3 * heads + din                  # A_log, dt_bias, D, norm
                     + din * d)                         # out_proj
        else:
            hd = self.hd
            mixer = (2 * d * self.n_heads * hd
                     + 2 * d * self.n_kv_heads * hd)
        mlp = 3 * d * self.shared_d_ff
        if self.moe is not None:
            mlp += (d * self.moe.n_experts
                    + self.moe.held * 3 * d * self.moe.expert_d_ff)
        return mixer + mlp + 2 * d

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: only top-k experts; of a
        share of the experts, top-k times the share held)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        # the layer pattern counts the held experts, the families all
        held = m.held if self.layer_types else m.n_experts
        expert = self.n_layers * 3 * self.d_model * m.expert_d_ff
        return round(self.param_count()
                     - expert * held * (1 - m.top_k / m.n_experts))

    def smoke(self) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests."""
        kw: dict = dict(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=min(self.n_kv_heads, 4) or 2,
            d_ff=128, vocab=128, pad_to=16,
        )
        if self.moe is not None:
            kw["moe"] = MoEConfig(n_experts=4, top_k=min(self.moe.top_k, 2),
                                  expert_d_ff=64)
        if self.ssm is not None:
            kw["ssm"] = replace(self.ssm, state_dim=8, conv_dim=4, expand=2,
                                headdim=16, chunk=8)
        if self.layer_types:
            kw["layer_types"] = ("mamba", "attention", "mamba")
            kw["n_layers"] = 3
            kw["shared_d_ff"] = 64
        elif self.family == "hybrid":
            kw["shared_attn_every"] = 2
            kw["n_layers"] = 4
        if self.family == "encdec":
            kw["enc_layers"] = 2
            kw["enc_seq"] = 16
        if self.family == "vlm":
            kw["cross_attn_every"] = 2
            kw["n_layers"] = 4
            kw["img_tokens"] = 16
        return replace(self, **kw)
