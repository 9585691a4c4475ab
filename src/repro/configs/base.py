"""Config dataclasses for models, shapes and meshes."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | vlm | hybrid | ssm | audio | mlp | cnn
    num_layers: int
    d_model: int
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    activation: str = "silu"
    gated_mlp: bool = True
    norm: str = "rmsnorm"
    positional: str = "rope"         # rope | sinusoidal | none
    rope_theta: float = 1e4
    tie_embeddings: bool = False

    # MoE
    num_experts: int = 0             # routed experts held here (see below)
    top_k: int = 0
    num_shared_experts: int = 0
    first_dense_layers: int = 0      # leading dense-FFN layers (DeepSeekMoE)
    dense_d_ff: int = 0              # their FFN width; 0 = d_ff
    # Gates are the top-k softmax probabilities, renormalised to sum to 1
    # (True) or taken as they are (False, DeepSeekMoE).
    norm_topk_prob: bool = True
    # Expert share: the router scores ``routed_experts`` experts (0 = all
    # ``num_experts``), of which this program holds the ``num_experts``
    # starting at global index ``expert_offset``; it computes their part
    # of each token's result and routes nothing to the rest.
    routed_experts: int = 0
    expert_offset: int = 0
    capacity_factor: float = 1.25
    # 'scatter' = GSPMD scatter/gather dispatch; 'a2a' = explicit shard_map
    # all-to-all dispatch/combine over the 'data' mesh axis (nn/moe.py) —
    # falls back to scatter when no mesh is bound or sizes don't divide.
    moe_dispatch: str = "scatter"

    # VLM (backbone only; frontend is a stub per assignment)
    cross_attn_every: int = 0        # every Nth layer is a cross-attn layer
    num_image_tokens: int = 0

    # Hybrid (RG-LRU) — block_pattern tiles to num_layers; remainder unscanned
    block_pattern: Tuple[str, ...] = ("attn",)
    window: int = 0                  # local attention window (0 = global)
    d_rnn: int = 0

    # SSM (Mamba2 SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 128

    # Modality frontend: False => inputs are precomputed embeddings (stub)
    embed_inputs: bool = True

    sigma_init: float = 1e-4
    sub_quadratic: bool = False      # can run long_500k

    # -- derived -------------------------------------------------------------
    @property
    def attn_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def router_width(self) -> int:
        return self.routed_experts or self.num_experts

    @property
    def dense_ffn_width(self) -> int:
        """FFN width of the leading dense layers of an MoE model."""
        return self.dense_d_ff or self.d_ff

    def param_count(self) -> int:
        """Approximate N (per-weight count, mu only) for MODEL_FLOPS."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        n = 0
        if self.embed_inputs:
            n += v * d
        n += v * d  # lm head
        for i in range(self.num_layers):
            kind = self.layer_kind(i)
            if kind in ("attn", "cross", "moe"):
                n += d * self.attn_dim + 2 * d * self.num_kv_heads * self.head_dim \
                     + self.attn_dim * d
            if kind in ("attn", "cross"):
                fi = self.dense_ffn_width if self.family == "moe" else f
                n += (3 if self.gated_mlp else 2) * d * fi
            if kind == "moe":
                per_e = (3 if self.gated_mlp else 2) * d * f
                n += self.num_experts * per_e + d * self.router_width
                n += self.num_shared_experts * per_e
            if kind == "rec":
                r = self.d_rnn or d
                n += 2 * d * r + r * d + 2 * r * r + 4 * r
                n += (3 if self.gated_mlp else 2) * d * f
            if kind == "ssm":
                din = self.ssm_expand * d
                nh = din // self.ssm_head_dim
                n += d * (2 * din + 2 * self.ssm_state + nh) + din * d
        return n

    def active_param_count(self) -> int:
        """N_active for MoE MODEL_FLOPS (routed top-k + shared only)."""
        if self.family != "moe":
            return self.param_count()
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        n = 2 * v * d
        for i in range(self.num_layers):
            kind = self.layer_kind(i)
            n += d * self.attn_dim + 2 * d * self.num_kv_heads * self.head_dim \
                 + self.attn_dim * d
            per_e = (3 if self.gated_mlp else 2) * d * f
            if kind == "moe":
                n += (self.top_k + self.num_shared_experts) * per_e \
                     + d * self.router_width
            else:
                n += (3 if self.gated_mlp else 2) * d * self.dense_ffn_width
        return n

    def layer_kind(self, i: int) -> str:
        """Block kind of layer i."""
        if self.family == "ssm":
            return "ssm"
        if self.family == "moe":
            return "attn" if i < self.first_dense_layers else "moe"
        if self.family == "vlm" and self.cross_attn_every:
            return "cross" if (i + 1) % self.cross_attn_every == 0 else "attn"
        if self.family == "hybrid":
            return self.block_pattern[i % len(self.block_pattern)]
        return "attn"

    @property
    def pattern(self) -> Tuple[str, ...]:
        """Scan super-block pattern (tiles into num_layers; see models.lm)."""
        if self.family == "ssm":
            return ("ssm",)
        if self.family == "moe":
            return ("moe",)
        if self.family == "vlm" and self.cross_attn_every:
            return ("attn",) * (self.cross_attn_every - 1) + ("cross",)
        if self.family == "hybrid":
            return self.block_pattern
        return ("attn",)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
