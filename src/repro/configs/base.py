"""Config system: model architectures, input shapes, run/parallelism settings."""
from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.comm.policy import PolicyTable


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0               # 0 -> d_model // n_heads
    rope_theta: float = 10_000.0
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    router: str = "softmax"         # softmax: GShard capacity dispatch |
                                    # sigmoid: DeepSeek-V3 gate, dropless
    routed_scaling: float = 1.0     # sigmoid gate: gates times this
    norm_topk: bool = True          # sigmoid gate: gates over their sum
    seq_aux_alpha: float = 0.0      # sigmoid gate: sequence balance loss
    n_shared_experts: int = 0       # shared experts, one SwiGLU of
                                    # n_shared_experts * d_ff_expert
    expert_offset: int = 0          # the experts this chip holds:
    n_experts_held: int = 0         # [offset, offset + held); 0 -> all
    first_k_dense: int = 0          # leading dense layers (MLP d_ff_dense)
    d_ff_dense: int = 0
    # --- multi-head latent attention (DeepSeek-V2/V3) ---
    kv_lora_rank: int = 0           # 0 -> grouped-query attention
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # --- hybrid (zamba2) ---
    attn_every: int = 0             # shared attention block every k ssm layers
    # --- sliding window (mixtral) ---
    window: int = 0
    # --- encoder-decoder (whisper) ---
    n_enc_layers: int = 0
    n_frames: int = 0
    # --- vlm (qwen2-vl) ---
    mrope_sections: tuple[int, ...] = ()
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    attn_chunk: int = 512           # KV chunk of the jnp online-softmax path
    loss_chunk: int = 8192          # token chunk of the CE loss

    @property
    def head_dim_(self) -> int:
        """Width of a query and key head."""
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim or self.d_model // max(self.n_heads, 1)

    @property
    def v_head_dim_(self) -> int:
        """Width of a value head (MLA's differs from the key's)."""
        return self.v_head_dim if self.kv_lora_rank else self.head_dim_

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to 128 so the head shards over any TP degree
        (padding logits are masked in the loss)."""
        return -(-self.vocab // 128) * 128

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim if self.ssm_state else 0

    @property
    def full_attention(self) -> bool:
        """True if attention cost is quadratic and unbounded (no window/ssm)."""
        return self.family in ("dense", "moe", "encdec", "vlm") and self.window == 0

    def _attn_params(self) -> int:
        d, hd, H = self.d_model, self.head_dim_, self.n_heads
        if self.kv_lora_rank:          # MLA: W_q, W_kv_a, its norm, W_kv_b, W_o
            r, dv = self.kv_lora_rank, self.v_head_dim_
            return (d * H * hd + d * (r + self.qk_rope_head_dim) + r
                    + r * H * (self.qk_nope_head_dim + dv) + H * dv * d)
        return d * (H * hd) * 2 + d * (self.n_kv_heads * hd) * 2

    def n_params(self) -> float:
        """Analytic parameter count (for roofline MODEL_FLOPS = 6·N·D)."""
        d = self.d_model
        attn = self._attn_params()
        if self.family in ("ssm",):
            attn = 0
        mlp = 3 * d * self.d_ff if self.d_ff else 0
        moe = 0
        if self.n_experts:
            mlp = 0
            moe = (self.n_experts * 3 * d * self.d_ff_expert + d * self.n_experts
                   + 3 * d * self.n_shared_experts * self.d_ff_expert)
        ssm = 0
        if self.ssm_state:
            din = self.d_inner
            proj_in = d * (2 * din + 2 * self.ssm_groups * self.ssm_state + self.n_ssm_heads)
            ssm = proj_in + din * d + self.ssm_conv * (din + 2 * self.ssm_groups * self.ssm_state)
        per_layer = attn + mlp + moe
        if self.family == "ssm":
            per_layer = ssm
        elif self.family == "hybrid":
            n_shared = self.n_layers // max(self.attn_every, 1)
            shared = attn + 3 * d * self.d_ff
            return self.n_layers * ssm + shared + 2 * self.vocab * d + n_shared * 0
        k = self.first_k_dense
        total = (self.n_layers - k) * per_layer + k * (attn + 3 * d * self.d_ff_dense)
        if self.family == "encdec":
            total += self.n_enc_layers * (d * d * 4 + 2 * d * self.d_ff)   # enc blocks (GELU MLP)
            total += self.n_layers * (d * d * 4)                            # cross-attn
        total += 2 * self.vocab * d                                         # embed + head
        return float(total)

    def n_active_params(self) -> float:
        """Active params per token (MoE uses top_k experts)."""
        if not self.n_experts:
            return self.n_params()
        d = self.d_model
        expert = 3 * d * self.d_ff_expert * (self.n_layers - self.first_k_dense)
        return float(self.n_params() - self.n_experts * expert + self.top_k * expert)

    def reduced(self) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=min(self.n_layers, 4 if self.family != "hybrid" else 2 * max(self.attn_every, 1)),
            d_model=128,
            n_heads=min(self.n_heads, 4),
            n_kv_heads=min(self.n_heads, 4) if self.kv_lora_rank else min(self.n_kv_heads, 2),
            head_dim=32,
            d_ff=256 if self.d_ff else 0,
            d_ff_expert=128 if self.d_ff_expert else 0,
            vocab=512,
            n_experts=min(self.n_experts, 4),
            top_k=min(self.top_k, 2),
            n_experts_held=min(self.n_experts_held, 4),
            expert_offset=0,
            first_k_dense=min(self.first_k_dense, 1),
            d_ff_dense=256 if self.d_ff_dense else 0,
            kv_lora_rank=32 if self.kv_lora_rank else 0,
            qk_nope_head_dim=16 if self.kv_lora_rank else 0,
            qk_rope_head_dim=16 if self.kv_lora_rank else 0,
            v_head_dim=16 if self.kv_lora_rank else 0,
            ssm_state=min(self.ssm_state, 16),
            ssm_headdim=32 if self.ssm_state else self.ssm_headdim,
            ssm_chunk=32,
            n_enc_layers=min(self.n_enc_layers, 2),
            n_frames=min(self.n_frames, 64),
            window=min(self.window, 64) if self.window else 0,
            mrope_sections=(4, 6, 6) if self.mrope_sections else (),
            attn_chunk=64,
            loss_chunk=1024,
            dtype="float32",
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode

    def applicable(self, cfg: ModelConfig) -> bool:
        if self.seq_len >= 500_000 and cfg.full_attention:
            return False             # long_500k skipped for pure full attention
        return True


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")
SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Parallelism + training knobs for one run.

    The collective-layer fields (``zero_stage``, ``collective_mode``,
    ``n_channels``, ``n_stripes``, ``bucket_bytes``, ``n_micro``) can be set
    by hand or materialized jointly by the autotuner — ``repro.plan
    .TrainPlan.run_config()`` (DESIGN.md §9), the ``--plan auto`` path of
    the launchers.  When ``policies`` carries a per-op
    :class:`~repro.comm.policy.PolicyTable` (DESIGN.md §12), the trainer
    builds its communicator from that table and the single-policy fields
    above serve only as the display/facade fallback.
    """

    zero_stage: int = 1              # 1 or 3 (the paper evaluates both)
    collective_mode: str = "auto"    # flat | hier | pipelined | auto (HetCCL)
    backend: str = "xla"             # collective ring backend: xla | pallas
                                     # (DMA rings, DESIGN.md §10)
    policies: PolicyTable | None = None   # per-op, size-classed policy table
                                     # (repro.comm, DESIGN.md §12); None ->
                                     # the single-policy facade above
    n_channels: int = 4              # pipeline channels of "pipelined" mode
    n_stripes: int = 1               # multi-NIC stripes of the DMA rings
                                     # (transport layer, DESIGN.md §11;
                                     # pallas backend only)
    pipeline_chunk_bytes: int | None = None   # alternative channel sizing
    bucket_bytes: int = 64 * 1024 * 1024      # gradient fusion bucket size
    n_micro: int = 1                 # gradient-accumulation micro-steps
    remat: bool = True
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    cross_dtype: str | None = None   # cross-pod gradient compression
    wire_quant: str | None = None    # wire quantization codec of the pallas
                                     # rings (None | "int8" | "fp8",
                                     # DESIGN.md §17); composes with a
                                     # planner table via with_wire_quant —
                                     # planner rows win
    error_feedback: str = "auto"     # EF residual state for quantized
                                     # gradient collectives: "auto" (on iff
                                     # the gradient rings quantize) | "on" |
                                     # "off" (ablation: quantize without EF)
    param_dtype: str = "bfloat16"
    master_dtype: str = "float32"
    seed: int = 0
