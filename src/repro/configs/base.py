"""Config system: model/architecture configs, input shapes, run configs.

Every assigned architecture gets a module in ``repro/configs`` exporting
``CONFIG`` (full size, citation in the docstring) and ``smoke_config()``
(reduced: <=2 layers-per-pattern repeat, d_model<=512, <=4 experts) for
CPU smoke tests. The registry maps ``--arch`` ids to these.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple


# --------------------------------------------------------------------------
# Layer-type vocabulary (see models/transformer.py):
#   "attn"        full-attention transformer block (attn + MLP)
#   "local"       sliding-window attention block
#   "moe"         attention + MoE-FFN block
#   "mla"         MLA attention + MLP block (DeepSeek dense layers)
#   "mla_moe"     MLA attention + MoE block (DeepSeek MoE layers)
#   "moe_res"     attention + (MoE || dense residual) block (Arctic)
#   "mamba"       Mamba2 SSD block
#   "zshared"     Zamba2 shared attention+MLP block (weights shared)
#   "mlstm"       xLSTM matrix-memory block
#   "slstm"       xLSTM scalar-memory block
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoESettings:
    num_experts: int = 0
    num_experts_per_tok: int = 2
    d_ff: int = 0                    # per-expert hidden size
    num_shared_experts: int = 0      # DeepSeek shared expert(s)
    dense_residual: bool = False     # Arctic: dense FFN in parallel
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.001
    router_noise: float = 0.0
    # §Perf knob: sharding of the (E, C, d) dispatch buffer's capacity dim.
    # "none"  — capacity replicated across data shards (baseline; GSPMD
    #           gathers tokens to every expert shard);
    # "data"  — capacity sharded over the data axis (each data shard
    #           scatters its local tokens; combine via reduce-scatter).
    capacity_sharding: str = "none"
    # §Perf knob: dispatch implementation for training/prefill.
    # "gspmd"    — capacity scatter, collectives chosen by the partitioner;
    # "shardmap" — explicit expert-parallel all_to_all (moe_shardmap.py).
    dispatch_impl: str = "gspmd"


@dataclasses.dataclass(frozen=True)
class MLASettings:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMSettings:
    state_dim: int = 64      # N (SSD state per head-channel)
    conv_width: int = 4
    expand: int = 2
    head_dim: int = 64       # mamba2 P
    chunk: int = 128
    # xLSTM
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 1.3333


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // num_heads

    # layer pattern: optional `prefix` layers, then `pattern` repeats,
    # remainder handled explicitly (all unrolled except the repeats).
    pattern: Tuple[str, ...] = ("attn",)
    prefix: Tuple[str, ...] = ()
    # attention details
    rope_theta: float = 10000.0
    rope_type: str = "default"       # none | default | mrope | dual (gemma3)
    sliding_window: int = 4096
    local_rope_theta: float = 10000.0
    qk_norm: bool = False
    attn_logit_softcap: float = 0.0
    use_bias: bool = False           # starcoder2 uses bias
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    norm_eps: float = 1e-6
    act: str = "silu"                # silu | gelu
    mlp_gated: bool = True           # gated (SwiGLU) vs plain 2-layer MLP
    post_norms: bool = False         # gemma3: post-attn/post-ffn norms
    tie_embeddings: bool = True
    embed_scale: bool = False        # gemma: scale embeds by sqrt(d_model)
    max_seq_len: int = 131072

    moe: MoESettings = MoESettings()
    mla: Optional[MLASettings] = None
    ssm: SSMSettings = SSMSettings()

    # enc-dec (whisper)
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    num_audio_frames: int = 1500

    # vlm (qwen2-vl)
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    num_vision_tokens: int = 0       # patch embeds prepended in input stub

    # deepseek multi-token prediction auxiliary head
    mtp_depth: int = 0

    # dtypes
    dtype: str = "bfloat16"          # activation dtype
    param_dtype: str = "float32"

    # DFM-denoiser mode additions
    time_embed_dim: int = 256

    # long-context variant: replace full attention with sliding window of
    # this size when lowering long_500k for full-attention archs (see
    # DESIGN.md §4 policy). None = faithful (full attention everywhere).
    long_context_window: Optional[int] = 8192

    # attention implementation: "auto" (the fused Pallas kernel where
    # models/attention.py::attention_impl allows it — bidirectional, no
    # cache, one TPU — else "xla") | "xla" (einsum, O(S*T) scores) |
    # "chunked" (flash-style online softmax over key chunks in XLA,
    # O(S*chunk) scores — §Perf iteration).
    attn_impl: str = "auto"
    attn_chunk: int = 1024
    # MLA decode: absorb the latent up-projections into the query/output
    # (DeepSeek-V2 §"absorbed" inference trick) instead of expanding the
    # per-head K/V for the whole cache every step. §Perf iteration.
    mla_absorb: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        assert self.num_heads % max(self.num_kv_heads, 1) == 0, (
            f"{self.name}: heads {self.num_heads} not divisible by kv {self.num_kv_heads}"
        )

    # -- layer pattern helpers ------------------------------------------

    def layer_types(self) -> Tuple[str, ...]:
        n = self.num_layers - len(self.prefix)
        reps = n // len(self.pattern)
        rem = n - reps * len(self.pattern)
        return self.prefix + self.pattern * reps + self.pattern[:rem]

    def scan_split(self) -> Tuple[int, Tuple[str, ...]]:
        """(num_scanned_groups, remainder_layer_types). Prefix layers are
        also unrolled (see transformer.init_stack)."""
        n = self.num_layers - len(self.prefix)
        reps = n // len(self.pattern)
        rem = n - reps * len(self.pattern)
        return reps, self.pattern[:rem]

    def is_recurrent(self) -> bool:
        return any(t in ("mamba", "mlstm", "slstm") for t in self.pattern)

    def supports_long_context_faithful(self) -> bool:
        """Sub-quadratic per faithful config: SSM/hybrid or all-windowed."""
        att = {"attn", "moe", "mla", "mla_moe", "moe_res", "zshared"}
        types = set(self.layer_types())
        full_attn = types & (att - {"local"})
        return not full_attn or self.family in ("ssm",)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Trainer/launcher knobs."""
    arch: str = "dfm_dit"
    shape: str = "train_4k"
    t0: float = 0.8                  # warm-start time (0 = cold-start DFM)
    cold_nfe: int = 1024             # baseline step count (paper text exps)
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 300
    batch_size: int = 32
    seed: int = 0
    grad_clip: float = 1.0
    amsgrad: bool = True             # paper uses AMSGrad
    optimizer: str = "adamw"         # adamw | adafactor
    moments_dtype: str = "float32"   # bfloat16 for >=100B configs
    remat: str = "none"              # none | block | full
    checkpoint_dir: str = "/tmp/repro_ckpt"
    log_every: int = 10
