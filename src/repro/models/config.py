"""Architecture configuration: one dataclass drives every model family."""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional

from ..core.ccim import CCIMConfig

if TYPE_CHECKING:  # annotation only -- models must not import repro.plan
    from ..plan.plan import DeploymentPlan


LAYER_KINDS = ("M", "E", "*")     # layer_types: mamba2, experts, attention


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                        # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0                    # 0 -> d_model // n_heads

    # attention features
    rope_theta: float = 10000.0
    use_rope: bool = True              # nemotron_h attention: no rotary
    qk_norm: bool = False              # qwen3
    attn_softcap: Optional[float] = None    # gemma2: 50.0
    logit_softcap: Optional[float] = None   # gemma2: 30.0
    sliding_window: Optional[int] = None    # local-attention window
    layer_pattern: str = "global"      # "global" | "local_global" (alternating)
    attn_impl: str = "flash"           # "flash" (scan, O(S) memory) | "plain"
    flash_block: int = 512             # kv block for the flash scan
    prefix_lm: bool = False            # paligemma: bidirectional prefix

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    shared_expert_d_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_loss: float = 0.001
    # expert parallelism (the "E" layers of a layer_types stack): a
    # sigmoid router with a choice bias scores moe_router_experts experts
    # (0 -> n_experts), and this chip holds the n_experts of them that
    # start at moe_first_expert
    moe_router_experts: int = 0
    moe_first_expert: int = 0
    moe_routed_scaling: float = 1.0

    # SSM (mamba2 / SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 256
    ssm_n_heads: int = 0               # 0 -> d_inner = ssm_expand * d_model
    ssm_n_groups: int = 1              # B/C groups, each serving H/G heads

    # hybrid (zamba2): one SHARED attention block applied every N ssm layers
    shared_attn_period: int = 0
    # hybrid (nemotron_h): one character per layer -- "M" mamba2 mixer,
    # "E" held-expert MoE, "*" attention -- each a pre-norm residual block
    layer_types: str = ""

    # frontends (stubs per the brief: precomputed patch/frame embeddings)
    frontend: Optional[str] = None     # "siglip_stub" | "encodec_stub"
    n_frontend_tokens: int = 0

    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    act: str = "silu"
    dtype: str = "bfloat16"

    # CIM execution mode (the paper's technique as a first-class feature).
    # cim_cfg/cim_use_pallas are threaded into the execution engine
    # (core.engine.CimEngine) -- no module-global macro config anywhere.
    cim_mode: bool = False             # run linear layers through the macro
    cim_fidelity: str = "fast"
    cim_cfg: Optional[CCIMConfig] = None   # None -> the 28nm prototype macro
    cim_use_pallas: Optional[bool] = None  # None -> auto (TPU backend only)
    # Mixed-fidelity deployment plan (repro.plan): per-projection CCIMConfig
    # + fidelity overriding the single global cim_cfg/cim_fidelity above.
    # Static and hashable, resolved at trace time by layers._dense, so a
    # planned model still compiles to one executable per step -- zero
    # recompiles across decode steps.
    cim_plan: Optional["DeploymentPlan"] = None
    # Horizontal projection fusion (decode hot path): projections that
    # consume the same input activation AND resolve to the same plan entry
    # (QKV, gate/up, the mamba2 input projections) execute as ONE wide
    # macro GEMM -- bit-identical per projection (see DESIGN.md section 9).
    # Static, so fused and unfused models are separate jit cache entries.
    cim_fuse: bool = True
    # Deterministic analog-noise emulation for CIM serving: when set, every
    # _dense projection derives its own noise stream by folding this seed
    # with the projection path (shared across scanned depth -- the same
    # physical-bank reuse the weight-stationary macro has).  None keeps
    # serving noise-free.  The profiler sets it so analog candidates are
    # charged for their mismatch/comparator noise, not just rounding.
    cim_noise_seed: Optional[int] = None

    # schedule hint (minicpm: WSD)
    lr_schedule: str = "cosine"        # "cosine" | "wsd"

    # TP head padding: q (and MHA kv) head counts are padded up to a
    # multiple of this so the head dim divides the 16-way model axis --
    # zero-masked pad heads keep the math exactly equivalent (Megatron
    # pads vocab the same way).  reduced() sets 1 (no pad on CPU smoke).
    tp_head_pad: int = 16

    def __post_init__(self):
        if self.layer_types:
            if self.family != "hybrid" or self.shared_attn_period:
                raise ValueError("layer_types builds a 'hybrid' stack "
                                 "without a shared attention block")
            if (len(self.layer_types) != self.n_layers
                    or set(self.layer_types) - set(LAYER_KINDS)):
                raise ValueError(
                    f"layer_types {self.layer_types!r} must give one of "
                    f"{LAYER_KINDS} for each of the {self.n_layers} layers")

    # ----- derived -----
    @property
    def head_dim(self) -> int:
        if self.d_head:
            return self.d_head
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def padded_heads(self) -> int:
        p = max(self.tp_head_pad, 1)
        return (self.n_heads + p - 1) // p * p if self.n_heads else 0

    @property
    def padded_kv_heads(self) -> int:
        if self.n_kv_heads and self.n_kv_heads == self.n_heads:
            return self.padded_heads           # MHA: pad kv with q
        return self.n_kv_heads                 # GQA: kv heads stay

    @property
    def q_per_kv(self) -> int:
        return max(1, self.n_heads // max(self.n_kv_heads, 1))

    @property
    def d_inner(self) -> int:
        if self.ssm_n_heads:
            return self.ssm_n_heads * self.ssm_head_dim
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def ssm_bc_dim(self) -> int:
        """Width of the B and C streams together: 2 * groups * state."""
        return 2 * self.ssm_n_groups * self.ssm_state

    @property
    def act_dtype(self) -> str:
        """What the residual stream, the KV cache and the conv state are
        kept in: float32 for a ``layer_types`` stack, whose router top-k
        and macro input quantization are discontinuous, so that rounding
        to the weights' dtype would move them; else the weights' dtype."""
        return "float32" if self.layer_types else self.dtype

    @property
    def router_experts(self) -> int:
        return self.moe_router_experts or self.n_experts

    @property
    def attn_layers(self) -> int:
        """Layers that keep a KV cache of their own (the zamba2 shared
        block's invocations aside)."""
        if self.layer_types:
            return self.layer_count("*")
        return (self.n_layers if self.family in ("dense", "moe", "vlm",
                                                  "audio") else 0)

    @property
    def ssm_layers(self) -> int:
        """Layers that keep SSM and conv state."""
        if self.layer_types:
            return self.layer_count("M")
        return self.n_layers if self.family in ("ssm", "hybrid") else 0

    def layer_count(self, kind: str) -> int:
        """Layers of one ``layer_types`` kind ("M", "E", "*")."""
        return self.layer_types.count(kind)

    def layer_is_local(self, i: int) -> bool:
        return self.layer_pattern == "local_global" and i % 2 == 0

    def reduced(self, **overrides) -> "ModelConfig":
        """Smoke-test configuration of the same family (tiny dims)."""
        small = dict(
            n_layers=min(self.n_layers, 2 if self.shared_attn_period == 0 else 4),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 4) if self.n_kv_heads else 0,
            d_head=32,
            d_ff=256 if self.d_ff else 0,  # preserve tree structure (moe)
            vocab_size=512,
            sliding_window=64 if self.sliding_window else None,
            n_experts=min(self.n_experts, 8),
            moe_d_ff=64 if self.n_experts else 0,
            shared_expert_d_ff=64 if self.n_shared_experts else 0,
            ssm_state=min(self.ssm_state, 16),
            ssm_head_dim=32 if self.ssm_state else 64,
            ssm_chunk=32,
            shared_attn_period=2 if self.shared_attn_period else 0,
            n_frontend_tokens=8 if self.frontend else 0,
            flash_block=64,
            tp_head_pad=1,
        )
        small.update(overrides)
        return dataclasses.replace(self, **small)
