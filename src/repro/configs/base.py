"""Model/run configuration schema.

One ``ModelConfig`` instance fully determines an architecture; the ten
assigned architectures live in sibling modules and register themselves in
``repro.configs.registry``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ApproxConfig:
    """MCMA-as-FFN (DESIGN.md §4): n approximators + exact fallback."""

    enable: bool = False
    n_approx: int = 3
    # approximator-library residency: 0 disables (n_approx approximators,
    # all resident — the historic engine).  > 0 trains/stores a LIBRARY of
    # library_size approximators while only n_approx of them occupy the
    # prepadded weight stacks at any moment: routing happens over the full
    # library (router/tick-router heads carry library_size + 1 logits), a
    # runtime residency map folds library classes onto resident slots, and
    # off-set classes fall back to the exact path until promoted
    # (runtime/autotune.ResidencyController).  Must be >= n_approx.
    library_size: int = 0
    d_hidden: int = 256          # approximator hidden width (<< d_ff)
    error_bound: float = 0.10    # relative L2 error vs the exact FFN
    scheme: str = "competitive"  # label scheme for router co-training
    router_weight: float = 0.01  # aux loss weights
    distill_weight: float = 1.0
    # serve-mode capacity fractions (of total tokens): exact path and each
    # approximator.  FLOP savings vs dense FFN = 1 - exact_frac.
    exact_frac: float = 0.5
    invoke_frac: float = 0.4
    # asymmetric per-class capacity fractions (len n_approx) — () keeps the
    # shared invoke_frac for every class.  Heavy-tailed served mixes derive
    # these from per-class count quantiles (runtime/autotune.
    # ladder_from_counts) so a hot approximator gets capacity a cold one
    # would waste as padding.
    invoke_fracs: tuple = ()
    # per-request QoS tiers: n_tiers is the STATIC tier count (shapes of
    # the per-tier invoke stats); tier_bounds are the ascending per-tier
    # error bounds a server quantizes Request.error_bound against (() =
    # single-tier, the global error_bound above); tier_margins are the
    # default per-tier exact-logit router margins — a TRACED input at
    # serve time (runtime/dispatch.route), these are only the static
    # fallback when a caller passes a tier vector without margins.
    n_tiers: int = 1
    tier_bounds: tuple = ()
    tier_margins: tuple = ()
    # per-shard capacity over-provisioning under a mesh (the engine
    # dispatches each data shard's rows against its own budgets, so a
    # class hot on one shard drops rows even when another shard has
    # slack).  >1 buys headroom; sharding/rules.shard_capacity applies it.
    shard_slack: float = 1.0
    # serve-mode dispatch engine (runtime/dispatch.py): "xla" = portable
    # per-class capacity dispatch (the test oracle); "pallas" = the
    # scalar-prefetch weight-switch kernel (kernels/switched_mlp.py);
    # "pallas_fused" = the same kernel with the class-sort gather/scatter
    # fused in (kernels/fused_dispatch.py) — one HBM pass over
    # activations per layer.
    backend: str = "xla"
    # routing granularity at decode (runtime/dispatch.py plan/execute):
    # "layer" = per-layer route -> sort -> dispatch (today's semantics, the
    # only scope the train path uses); "tick" = the paper's one decision
    # per input datum — ONE DispatchPlan per decode tick from the model's
    # tick-router head, reused by every layer of the scan (each layer is
    # just a weight-switch kernel launch on already-sorted rows).
    route_scope: str = "layer"
    block_t: int = 128           # Pallas dispatch row-tile size
    interpret: bool = False      # Pallas interpreter mode (CPU/CI runs)

    @property
    def n_live(self) -> int:
        """Trained approximator count: the library size when a library is
        configured, else n_approx (the historic all-resident engine).
        Weight stacks and router/tick-router heads are sized by THIS;
        capacities and the dispatch plan stay sized by n_approx (the
        resident slots)."""
        return self.library_size or self.n_approx


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_weight: float = 0.01     # load-balancing loss weight
    # GShard-style token groups: tokens are dispatched in chunks of this
    # many (scan), bounding the (E, cap, d) buffers; 0 = one group.
    scan_chunk: int = 32768


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / mLSTM knobs."""

    d_state: int = 64
    expand: int = 2              # d_inner = expand * d_model
    head_dim: int = 64           # Mamba2 P (state head dim)
    chunk: int = 256             # SSD / mLSTM chunk length
    slstm_every: int = 8         # xLSTM: one sLSTM block per this many blocks
    # Mamba2's causal depthwise conv over x, B and C before the SSD (with
    # its own per-slot state at decode); 0 = no conv (zamba2 here)
    conv_width: int = 0
    conv_bias: bool = False


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | layered | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    norm: str = "rmsnorm"        # rmsnorm | layernorm | nonparam_ln
    act: str = "silu"            # FFN activation; "swiglu" = gated
    gated_ffn: bool = True
    rope_base: float = 10_000.0
    rope_pct: float = 1.0        # partial rotary (stablelm-2: 0.25)
    parallel_block: bool = False # attn+FFN in parallel (stablelm-2 style)
    qkv_bias: bool = False
    sliding_window: int = 0      # 0 = full attention (mixtral: 4096)
    tie_embeddings: bool = False
    input_mode: str = "tokens"   # tokens | embeddings (audio/vlm stubs)
    moe: MoEConfig = MoEConfig()
    ssm: SSMConfig = SSMConfig()
    approx: ApproxConfig = ApproxConfig()
    # hybrid wiring (zamba2): shared attention block applied every N layers
    attn_every: int = 0          # 0 = no shared-attn interleave
    # layered wiring (family "layered", granite-4.0-h): the mixer of each
    # layer, "mamba" or "attention", each followed by the layer's own FFN
    layer_types: tuple = ()
    # granite's scalings; the neutral defaults compile to no operation
    embedding_multiplier: float = 1.0    # embeddings x this
    residual_multiplier: float = 1.0     # each sublayer's output x this
    attention_multiplier: float = 0.0    # softmax scale; 0 -> hd ** -0.5
    logits_scaling: float = 1.0          # logits / this
    # numerics / structure
    param_dtype: str = "bfloat16"
    act_dtype: str = "bfloat16"
    remat: bool = True
    scan_layers: bool = True
    # microbatch gradient-accumulation factor for train_4k (memory knob;
    # sized so per-chip residuals fit v5e HBM — see EXPERIMENTS.md §Dry-run)
    grad_accum: int = 1
    # residual-stream sharding between blocks: "dp" batch-only (baseline),
    # "fp" feature-sharded over the TP axis (shards the per-layer remat
    # saves 16x — required for the 76B train cell; see EXPERIMENTS.md §Perf)
    act_shard: str = "dp"
    # attention flash-scan block sizes (perf knobs for §Perf)
    q_block: int = 512
    kv_block: int = 512
    decode_flash_threshold: int = 8192   # decode uses direct attn below this

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def attn_scale(self) -> float:
        return self.attention_multiplier or self.hd ** -0.5

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def adtype(self):
        return jnp.dtype(self.act_dtype)

    def params_count(self) -> int:
        """Analytic parameter count N (for 6*N*D model FLOPs)."""
        d, hd = self.d_model, self.hd
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
            + (self.n_heads * hd) * d
        if self.family in ("dense", "moe", "audio", "vlm"):
            if self.moe.n_experts:
                ffn = self.moe.n_experts * (3 if self.gated_ffn else 2) * d * self.d_ff \
                    + d * self.moe.n_experts
            else:
                ffn = (3 if self.gated_ffn else 2) * d * self.d_ff
            per_layer = attn + ffn
        elif self.family == "ssm":      # xLSTM blocks (see mlstm.py)
            d_in = self.ssm.expand * d
            per_layer = 2 * d * d_in + d_in * d + 3 * d_in  # up/gate/down + qkv-ish
        elif self.family == "hybrid":   # mamba2 + shared attn + FFN
            d_in = self.ssm.expand * d
            mamba = d * (2 * d_in + 2 * self.ssm.d_state) + d_in * d
            ffn = (3 if self.gated_ffn else 2) * d * self.d_ff
            per_layer = mamba + ffn
        elif self.family == "layered":  # per-layer mixer + its own FFN
            s = self.ssm
            d_in = s.expand * d
            n_heads = d_in // s.head_dim
            conv = (s.conv_width + s.conv_bias) * (d_in + 2 * s.d_state)
            mamba = d * (2 * d_in + 2 * s.d_state + n_heads) + conv \
                + d_in * d
            ffn = (3 if self.gated_ffn else 2) * d * self.d_ff
            n_attn = self.layer_types.count("attention")
            return emb + n_attn * attn \
                + (self.n_layers - n_attn) * mamba + self.n_layers * ffn
        return emb + self.n_layers * per_layer

    def active_params_count(self) -> int:
        """Active parameters per token (MoE: top_k of n_experts)."""
        if not self.moe.n_experts:
            return self.params_count()
        d = self.d_model
        full = self.params_count()
        all_experts = self.n_layers * self.moe.n_experts * \
            (3 if self.gated_ffn else 2) * d * self.d_ff
        active = self.n_layers * self.moe.top_k * \
            (3 if self.gated_ffn else 2) * d * self.d_ff
        return full - all_experts + active


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell (assigned per architecture)."""

    name: str                    # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                    # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}
