"""ArchConfig: one dataclass describing every supported architecture family.

Families:
  dense   — llama-style decoder (GQA + SwiGLU/GeGLU)
  moe     — dense backbone with MoE FFN (top-k routing, optional shared experts)
  mamba   — the paper's architecture (conv1d_pack + selective_scan blocks)
  hybrid  — RecurrentGemma/Griffin: RG-LRU recurrent blocks + local attention
  xlstm   — mLSTM blocks with interspersed sLSTM
  audio   — encoder-only transformer over precomputed frame embeddings (stub
            frontend per assignment), bidirectional attention
  vlm     — decoder with M-RoPE + vision-embedding injection (stub frontend)

Heterogeneous layer stacks are expressed as a repeating *pattern unit*
(e.g. ("rec", "rec", "attn") for RecurrentGemma): the model stacks whole
units and lax.scan's over them, with any remainder layers applied unstacked.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

REGISTRY = {}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                       # dense|moe|mamba|hybrid|xlstm|audio|vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None    # default d_model // n_heads
    act: str = "swiglu"               # swiglu | geglu
    attn_window: Optional[int] = None  # sliding-window size (None = full)
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, ...]] = None   # vlm only
    encoder_only: bool = False
    tie_embeddings: bool = False
    embed_scale: bool = False         # input embedding × sqrt(d_model), as
                                      # the published Gemma-family models
                                      # do (Mamba does not)
    norm_eps: float = 1e-6
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    moe_token_chunk: int = 0          # >0: lax.map the MoE over token chunks
                                      # (bounds dispatch-buffer memory)
    # Mamba
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None     # default ceil(d_model / 16)
    ssm_variant: str = "mamba1"       # mamba1 (per-channel decay, dh=1) |
                                      # mamba2 (SSD: scalar per-head decay,
                                      # single-matmul blocked schedule)
    ssm_heads: Optional[int] = None   # mamba2: #heads (default d_inner/hd)
    ssm_head_dim: Optional[int] = None  # mamba2: head dim dh (default 64)
    ssm_norm: str = "none"            # mamba2 output gate: "none" (plain
                                      # y·silu(z)) | "rms_gate" (RMSNorm the
                                      # gated product before out_proj, with
                                      # a learned (d_inner,) scale — the
                                      # Mamba-2 `rmsnorm` variant)
    # hybrid / xlstm layer pattern: one entry per layer in the unit
    pattern: Tuple[str, ...] = ()     # e.g. ("rec","rec","attn"); () = homogeneous
    lru_width: Optional[int] = None   # hybrid recurrent width (default d_model)
    lru_gate_blocks: int = 16         # block-diagonal RG-LRU gates (Griffin);
                                      # blocks shard over the model axis
    conv_width: int = 4               # hybrid/xlstm temporal conv width
    proj_factor: float = 2.0          # xlstm mLSTM up-projection factor
    # execution
    dtype: str = "bfloat16"           # activation/compute dtype
    param_dtype: str = "float32"
    use_pallas: Optional[bool] = None  # None: the Mamba-1 train/forward
                                      # scan runs the Pallas kernel where
                                      # the default backend is a TPU and
                                      # the XLA evaluator elsewhere; conv,
                                      # Mamba-2 heads and the state-
                                      # collecting prefills stay on XLA.
                                      # True: Pallas conv + scan everywhere
                                      # (Mosaic on a TPU, interpreted on
                                      # the CPU). False: XLA everywhere.
                                      # The table: models/blocks.py::
                                      # _scan_backend.
    scan_chunk: int = 256             # chunk length for XLA-path scans
    scan_impl: str = "blocked"        # blocked | chunked | fused_seq (XLA
                                      # ssm path; blocked = SSD-style
                                      # block-parallel schedule, the default
                                      # hot path — see core/scan.py)
    scan_intra: Optional[str] = None  # XLA blocked in-chunk evaluator:
                                      # None = auto (mamba1: matmul on TPU
                                      # — which, with use_pallas unset,
                                      # serves only the state-collecting
                                      # prefills there — assoc on CPU;
                                      # mamba2: quad). Force
                                      # "matmul" | "assoc" (mamba1) or
                                      # "quad" | "dual" (mamba2; dual = the
                                      # C·Bᵀ attention-like form, wins when
                                      # head dim ≫ chunk)
    scan_dtype: str = "float32"       # recurrence compute dtype (bf16 halves
                                      # the scan's HBM traffic on the XLA path)
    act_pspec: Optional[Tuple] = None  # sharding constraint on the residual
    #   carry between layer units, e.g. (("pod","data"), "model", None) —
    #   Megatron-SP-style sequence sharding of saved activations
    attn_chunk: Optional[int] = None  # online-softmax KV chunk (None=auto)
    remat: str = "unit"               # none | unit (checkpoint each unit)
    # sub-quadratic? (drives the long_500k skip rule)
    notes: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else \
            self.d_model // self.n_heads

    @property
    def dtr(self) -> int:
        if self.dt_rank is not None:
            return self.dt_rank
        return -(-self.d_model // 16)

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_hd(self) -> int:
        """Mamba-2 head dim dh; enforces d_inner = ssm_heads · ssm_hd."""
        hd = self.ssm_head_dim
        if hd is None:
            hd = (self.d_inner // self.ssm_heads) if self.ssm_heads else 64
        if self.ssm_heads:
            if self.ssm_heads * hd != self.d_inner:
                raise ValueError(
                    f"ssm_heads ({self.ssm_heads}) × head dim ({hd}) != "
                    f"d_inner ({self.d_inner})")
        elif self.d_inner % hd:
            raise ValueError(
                f"d_inner {self.d_inner} not divisible by ssm_head_dim {hd}")
        return hd

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_hd

    @property
    def unit(self) -> Tuple[str, ...]:
        """The repeating layer-pattern unit."""
        if self.pattern:
            return self.pattern
        if self.family == "mamba":
            return ("mamba2",) if self.ssm_variant == "mamba2" else ("mamba",)
        if self.family == "moe":
            return ("moe_attn",)
        return ("attn",)

    @property
    def sub_quadratic(self) -> bool:
        """True if per-token decode state is O(1) w.r.t. context length."""
        kinds = set(self.unit)
        if kinds <= {"mamba", "mamba2", "rec", "mlstm", "slstm"}:
            return True
        # attention present: sub-quadratic iff windowed
        return self.attn_window is not None

    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        k = {}
        if self.n_experts:
            k["n_experts"] = min(self.n_experts, 4)
            k["top_k"] = min(self.top_k, 2)
            k["n_shared_experts"] = min(self.n_shared_experts, 1)
            # no capacity drops at smoke scale (keeps decode parity exact)
            k["capacity_factor"] = 4.0
        if self.mrope_sections is not None:
            k["mrope_sections"] = (2, 3, 3)    # sums to reduced head_dim/2
        if self.family == "hybrid":
            k["lru_gate_blocks"] = 4
        if self.ssm_variant == "mamba2":
            k["ssm_head_dim"] = 16             # 8 heads at d_inner = 128
            k["ssm_heads"] = None
        return dataclasses.replace(
            self, name=self.name + "-smoke",
            n_layers=max(len(self.unit) * 2, 2),
            d_model=64,
            n_heads=4, n_kv_heads=max(1, min(self.n_kv_heads, 2)),
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab=128,
            lru_width=64 if self.lru_width else None,
            dtype="float32", scan_chunk=8, attn_chunk=None, **k)


def register(cfg: ArchConfig) -> ArchConfig:
    REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    # import the config modules for their registration side effects
    from repro import configs as _c  # noqa: F401
    _c.load_all()
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(REGISTRY)}")
    return REGISTRY[name]
