"""Config dataclasses: model architecture + input shapes + runtime knobs."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling (HF transformers' ``rope_type: yarn``): the
    frequencies are blended between interpolated (÷ ``factor``) and
    extrapolated by a linear ramp between the correction dims that
    ``beta_fast`` and ``beta_slow`` rotations give at ``original_max``
    positions, and cos/sin are multiplied by ``attention_factor``."""
    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim_: Optional[int] = None
    # attention / norm / act
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    rope_pct: float = 1.0
    pos_embed: str = "rope"        # rope | learned | none
    norm: str = "rmsnorm"
    norm_eps: float = 1e-5
    activation: str = "silu"
    attention_impl: str = "xla"    # xla | pallas (DASH kernels)
    attn_chunk_q: int = 1024       # q-chunked attention above this seq (HBM bound)
    attn_window: int = 0           # sliding-window size in tokens (0 = full);
                                   # lowers as masks.SlidingWindow on both impls
    layer_windows: Tuple[int, ...] = ()  # per block_pattern position: its
                                   # attn_window (0 = full); () = attn_window
                                   # at every position
    rope_yarn: Optional[Yarn] = None  # YaRN rope; with layer_windows, on the
                                   # full-attention positions only
    packed_inputs: bool = False    # batches carry segment_ids/positions from
                                   # the deterministic sequence packer
                                   # (data.pipeline.pack_documents): attention
                                   # is segment-masked, RoPE restarts per doc
    # moe
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    renorm_topk: bool = True
    n_shared_experts: int = 0
    moe_aux_weight: float = 0.01
    moe_impl: str = "einsum"       # einsum (MeshTF, paper-era baseline) | gather
    moe_groups: int = 1            # >1: split seq into token-parallel dispatch
                                   # groups (GShard-style; pairs with seq_sp)
    moe_held: Tuple[int, ...] = () # expert ids this chip holds of the
                                   # n_experts routed (models.moe.
                                   # apply_moe_held: no token dropped);
                                   # () = every expert, einsum/gather paths
    # ssm
    ssm_expand: int = 2
    ssm_state_dim: int = 16
    ssm_conv: int = 4
    ssm_chunk: int = 512
    # structure
    block_pattern: Tuple[str, ...] = ("attn",)
    encoder: Optional["ModelConfig"] = None
    frontend: Optional[str] = None          # vision | audio
    frontend_dim: int = 0
    frontend_len: int = 0                   # stub embedding count (patches/frames)
    tie_embeddings: bool = False
    max_seq: int = 32_768
    # sharding hints (mesh model axis = 16; see DESIGN.md §5)
    shard_heads: bool = True
    shard_kv: bool = True
    attn_seq_shard: bool = False   # when heads unshardable: shard q-seq over
                                   # model (worth it for big archs — llama4;
                                   # loses for small ones — whisper/internvl)
    # numerics
    dtype_name: str = "bfloat16"
    vocab_pad: int = 2048                   # pad vocab to multiple of tp*128
    scan_unroll: bool = False               # unroll the layer scan (cost probes)
    det_embed_grad: bool = True    # embedding bwd as a sorted, fixed-order
                                   # segment sum gathered once a row (no
                                   # unordered scatter-add); False restores
                                   # the gather-grad scatter — flagged by
                                   # repro.verify.trace
    canonical_reductions: int = 0  # 0 = fused XLA reductions (training
                                   # default). N>0 = serve-canonical mode:
                                   # forward() runs under dist.fold's
                                   # topology-invariant fold discipline with an
                                   # N-token paged attention walk, bitwise
                                   # matching ContinuousEngine prefill at
                                   # page_size=N (train≡serve parity)

    @property
    def head_dim(self) -> int:
        return self.head_dim_ or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // self.vocab_pad) * self.vocab_pad

    @property
    def dtype(self):
        return jnp.dtype(self.dtype_name)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self, **kw) -> "ModelConfig":
        """Smoke-test scale: one pattern repeat, tiny widths, same structure."""
        kvr = max(1, self.n_heads // max(1, self.n_kv_heads))  # keep GQA ratio
        small = dict(
            n_layers=len(self.block_pattern),
            d_model=128, n_heads=4, n_kv_heads=max(1, 4 // kvr), head_dim_=32,
            d_ff=256 if self.d_ff else 0, vocab=512, vocab_pad=128,
            n_experts=4 if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            moe_held=self.moe_held[:2],
            frontend_dim=64 if self.frontend_dim else 0,
            frontend_len=16 if self.frontend_len else 0,
            max_seq=256, ssm_chunk=32,
            shard_heads=True, shard_kv=True,
            encoder=self.encoder.reduced() if self.encoder else None,
        )
        small.update(kw)
        return self.replace(**small)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    kind: str                      # train | prefill | decode
    seq: int
    batch: int


SHAPES = {
    "train_4k": InputShape("train_4k", "train", 4_096, 256),
    "prefill_32k": InputShape("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": InputShape("decode_32k", "decode", 32_768, 128),
    "long_500k": InputShape("long_500k", "decode", 524_288, 1),
}


def shape_applicable(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """Spec rules: long_500k only for sub-quadratic archs (SSM/hybrid);
    decode shapes skipped for encoder-only archs (none assigned)."""
    if shape.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return False, ("long_500k needs sub-quadratic attention; "
                       f"{cfg.name} is pure full/causal attention (skip per spec)")
    return True, ""
