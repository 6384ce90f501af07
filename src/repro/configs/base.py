"""Architecture configuration schema.

One ``ArchConfig`` per assigned architecture (exact hyperparameters from the
assignment table); ``reduced()`` produces the smoke-test scale of the same
family.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

__all__ = ["ArchConfig"]


@dataclass(frozen=True)
class ArchConfig:
    # identity
    name: str
    family: str                 # dense | moe | vlm | hybrid | audio | ssm
    source: str = ""

    # transformer backbone
    n_layers: int = 0
    d_model: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0           # 0 -> d_model // n_heads
    d_ff: int = 0
    vocab_size: int = 0
    qk_norm: bool = False
    use_bias: bool = False
    tie_embeddings: bool = False
    vocab_multiple: int = 256   # embed/lm_head rows padded to a multiple
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6

    # MoE
    moe: bool = False
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0           # per-expert hidden dim
    moe_every: int = 1          # apply MoE on layers where l % moe_every == moe_offset
    moe_offset: int = 0
    capacity_factor: float = 1.25
    # DeepSeek-V3-style routing (dropless expert layer, models/layers.py):
    # 'softmax' routes through the capacity path above; 'sigmoid' scores
    # each expert alone, picks the top-k scores (the correction bias held
    # at 0: no parameter, not trained), renormalises them and scales them
    # by moe_routed_scale; no aux loss.
    moe_score: str = "softmax"
    moe_routed_scale: float = 1.0
    moe_shared_d_ff: int = 0    # shared experts as one SwiGLU of this width
    experts_held: int = 0       # experts 0..held-1 live here (0 -> all)
    first_dense_layers: int = 0  # leading dense layers ahead of the period

    # multi-head latent attention (MLA, mixer 'mla' when kv_lora_rank > 0;
    # RoPE over interleaved pairs)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # hybrid / ssm
    attn_every: int = 1         # jamba: attention on layers where l % attn_every == attn_offset
    attn_offset: int = 0
    ssm_state: int = 0          # mamba d_state
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4

    # encoder-decoder (audio) / multimodal
    enc_layers: int = 0         # >0 -> encoder-decoder
    frontend: str = ""          # 'audio' | 'patch' | '' — stubbed modality input
    frontend_len: int = 0       # frames/patches per example fed as embeddings
    frontend_dim: int = 0       # stub embedding dim (e.g. ViT width); 0 -> d_model

    # block structure details
    mlp_act: str = "swiglu"     # 'swiglu' | 'relu2' | 'gelu'
    parallel_block: bool = False  # command-r style attn ∥ mlp with shared norm
    rope: bool = True
    learned_pos: bool = False   # whisper decoder absolute positions
    max_position: int = 0       # learned-pos table size (set by build_engine)
    ssm_groups: int = 1         # B/C groups (mamba2 'ngroups')

    # execution knobs (the §Perf hillclimbing surface; swapped via replace())
    attn_impl: str = "dense"    # 'dense' | 'chunked' | 'pallas'
    attn_q_chunk: int = 512
    moe_impl: str = "einsum"    # 'einsum' | 'scatter'
    ssd_impl: str = "chunked"   # 'chunked' | 'recurrent' | 'pallas'
    ssd_chunk: int = 128
    remat: bool = False         # jax.checkpoint around each period body
    loss_chunk: int = 2048      # seq-chunked CE (0 = single shot)
    moe_aux_weight: float = 0.01

    # numerics
    dtype: str = "bfloat16"

    # --- derived -----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def d_inner(self) -> int:   # mamba inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def is_attention_layer(self, l: int) -> bool:
        if self.family == "ssm":
            return False
        if self.attn_every <= 1:
            return True
        return l % self.attn_every == self.attn_offset

    def is_moe_layer(self, l: int) -> bool:
        if not self.moe:
            return False
        return l % self.moe_every == self.moe_offset

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def servable(self) -> bool:
        """Has a serving cache (``init_cache``/``prefill``/``decode_step``):
        none for latent attention or leading dense layers."""
        return not (self.mla or self.first_dense_layers)

    @property
    def resolved_frontend_dim(self) -> int:
        return self.frontend_dim or self.d_model

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded to a ``vocab_multiple`` (256: embed/lm_head shard
        evenly over a 16-way TP axis with 128-lane alignment, Megatron-style
        padding; pad columns are masked out of the loss)."""
        m = self.vocab_multiple
        return (self.vocab_size + m - 1) // m * m

    def encoder_cfg(self) -> "ArchConfig":
        """The encoder stack of an enc-dec arch as a standalone config:
        full attention (non-causal applied by the caller), dense MLP, no MoE,
        no cross, no ssm."""
        return replace(self, n_layers=self.enc_layers, enc_layers=0,
                       attn_every=1, moe=False, rope=False, learned_pos=False,
                       parallel_block=False)

    def reduced(self) -> "ArchConfig":
        """Same-family smoke-test scale: small layers/width/experts/vocab."""
        kw = dict(
            n_layers=min(self.n_layers, 4) or 2,
            d_model=min(self.d_model, 64) or 64,
            d_ff=min(self.d_ff, 128) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 256) or 256,
            dtype="float32",
        )
        if self.n_heads:
            kw["n_heads"] = min(self.n_heads, 4)
            kw["n_kv_heads"] = min(self.n_kv_heads or self.n_heads, 2)
            kw["head_dim"] = 16
        if self.moe:
            kw["n_experts"] = min(self.n_experts, 4)
            kw["top_k"] = min(self.top_k, 2)
            kw["moe_d_ff"] = min(self.moe_d_ff, 64)
            if self.experts_held:
                kw["experts_held"] = min(self.experts_held, kw["n_experts"])
            if self.moe_shared_d_ff:
                kw["moe_shared_d_ff"] = min(self.moe_shared_d_ff, 128)
        if self.mla:
            kw["kv_lora_rank"] = min(self.kv_lora_rank, 32)
            kw["qk_nope_head_dim"] = min(self.qk_nope_head_dim, 16)
            kw["qk_rope_head_dim"] = min(self.qk_rope_head_dim, 8)
            kw["v_head_dim"] = min(self.v_head_dim, 16)
        if self.first_dense_layers:
            kw["n_layers"] = self.first_dense_layers + max(
                1, min(self.n_layers - self.first_dense_layers, 3))
        if self.ssm_state:
            kw["ssm_state"] = min(self.ssm_state, 16)
            kw["ssm_head_dim"] = 16
        if self.enc_layers:
            kw["enc_layers"] = min(self.enc_layers, 2)
        if self.attn_every > 1:
            period = self.attn_every
            if self.moe and self.moe_every > 1:
                import math as _math
                period = period * self.moe_every // _math.gcd(
                    period, self.moe_every)
            kw["n_layers"] = max(kw["n_layers"], period)
        if self.frontend:
            kw["frontend_len"] = min(self.frontend_len or 16, 16)
            if self.frontend_dim:
                kw["frontend_dim"] = min(self.frontend_dim, 32)
        if self.learned_pos:
            kw["max_position"] = 128
        kw["loss_chunk"] = 0
        kw["remat"] = False
        return replace(self, **kw)
