"""kanana-2-30b-a3b — DeepSeek-V3-style MoE (``model_type`` deepseek_v3),
48L d2048: multi-head latent attention without a query LoRA (32 heads,
q/k head 128 nope + 64 interleaved-RoPE, v head 128, kv rank 512 with its
own RMS norm), layer 0 a dense SwiGLU of width 6144, then 47 MoE layers of
128 routed SwiGLU experts of width 768, top-6 by sigmoid score with an
aux-free correction bias (``noaux_tc``, one group), chosen weights
renormalised and scaled by 2.448, plus 2 shared experts (one SwiGLU of
width 1536).  RMS eps 1e-6, RoPE theta 1e6, vocab 128,256 untied.
[hf:kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json]

``SHARE`` is one chip's share of a deployment in which 16 chips share each
layer: experts split 16 ways (experts 0-7 held here), the embedding and
head split 8 ways (16,032 rows), attention, the dense MLP, the shared
experts and the router replicated; the dense layer and the first 4 MoE
layers (layers 5-47 would lie on further chips).  424,960,512 float32
parameters, trained by FedAvg clients (``build_engine(task="kanana2-a3b")``).
"""

from dataclasses import replace

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="kanana-2-30b-a3b",
    family="moe",
    source="hf:kakaocorp/kanana-2-30b-a3b-instruct-2601",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    head_dim=64,
    d_ff=6144,              # the leading dense layer's SwiGLU
    vocab_size=128_256,
    use_bias=False,
    tie_embeddings=False,
    rope_theta=1_000_000.0,
    norm_eps=1e-6,
    moe=True,
    n_experts=128,
    top_k=6,
    moe_d_ff=768,
    moe_every=1,
    moe_score="sigmoid",
    moe_routed_scale=2.448,
    moe_shared_d_ff=1536,   # n_shared_experts 2 x moe_intermediate_size 768
    first_dense_layers=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    mlp_act="swiglu",
    remat=True,
)

# One chip's share (see the module docstring and bench/configs/kanana2-a3b.json)
# and its client batches' sequence length (4 sequences a batch).
SHARE = replace(CONFIG, n_layers=5, experts_held=8, vocab_size=16_032,
                vocab_multiple=1, dtype="float32")
SEQ_LEN = 512
