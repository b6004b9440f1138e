"""deepseek-v2-lite — DeepSeek-V2-Lite, 15.7B-A2.4B
(huggingface.co/deepseek-ai/DeepSeek-V2-Lite, ``model_type: deepseek_v2``).

27 layers at width 2048.  Every layer's mixer is latent attention (MLA):
16 heads, each query a direct projection to 128 dimensions without RoPE
and 64 with it; K and V expand from one shared latent of 512 (after an
RMSNorm) to 128 + 128 per head, beside one 64-wide RoPE key shared by
the heads.  Only the latent and that key are cached: 576 values per token
per layer.  RoPE turns interleaved pairs, stretched by YaRN (factor 40
over an original 4096 positions, beta 32/1, mscale 0.707 both ways); the
softmax scale is ``192^-0.5 * mscale(40, 0.707)^2``.  Layer 0's FFN is a
dense SwiGLU of 10944; layers 1-26 hold 64 routed experts of 1408 (a
softmax over all 64, the 6 largest probabilities, not renormalised,
scaled by 1) beside 2 shared experts, one SwiGLU of 2816.  RMSNorm eps
1e-6, untied ``lm_head``, vocabulary 102400.

``CONFIG`` is the published model, every expert held.  The chip benchmark
serves one chip's share of an 8-chip expert-parallel deployment
(``benchmarks/chip/configs/deepseek-v2-lite.json``): all 27 layers,
experts [0, 8).
"""

from repro.configs import ArchSpec
from repro.models.layers import Yarn
from repro.models.transformer import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    n_layers=27,
    d_model=2048,
    n_q_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=1408,
    d_ff_dense=10944,
    vocab=102400,
    block="interleaved",
    layer_types=("mla",) * 27,
    n_dense_lead=1,
    rope_theta=10000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn=Yarn(factor=40.0, original_max_position_embeddings=4096,
              beta_fast=32.0, beta_slow=1.0, mscale=0.707,
              mscale_all_dim=0.707),
    n_experts=64,
    top_k=6,
    shared_expert_ff=2816,
    router="softmax_topk",
    norm_eps=1e-6,
)


def smoke_config() -> ModelConfig:
    """A dense lead layer and four MLA expert layers at small widths; 16
    experts, top-4, so that eight shares hold two each; YaRN over an
    original 16 positions, so that smoke sequences pass it."""
    return ModelConfig(
        name="deepseek-v2-smoke",
        n_layers=5,
        d_model=64,
        n_q_heads=4,
        n_kv_heads=4,
        head_dim=16,
        d_ff=32,
        d_ff_dense=96,
        vocab=256,
        block="interleaved",
        layer_types=("mla",) * 5,
        n_dense_lead=1,
        rope_theta=10000.0,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        yarn=Yarn(factor=4.0, original_max_position_embeddings=16,
                  beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                  mscale_all_dim=0.707),
        n_experts=16,
        top_k=4,
        shared_expert_ff=64,
        router="softmax_topk",
        norm_eps=1e-6,
    )


SPEC = ArchSpec(
    arch_id="deepseek-v2-lite",
    config=CONFIG,
    smoke=smoke_config(),
    long_context=False,  # the latent cache grows with the context
    notes="latent attention (MLA) with YaRN; a dense lead layer, then 64 "
          "experts top-6 beside 2 shared experts",
)
