"""granite-4.0-h-small — IBM Granite 4.0-H Small, 32B-A9B
(huggingface.co/ibm-granite/granite-4.0-h-small, ``model_type:
granitemoehybrid``).

40 layers of two kinds in one stack, repeating every 10: five Mamba-2
layers, one attention layer, four Mamba-2 layers (attention at layers 5,
15, 25 and 35).  Mamba-2: 128 heads of 64, d_state 128, one group, conv 4
with a bias, chunk 256, a gated RMSNorm.  Attention: 32 query and 8 KV
heads of 128, no positional encoding, scale 1/128.  Every layer ends in an
expert layer (72 experts of width 768, top-10, a softmax over the ten
selected logits) beside a shared SwiGLU MLP of width 1536.  Embedding x12,
residual branches x0.22, logits /16, tied embeddings, RMSNorm eps 1e-5.

``CONFIG`` is the published model, every expert held.  The chip benchmark
serves one chip's share of a 16-chip deployment (``benchmarks/chip/
configs/granite-4.0-h-small.json``): layers 0-19 and experts [0, 9).
"""

from repro.configs import ArchSpec
from repro.models.transformer import ModelConfig

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    n_layers=40,
    d_model=4096,
    n_q_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab=100352,
    block="interleaved",
    layer_types=PERIOD * 4,
    rope_theta=None,
    query_scale=1 / 128,
    n_experts=72,
    top_k=10,
    shared_expert_ff=1536,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_expand=2,
    ssm_chunk=256,
    tied_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    norm_eps=1e-5,
)


def smoke_config() -> ModelConfig:
    """One period of the same pattern at small widths; 16 experts, top-4,
    so that eight shares hold two experts each."""
    return ModelConfig(
        name="granite-smoke",
        n_layers=10,
        d_model=64,
        n_q_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=32,
        vocab=256,
        block="interleaved",
        layer_types=PERIOD,
        rope_theta=None,
        query_scale=1 / 16,
        n_experts=16,
        top_k=4,
        shared_expert_ff=64,
        ssm_state=16,
        ssm_head_dim=16,
        ssm_groups=1,
        ssm_expand=2,
        ssm_chunk=16,
        tied_embeddings=True,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        norm_eps=1e-5,
    )


SPEC = ArchSpec(
    arch_id="granite-4.0-h-small",
    config=CONFIG,
    smoke=smoke_config(),
    long_context=False,  # four full-attention layers keep a KV cache
    notes="Mamba-2 and NoPE attention layers in sequence; 72 experts top-10",
)
