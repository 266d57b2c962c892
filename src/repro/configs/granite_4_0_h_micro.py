"""granite-4.0-h-micro [interleaved]: 40L d=2048, 36 Mamba-2 layers (64 SSD
heads of 64, state 128, gated RMSNorm) and 4 NoPE GQA attention layers
(5, 15, 25, 35; 32H, kv=8, head 64), a SwiGLU MLP of 8192 in every layer,
tied vocab 100352; embeddings x12, each residual branch x0.22, softmax
scale 1/64, logits / 8, RMSNorm eps 1e-5. 3.19e9 parameters.
[hf ibm-granite/granite-4.0-h-micro config.json]"""
from repro.core.arch import ModelArch

ATTENTION_LAYERS = (5, 15, 25, 35)

ARCH = ModelArch(
    name="granite-4.0-h-micro", family="interleaved",
    num_layers=40, hidden=2048, heads=32, kv_heads=8, head_dim=64,
    ffn=8192, vocab=100352, tie_embeddings=True,
    ssm_state=128, ssm_heads=64, ssm_expand=2, ssm_chunk=256,
    layer_types=tuple("attention" if i in ATTENTION_LAYERS else "mamba" for i in range(40)),
    rms_norm_eps=1e-5, attention_multiplier=0.015625, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0, rope=False, ssm_gated_norm=True,
)


def reduced() -> ModelArch:
    """Both kinds, a run of two Mamba layers among single ones, the published
    multipliers."""
    return ModelArch(
        name="granite-reduced", family="interleaved",
        num_layers=6, hidden=128, heads=4, kv_heads=2, head_dim=32,
        ffn=256, vocab=128, tie_embeddings=True,
        ssm_state=16, ssm_heads=4, ssm_expand=2, ssm_chunk=16,
        layer_types=("mamba", "mamba", "attention", "mamba", "attention", "mamba"),
        rms_norm_eps=1e-5, attention_multiplier=0.015625, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0, rope=False, ssm_gated_norm=True,
    )
