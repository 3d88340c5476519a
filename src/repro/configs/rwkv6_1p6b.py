"""rwkv6-1.6b: RWKV-6 "Finch" 1.6B, an attention-free RNN with
data-dependent token shift and decay.

Source: Peng et al., "Eagle and Finch", arXiv:2404.05892; the published
config of HF ``RWKV/v6-Finch-1B6-HF`` and the RWKV-LM ``v6`` model code:
24 layers, ``n_embd`` 2048, head size 64 (32 heads), FFN
int(3.5 * 2048 // 32) * 32 = 7168, vocabulary 65536, token-shift LoRA rank
32, decay LoRA rank 64, LayerNorm eps 1e-5, ``ln_x`` GroupNorm eps 64e-5
(1e-5 x head_size_divisor^2, divisor 8), untied head.
"""
from repro.configs.base import (BlockKind, ModelConfig, RetrievalConfig,
                                RWKVConfig, register)


@register("rwkv6-1.6b")
def rwkv6_1p6b() -> ModelConfig:
    return ModelConfig(
        name="rwkv6-1.6b",
        family="ssm",
        num_layers=24,
        d_model=2048,
        num_heads=0,             # attention-free
        num_kv_heads=0,
        d_ff=7168,
        vocab_size=65536,
        mlp_activation="relu_sq",  # rwkv channel-mix uses squared relu
        norm_eps=1e-5,
        block_pattern=(BlockKind.RWKV6,),
        rwkv=RWKVConfig(head_dim=64, mix_lora=32, decay_lora=64),
        retrieval=RetrievalConfig(enabled=True),
    )
