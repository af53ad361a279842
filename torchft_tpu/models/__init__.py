"""Model families: flagship llama-style transformer + the reference's
example-scale CNN/MLP (reference train_ddp.py:84-102, train_diloco.py:76-120),
a sparse hybrid decoder (``kimi_linear``: delta-rule linear attention beside
latent attention, a chip's share of sigmoid-routed experts), a sparse decoder
with windowed beside global attention (``afmoe``: gated heads, norms on both
sides of a sub-block, the same expert layer) and a sparse decoder with latent
attention in every layer and a multi-token-prediction module (``joyai``: a
query latent and a rotary part through ``mla``, the function ``kimi_linear``
runs too; a loss over two prediction depths; the same expert layer) and a
sparse decoder whose token mixer is a gated short convolution in three layers
of four (``lfm2``: grouped-query attention in the fourth, the same expert
layer without its shared expert, a head tied to the embedding) and a sparse
decoder with experts in every layer (``mellum``: windowed beside global
attention with a rotary table for each kind, the global one YaRN-scaled; the
same expert layer routed by a softmax over all published experts, holding
more of them than a token chooses, no shared expert) and a sparse decoder
trained as a block-diffusion model (``sdar``: the Qwen3-MoE block under a step
that runs a row twice, noised beside clean, under a three-part mask at block
granularity through ``ops/flash_attention.py`` ``flash_block_diffusion``; a
loss over the masked positions, weighted by the row's noise level, without a
shift; the noise a pure function of the row and a seed)."""

from torchft_tpu.models import afmoe, cnn, joyai, kimi_linear, lfm2, mellum, mla, mlp, sdar, transformer
from torchft_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    make_grad_step,
    make_train_step,
    param_specs,
    shard_params,
)

__all__ = [
    "afmoe",
    "cnn",
    "joyai",
    "kimi_linear",
    "lfm2",
    "mellum",
    "mla",
    "mlp",
    "sdar",
    "transformer",
    "TransformerConfig",
    "init_params",
    "param_specs",
    "shard_params",
    "make_train_step",
    "make_grad_step",
]
