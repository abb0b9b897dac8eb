"""NVIDIA Nemotron-3 Nano 30B-A3B — hybrid of Mamba-2 mixers, sparse
experts and GQA attention, one mixer per block
[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16].

52 blocks in the published ``hybrid_override_pattern``: 23 Mamba-2 (M), 23
MoE (E) and 6 attention (*).  Mamba-2: 64 heads of 64, state 128, 8
groups, conv 4.  MoE: 128 routed experts of width 1856 (relu², not gated),
top-6 by sigmoid score plus a correction bias, weights normalised and
scaled by 2.5, one shared expert of width 3712.  Attention: 32 query and 2
KV heads of 128, read as having no rotary embedding.

As registered, the chip holds every expert; a serving deployment that
splits the experts over chips sets ``experts_held`` and ``expert_offset``.
"""
from .base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="nemotron3-nano-30b-a3b", family="hybrid",
    n_layers=52, d_model=2688, n_heads=32, n_kv_heads=2, d_head=128,
    d_ff=1856, vocab=131072,
    activation="sq_relu",
    n_experts=128, top_k=6, router="sigmoid", routed_scaling=2.5,
    moe_shared_ff=3712,
    layer_pattern="nemotron_h",
    block_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    mamba_heads=64, mamba_head_dim=64, ssm_state=128, ssm_groups=8,
    mamba_conv=4,
    rope=False, norm_eps=1e-5,
    sub_quadratic=False,
    source="https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
           "/blob/main/config.json",
))
