"""olmoe-1b-7b [moe]: allenai/OLMoE-1B-7B-0924 (OlmoeForCausalLM,
arXiv:2409.02060). 16L d_model=2048, 16 query and 16 KV heads (MHA, hd 128),
64 experts of width 1024, top-8 with the raw softmax weights
(norm_topk_prob false), RMSNorm over the whole q and k projections, vocab
50304, untied head, 4096 positions, rope theta 1e4, RMSNorm eps 1e-5."""
from repro.configs.base import Family, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="olmoe-1b-7b",
    family=Family.MOE,
    n_layers=16,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1024,
    vocab_size=50304,
    moe=MoEConfig(num_experts=64, top_k=8, norm_topk_prob=False),
    norm_eps=1e-5,
    qk_norm=True,
    rope_theta=10000.0,
    max_seq_len=4096,
)
