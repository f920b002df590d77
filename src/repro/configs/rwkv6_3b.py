"""rwkv6-3b [ssm]: 32L d_model=2560 (attention-free) d_ff=8960 vocab=65536,
40 heads of 64, decay LoRA 64 -- the widths of RWKV-6 "Finch" 3B
[arXiv:2404.05892].

The block is the matrix-state recurrence of models/rwkv.py with Finch's
data-dependent decay, but it departs from the published Finch block in five
ways:

  1. the token-shift mix is a static learned blend per stream, where Finch
     makes it data-dependent (the ``ddlerp`` LoRA);
  2. the recurrence's output is normalised by one LayerNorm over the whole
     width, where Finch uses a GroupNorm per head;
  3. the channel mix is a squared-ReLU MLP with no receptance gate and no
     token shift;
  4. the pre-norms are RMSNorm, where Finch uses LayerNorm;
  5. there is no ``ln0`` LayerNorm after the embedding.
"""
from repro.models.config import Block, ModelConfig

CONFIG = ModelConfig(
    name="rwkv6-3b",
    family="ssm",
    d_model=2560,
    n_heads=40,            # d_model / rwkv_head_dim; informational
    n_kv_heads=40,
    head_dim=64,
    d_ff=8960,
    vocab_size=65536,
    pattern=(Block("rwkv"),),
    n_periods=32,
    act="relu2",
    glu=False,
    tie_embeddings=False,
    rwkv_head_dim=64,
    n_microbatches=4,
)

SMOKE = CONFIG.scaled_down(
    n_microbatches=1,
    d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
    vocab_size=512, n_periods=2, rwkv_head_dim=16,
)
