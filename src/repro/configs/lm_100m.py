"""lm-100m [dense]: the ~100M-parameter LM that examples/train_e2e.py and
chip_smoke.py train (12 x 768, 32k vocab, fp32 compute). Not in the arch
registry: it is the training workload of the examples, not an assigned
architecture."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="lm-100m", family="dense", num_layers=12, d_model=768,
    num_heads=12, num_kv_heads=12, head_dim=64, d_ff=2048,
    vocab_size=32_000, layer_pattern=("attn",), mlp_kind="swiglu",
    tie_embeddings=True, dtype="float32")
