"""gemma-7b [dense] — GeGLU, head_dim=256. 28L d_model=3072 16H (kv=16)
d_ff=24576 vocab=256000 [arXiv:2403.08295; hf]. Tied embeddings + sqrt(d)
embedding scaling (Gemma convention)."""
from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="gemma-7b",
    family="dense",
    n_layers=28,
    d_model=3072,
    n_heads=16,
    n_kv_heads=16,
    head_dim=256,
    d_ff=24576,
    vocab=256000,
    act="geglu",
    tie_embeddings=True,
    embed_scale=True,
    notes="pure full attention ⇒ long_500k cell skipped (quadratic).",
))
