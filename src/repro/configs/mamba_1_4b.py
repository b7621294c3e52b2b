"""mamba-1.4b — paper §4: 48 layers, d_model=2048. Packed seq_len 4096.
Head tied to the embedding, as in state-spaces/mamba-1.4b's config.json."""
from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="mamba-1.4b",
    family="mamba",
    n_layers=48,
    d_model=2048,
    n_heads=1, n_kv_heads=1,
    d_ff=0,
    vocab=50280,
    d_state=16, d_conv=4, expand=2,
    tie_embeddings=True,
))
