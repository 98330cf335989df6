"""SmolLM-360M, a small llama-architecture decoder
[hf:HuggingFaceTB/SmolLM-360M].

Published widths; unlike the published model (tie_word_embeddings), the
input embedding and the LM head are separate matrices here, so the config
has 409 M parameters instead of 362 M."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="smollm-360m", family="dense",
    n_layers=32, d_model=960, n_heads=15, n_kv_heads=5, d_ff=2560,
    vocab_size=49152,
    source="llama-arch small, embeddings untied unlike the published "
           "model [hf:HuggingFaceTB/SmolLM-360M]",
)
