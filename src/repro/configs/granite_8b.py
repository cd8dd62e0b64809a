"""granite-8b — llama-arch dense GQA, code model.

Source: IBM Granite Code 8B, ibm-granite/granite-8b-code-base
config.json on Hugging Face (arXiv:2405.04324), as the benchmark's
`bench/configs/granite-8b.json` names it. No copy of that config.json
is in the repository, so two things are unverified: `rope_theta` (1e6
here), and the projection biases the checkpoints are believed to set
(`attention_bias`, `mlp_bias`), which the dense family does not have.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-8b", family="dense",
    num_layers=36, d_model=4096, num_heads=32, kv_heads=8,
    d_ff=14336, vocab=49152, head_dim=128, rope_theta=1e6,
)


def smoke_config():
    return ModelConfig(
        name="granite-smoke", family="dense",
        num_layers=2, d_model=64, num_heads=4, kv_heads=2,
        d_ff=96, vocab=256, head_dim=16)
