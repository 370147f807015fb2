"""mamba2-780m — attention-free Mamba-2 (SSD, state-space duality)
[arXiv:2405.21060; huggingface.co/state-spaces/mamba2-780m].  48 layers,
d_model 1536, expand 2 (d_inner 3072, 48 SSD heads of head_dim 64), d_state
128 with one group of B/C, conv width 4, chunk 256, tied embeddings, vocab
50277 padded to a multiple of 16 (50288), norm epsilon 1e-5.

``chipbench/configs/mamba2-780m.json`` holds the same values with the
departures from the published model and the values taken from the Mamba2
module's defaults (not in ``config.json``)."""

from repro.models.config import ArchConfig, SSMSpec

CONFIG = ArchConfig(
    name="mamba2-780m",
    family="ssm",
    n_layers=48,
    d_model=1536,
    n_heads=0,                  # attention-free
    n_kv_heads=0,
    d_ff=0,
    vocab=50288,
    norm_eps=1e-5,
    tie_embeddings=True,
    ssm=SSMSpec(d_state=128, head_dim=64, expand=2, chunk=256, conv_width=4),
    source="arXiv:2405.21060; huggingface.co/state-spaces/mamba2-780m",
)
