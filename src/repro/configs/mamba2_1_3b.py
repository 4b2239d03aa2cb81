"""mamba2-1.3b — SSD (state-space duality) [arXiv:2405.21060].

hf:state-spaces/mamba2-1.3b ``config.json``: d_model 2048, n_layer 48,
d_intermediate 0 (no FFN), ``ssm_cfg.layer`` "Mamba2", rms_norm,
tie_embeddings, vocab_size 50277 padded to a multiple of
``pad_vocab_size_multiple`` 16 = 50288 rows.  The Mamba-2 layer's
defaults (``mamba_ssm/modules/mamba2.py``): d_state 128, headdim 64,
expand 2, ngroups 1, d_conv 4, chunk_size 256, so 64 SSD heads and
d_inner 4096; every norm at eps 1e-5.  Sub-quadratic: runs the
long_500k cell.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="mamba2-1.3b", family="ssm",
    n_layers=48, d_model=2048, vocab=50288,
    attn_kind="none", d_ff=0,
    ssm_state=128, ssm_expand=2, ssm_headdim=64, ssm_conv=4,
    norm="rmsnorm", norm_eps=1e-5, tie_embeddings=True,
)
