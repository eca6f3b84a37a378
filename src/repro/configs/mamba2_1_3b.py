"""mamba2-1.3b — attention-free SSD (state-space duality) [arXiv:2405.21060].

Source: https://huggingface.co/state-spaces/mamba2-1.3b — d_model 2048, 48
layers, the Mamba2 block's defaults (d_state 128, d_conv 4, expand 2,
headdim 64, ngroups 1, gated RMSNorm with norm_before_gate=False), RMSNorm
eps 1e-5, tied embeddings, no MLP.  The vocabulary of 50277 ids is padded
to a multiple of 8 rows (50280).  ``chunk_size`` 128 blocks the same scan
that the published kernel blocks by 256.
"""
from repro.configs.base import ArchConfig, SSMConfig, VerticalConfig, register

MAMBA2_1_3B = register(
    ArchConfig(
        name="mamba2-1.3b",
        family="ssm",
        num_layers=48,
        d_model=2048,
        num_heads=0,  # attention-free
        num_kv_heads=0,
        d_ff=0,
        vocab_size=50280,
        norm_eps=1e-5,
        tie_embeddings=True,
        ssm=SSMConfig(d_state=128, expand=2, head_dim=64, n_groups=1,
                      conv_width=4, chunk_size=128),
        vertical=VerticalConfig(num_clients=4, tower_layers=2, merge="avg"),
        source="https://huggingface.co/state-spaces/mamba2-1.3b",
    )
)
