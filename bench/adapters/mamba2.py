"""The program's config for a Mamba-2 configuration file, at every value
the file states (the program is run as the configuration says)."""
from __future__ import annotations


def program_config(c: dict):
    from repro.models.config import ModelConfig, SSMConfig
    return ModelConfig(
        name=c["name"], family="ssm", n_layers=c["n_layer"],
        d_model=c["d_model"], n_heads=0, n_kv_heads=0, d_ff=0,
        vocab=c["vocab_size"], tie_embeddings=c["tie_embeddings"],
        norm_eps=c["rms_norm_eps"],
        ssm=SSMConfig(d_state=c["d_state"], head_dim=c["headdim"],
                      expand=c["expand"], conv_width=c["d_conv"],
                      n_groups=c["ngroups"], chunk=c["chunk_size"]),
        grad_accum=1)
