"""The program's config for a Qwen2 configuration file, at every value
the file states (the program is run as the configuration says)."""
from __future__ import annotations


def program_config(c: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=c["name"], family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        qkv_bias=True, tie_embeddings=c["tie_word_embeddings"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        grad_accum=1)
