"""The program's side of the dense llama-style decoder that
``references/llama.py`` checks: the configuration's llama keys mapped onto
the repo's ``ArchConfig``."""
from __future__ import annotations

import dataclasses


def arch_config(config: dict):
    """The program's ArchConfig at the sizes ``config`` states, on the
    architecture module it names."""
    from repro.configs import get_arch

    arch = dataclasses.replace(
        get_arch(config["arch"]), n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        n_heads=config["num_attention_heads"],
        n_kv=config["num_key_value_heads"], vocab=config["vocab_size"],
        rope_theta=config["rope_theta"])
    if arch.family != "dense" or arch.head_dim or arch.window or arch.qk_norm:
        raise ValueError(f"{config['arch']} is not a plain dense decoder")
    return arch
