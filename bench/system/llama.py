"""The system under test's configuration of a llama-style decoder."""

from __future__ import annotations

from repro.models.config import ArchConfig


def arch_config(cfg: dict) -> ArchConfig:
    """The program's `ArchConfig` for a configuration file's sizes."""
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", 0), d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["dtype"],
        remat=False)
