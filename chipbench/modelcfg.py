"""Configuration files (``configs/<name>.json``) and what is built from them.

A configuration file holds the published ``config.json`` entries of the
model under their published keys (``bias`` for the q/k/v projections'
biases, ``rope_scaling``), as run (``reduced`` names each key that
was changed from the source), plus the engine and TierScape settings of the
deployment. ``Arch`` is the benchmark's own view of those sizes; the
program's ``ModelConfig`` is built from the same numbers.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    qkv_bias: bool
    tie_embeddings: bool

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def n_params(self) -> int:
        """Parameters used per decoded token (embedding lookup excluded)."""
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)
        ffn = 3 * d * self.d_ff
        return self.n_layers * (attn + ffn) + d * self.vocab


def load_json(kind: str, name: str, root: Path = HERE) -> dict:
    path = Path(root) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file named {name!r} ({path})")
    return json.loads(path.read_text())


def _unscaled_rope(cfg: dict) -> None:
    """Dynamic NTK scaling (the only kind accepted) leaves every position
    below ``max_position_embeddings`` unscaled: the rotation the program and
    the reference apply holds only while no request is longer."""
    scaling = cfg.get("rope_scaling")
    if scaling is None:
        return
    if scaling.get("type") != "dynamic":
        raise ValueError(f"rope_scaling {scaling!r} is not described by this reference")
    if int(cfg["engine"]["max_seq_len"]) > int(cfg["max_position_embeddings"]):
        raise ValueError("max_seq_len passes max_position_embeddings: dynamic "
                         "rope scaling would act")


def arch(cfg: dict) -> Arch:
    if cfg["hidden_act"] != "silu" or cfg.get("family", "dense") != "dense":
        raise ValueError("only dense SwiGLU decoders are described by this reference")
    _unscaled_rope(cfg)
    return Arch(
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        qkv_bias=bool(cfg["bias"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )


def program_config(cfg: dict):
    """The serving program's ``ModelConfig`` for these numbers."""
    from repro.configs.base import ModelConfig

    a = arch(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=a.n_layers, d_model=a.d_model,
        n_heads=a.n_heads, n_kv_heads=a.n_kv_heads, d_ff=a.d_ff, vocab_size=a.vocab,
        qkv_bias=a.qkv_bias, act="swiglu", rope_theta=a.rope_theta,
        norm_eps=a.norm_eps, tie_embeddings=a.tie_embeddings,
    )
