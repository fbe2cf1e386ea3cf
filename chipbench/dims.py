"""A dense GQA decoder's sizes, read from a configuration file.

The configuration files keep the published ``config.json`` key names; this
module is the one place that reads them, for the weights, the reference,
the operation counts and the server alike.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    arch: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    rotary_fraction: float
    qk_norm: bool
    tied: bool

    @property
    def q_width(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.n_kv_heads * self.head_dim


def dims_of(conf: dict[str, Any]) -> Dims:
    return Dims(
        name=conf["name"], arch=conf["arch"],
        n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]),
        d_ff=int(conf["intermediate_size"]),
        vocab=int(conf["vocab_size"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        rotary_fraction=float(conf.get("partial_rotary_factor", 1.0)),
        qk_norm=bool(conf.get("qk_norm", False)),
        tied=bool(conf["tie_word_embeddings"]))


def load_config(path: str | os.PathLike) -> dict[str, Any]:
    with open(path) as f:
        return json.load(f)
