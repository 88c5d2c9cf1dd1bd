"""A configuration file as the benchmark reads it.

``Dims`` holds the sizes the benchmark's own code needs (weights, the
reference, the FLOP and byte counts); ``program_config`` builds the
serving program's ``ModelConfig`` from the same file, so the program runs
exactly the sizes the file states.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    h: int
    kv: int
    hd: int
    ff: int
    vocab: int
    tied: bool
    eps: float
    theta: float

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 128) * 128


def load_config(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def dims_of(conf: dict, *, rehearse: bool = False) -> Dims:
    """Sizes of a configuration file; ``rehearse`` gives the tiny CPU
    shape the program's ``ModelConfig.reduced()`` uses."""
    if rehearse:
        return Dims(layers=2, d=128, h=4, kv=min(conf["num_key_value_heads"],
                                                  2),
                    hd=32, ff=256, vocab=512,
                    tied=bool(conf["tie_word_embeddings"]),
                    eps=float(conf["rms_norm_eps"]),
                    theta=float(conf["rope_theta"]))
    h = conf["num_attention_heads"]
    return Dims(layers=conf["num_hidden_layers"], d=conf["hidden_size"],
                h=h, kv=conf["num_key_value_heads"],
                hd=conf.get("head_dim") or conf["hidden_size"] // h,
                ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                tied=bool(conf["tie_word_embeddings"]),
                eps=float(conf["rms_norm_eps"]),
                theta=float(conf["rope_theta"]))


def program_config(name: str, dims: Dims):
    """The serving program's ``ModelConfig`` for these sizes."""
    from repro.configs.base import ModelConfig
    return ModelConfig(arch_id=name, family="dense", n_layers=dims.layers,
                       d_model=dims.d, n_heads=dims.h, n_kv_heads=dims.kv,
                       d_ff=dims.ff, vocab_size=dims.vocab, head_dim=dims.hd,
                       rope_theta=dims.theta, norm_eps=dims.eps,
                       tie_embeddings=dims.tied, dtype="bfloat16")
