"""The dense decoder: RMSNorm, rotary embeddings (rotate-half, as the
Llama and Phi-3 code applies them), grouped-query causal attention over
the whole causal context, SwiGLU MLP, tied or untied head.

An architecture module is what the harness and the metric readers know
of a model.  A configuration file names its module under ``"arch"``
(``chipbench/archs/<arch>.py``; ``dense`` where the key is absent), and
the module gives:

- ``dims_of(conf, rehearse)``: the sizes, frozen and hashable, with
  ``vocab``; ``rehearse`` gives the tiny CPU shape;
- ``program_config(name, dims)``: the serving program's ``ModelConfig``;
- ``make_weights(dims, seed, dtype)``: the tree the program serves, made
  on the device from the seed;
- ``logits_at`` and ``gaps``: the plain float32 reference at
  ``Precision.HIGHEST`` and the float8 control (``chipbench/reference.py``);
- ``linear_flops``, ``head_flops``, ``attn_flops``, ``prefill_attn_flops``
  and ``kernel_bytes``: the work the algorithm needs (``chipbench/costs.py``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as R
from chipbench.costs import STATED_BYTES
from chipbench.weights import draws, key_from_seed


# ----------------------------------------------------------------- sizes --
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


# --------------------------------------------------------------- weights --
@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _make(key, *, dims: Dims, dtype):
    """``embed.table`` (padded vocab, D), ``lm_head`` (D, padded vocab)
    unless tied, ``final_norm.scale``, and the layers stacked on a leading
    axis (``norm1``, ``attn.wq/wk/wv/wo``, ``norm2``,
    ``ffn.w_gate/w_up/w_down``)."""
    d, hd, h, k, f = dims.d, dims.hd, dims.h, dims.kv, dims.ff
    nl, vp = dims.layers, dims.padded_vocab
    normal, scale = draws(key, 12, dtype)
    tree = {
        "embed": {"table": normal((vp, d), 0.02)},
        "final_norm": {"scale": scale((d,))},
        "layers": {
            "norm1": {"scale": scale((nl, d))},
            "norm2": {"scale": scale((nl, d))},
            "attn": {"wq": normal((nl, d, h * hd), d ** -0.5),
                     "wk": normal((nl, d, k * hd), d ** -0.5),
                     "wv": normal((nl, d, k * hd), d ** -0.5),
                     "wo": normal((nl, h * hd, d), (h * hd) ** -0.5)},
            "ffn": {"w_gate": normal((nl, d, f), d ** -0.5),
                    "w_up": normal((nl, d, f), d ** -0.5),
                    "w_down": normal((nl, f, d), f ** -0.5)},
        },
    }
    if not dims.tied:
        tree["lm_head"] = normal((d, vp), d ** -0.5)
    return tree


def make_weights(dims: Dims, seed: int, dtype=jnp.bfloat16):
    return _make(key_from_seed(seed), dims=dims, dtype=dtype)


# ------------------------------------------------------------- reference --
@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, layers, li, *, dims: Dims, quant: bool):
    lw = jax.tree.map(lambda a: a[li], layers)
    s_len = x.shape[0]
    pos = jnp.arange(s_len)
    hn = R.rmsnorm(x, lw["norm1"]["scale"], dims.eps)
    a = lw["attn"]
    q = R.mm(hn, a["wq"], quant).reshape(s_len, dims.h, dims.hd)
    k = R.mm(hn, a["wk"], quant).reshape(s_len, dims.kv, dims.hd)
    v = R.mm(hn, a["wv"], quant).reshape(s_len, dims.kv, dims.hd)
    q, k = R.rope(q, pos, dims.theta), R.rope(k, pos, dims.theta)
    x = x + R.mm(R.attention(q, k, v, quant), a["wo"], quant)
    hn = R.rmsnorm(x, lw["norm2"]["scale"], dims.eps)
    f = lw["ffn"]
    gate = R.mm(hn, f["w_gate"], quant)
    up = R.mm(hn, f["w_up"], quant)
    return x + R.mm(jax.nn.silu(gate) * up, f["w_down"], quant)


@jax.jit
def _embed(weights, tokens):
    return weights["embed"]["table"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _logits(weights, x, rows, *, dims: Dims, quant: bool):
    """Logits (len(rows), vocab) at hidden rows ``rows``."""
    hn = R.rmsnorm(x[rows], weights["final_norm"]["scale"], dims.eps)
    head = (weights["embed"]["table"].T if dims.tied
            else weights["lm_head"])
    return R.mm(hn, head, quant)[:, :dims.vocab]


def logits_at(weights, dims: Dims, tokens, rows, *, pad_to: int,
              quant: bool = False) -> np.ndarray:
    """Reference logits after ``tokens`` at positions ``rows``, one layer
    at a time (padded as ``reference.padded`` says)."""
    toks, r = R.padded(tokens, rows, pad_to)
    x = _embed(weights, toks)
    for li in range(dims.layers):
        x = _layer(x, weights["layers"], jnp.int32(li), dims=dims,
                   quant=quant)
    out = _logits(weights, x, r, dims=dims, quant=quant)
    return np.asarray(out)[:len(rows)]


gaps = functools.partial(R.gaps, logits_at)


# ---------------------------------------------------------------- counts --
def linear_flops(d: Dims) -> int:
    """Matmul FLOPs of the layers for one token (no head, no attention)."""
    per_layer = (d.d * d.h * d.hd + 2 * d.d * d.kv * d.hd + d.h * d.hd * d.d
                 + 3 * d.d * d.ff)
    return 2 * d.layers * per_layer


def head_flops(d: Dims) -> int:
    """FLOPs of the output head for one token whose logits are computed."""
    return 2 * d.d * d.vocab


def attn_flops(d: Dims, ctx: int) -> int:
    """Attention FLOPs of one query over ``ctx`` keys, all layers."""
    return 4 * d.layers * d.h * d.hd * ctx


def prefill_attn_flops(d: Dims, start: int, n: int) -> int:
    """Attention FLOPs of queries at positions ``start .. start+n-1``,
    each over its causal context (position + 1 keys)."""
    ctx_sum = n * start + n * (n + 1) // 2
    return attn_flops(d, ctx_sum)


def kernel_bytes(d: Dims, ctx_sum: int, rows: int) -> int:
    """Paged decode attention bytes: K and V of ``ctx_sum`` live tokens,
    plus q and o of ``rows`` rows, all layers, at the stated dtype."""
    kv = 2 * ctx_sum * d.kv * d.hd
    qo = 2 * rows * d.h * d.hd
    return d.layers * (kv + qo) * STATED_BYTES
