"""The dense decoder family: a configuration file as the program runs it,
the benchmark's own weights, and the yardstick's counts of FLOPs and bytes.

The counts are the algorithm's, from shapes alone, whatever implements it:
a matmul of an [m, k] by a [k, n] is 2mkn FLOPs; a decode step reads every
weight once and the keys and values of the positions its sequences attend.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.families import dense_reference as reference  # noqa: F401


def dims(conf: dict):
    d, H = int(conf["hidden_size"]), int(conf["num_attention_heads"])
    return dict(d=d, L=int(conf["num_hidden_layers"]), H=H,
                KV=int(conf["num_key_value_heads"]),
                hd=int(conf.get("head_dim") or d // H),
                ff=int(conf["intermediate_size"]),
                V=int(conf["vocab_size"]))


def program_config(conf: dict):
    """The program's ``ModelConfig`` for this file."""
    from repro.configs.base import ModelConfig
    k = dims(conf)
    return ModelConfig(
        name=conf["name"], family="dense", n_layers=k["L"], d_model=k["d"],
        n_heads=k["H"], n_kv_heads=k["KV"], d_ff=k["ff"], vocab_size=k["V"],
        head_dim=k["hd"],
        norm_eps=reference.norm_eps(conf),
        rope_theta=float(conf["rope_theta"]), act=conf["hidden_act"],
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        qkv_bias=bool(conf.get("use_qkv_bias", False)),
        dtype=conf["torch_dtype"])


def _leaves(conf: dict) -> Dict:
    """Every weight as (shape, scale); a scale of None is a norm gain."""
    k = dims(conf)
    d, L, H, KV, hd, ff, V = (k[n] for n in ("d", "L", "H", "KV", "hd",
                                             "ff", "V"))
    return {
        "emb": ((V, d), 0.02),
        "ln_f": ((d,), None),
        "unemb": ((d, V), d ** -0.5),
        "layers": {
            "ln1": ((L, d), None),
            "ln2": ((L, d), None),
            "attn": {"wq": ((L, d, H, hd), d ** -0.5),
                     "wk": ((L, d, KV, hd), d ** -0.5),
                     "wv": ((L, d, KV, hd), d ** -0.5),
                     "wo": ((L, H, hd, d), (H * hd) ** -0.5)},
            "mlp": {"wi": ((L, d, ff), d ** -0.5),
                    "wg": ((L, d, ff), d ** -0.5),
                    "wo": ((L, ff, d), ff ** -0.5)},
        },
    }


def make_weights(conf: dict, seed: int):
    """All weights on the device, in the served dtype, in one jitted call:
    matrices N(0, scale^2), norm gains 1 + N(0, 0.1^2)."""
    spec = _leaves(conf)
    flat, tree = jax.tree.flatten(spec, is_leaf=lambda x: isinstance(x,
                                                                     tuple))
    dt = jnp.dtype(conf["torch_dtype"])

    def draw(key):
        out = []
        for i, (shape, scale) in enumerate(flat):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out.append((1.0 + 0.1 * z if scale is None else z * scale
                        ).astype(dt))
        return jax.tree.unflatten(tree, out)

    return jax.jit(draw)(jax.random.PRNGKey(seed))


def matmul_params(conf: dict) -> int:
    """Weights that multiply each token (the embedding is a gather)."""
    k = dims(conf)
    per = (2 * k["d"] * k["H"] * k["hd"] + 2 * k["d"] * k["KV"] * k["hd"]
           + 3 * k["d"] * k["ff"])
    return k["L"] * per + k["d"] * k["V"]


def token_flops(conf: dict, keys: np.ndarray) -> float:
    """Forward FLOPs of tokens that attend ``keys`` positions each."""
    k = dims(conf)
    keys = np.asarray(keys, np.float64)
    return float(len(keys) * 2.0 * matmul_params(conf)
                 + 4.0 * k["L"] * k["H"] * k["hd"] * keys.sum())


def prompt_flops(conf: dict, lengths: Sequence[int]) -> float:
    """Forward FLOPs of whole prompts (causal: position i attends i+1)."""
    return sum(token_flops(conf, np.arange(1, n + 1)) for n in lengths)


def weight_bytes(conf: dict) -> int:
    k = dims(conf)
    itemsize = jnp.dtype(conf["torch_dtype"]).itemsize
    norms = (2 * k["L"] + 1) * k["d"]
    return itemsize * (matmul_params(conf) + norms)


def kv_bytes_per_position(conf: dict) -> int:
    k = dims(conf)
    return (2 * k["L"] * k["KV"] * k["hd"]
            * jnp.dtype(conf["torch_dtype"]).itemsize)


def decode_step_least(conf: dict, keys: np.ndarray, peak_flops: float,
                      peak_bw: float) -> float:
    """Least time of one decode step whose live sequences attend ``keys``
    positions each (the new one included): every weight read once, the
    embedding rows of those tokens, the live keys and values read and the
    new ones written."""
    k = dims(conf)
    itemsize = jnp.dtype(conf["torch_dtype"]).itemsize
    keys = np.asarray(keys, np.float64)
    nbytes = (weight_bytes(conf) + len(keys) * k["d"] * itemsize
              + kv_bytes_per_position(conf) * (keys.sum() + len(keys)))
    return max(token_flops(conf, keys) / peak_flops, nbytes / peak_bw)
