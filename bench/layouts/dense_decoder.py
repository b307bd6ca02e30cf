"""The dense decoder's weights (Llama / Qwen2 layout), as the program's
``Family.DENSE`` takes them and ``references/dense_decoder.py`` reads them.

A layout module is found by a configuration's ``"layout"`` key
(``model.load_layout``) and gives the harness everything that depends on
how a model's weights are laid out:

* ``program_config(cfg)``: the program's ``ModelConfig``;
* ``make_weights(cfg, seed)``: the weights drawn from the seed, on the
  device in one jitted call and in the type they are served in;
* ``linear_shapes(cfg)``: the (K, N) of the W8A8 projections one token
  multiplies in one layer;
* ``matmul_shapes(cfg)``: the (K, N) of every matrix one token multiplies
  in one layer, W8A8 or not (a routed expert's too).

``costs.py`` counts operations and bytes from the last two, and the LM
head.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import model as M

# init scales of the seeded weights (also stated in each config's "assumed")
EMBED_STD = 0.02
BIAS_STD = 0.02
GAIN_STD = 0.1
# the RMSNorm eps that models/common.apply_norm fixes where the program's
# ModelConfig has no norm_eps field
PROGRAM_FIXED_EPS = 1e-6


def program_config(cfg: dict):
    """The program's ``ModelConfig``. A configuration whose RMSNorm eps the
    program cannot take is refused, not served with another."""
    from repro.configs.base import Family, ModelConfig
    n = M.dims(cfg)
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only gated SiLU MLPs are served")
    eps = float(cfg["rms_norm_eps"])
    norm = {}
    if "norm_eps" in {f.name for f in dataclasses.fields(ModelConfig)}:
        norm["norm_eps"] = eps
    elif eps != PROGRAM_FIXED_EPS:
        raise ValueError(f"{cfg['name']}: rms_norm_eps {eps}, but the "
                         f"program's RMSNorm fixes {PROGRAM_FIXED_EPS}")
    return ModelConfig(
        name=cfg["name"], family=Family.DENSE, n_layers=n["L"],
        d_model=n["d"], n_heads=n["H"], n_kv_heads=n["K"], d_ff=n["ff"],
        vocab_size=n["V"], d_head=int(cfg.get("head_dim") or 0),
        qkv_bias=bool(cfg["qkv_bias"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        max_seq_len=int(cfg["max_position_embeddings"]),
        dtype=cfg["torch_dtype"], **norm)


def linear_shapes(cfg: dict) -> List[Tuple[int, int]]:
    """(K, N) of the W8A8 projections of one layer: QKV, O, up, gate,
    down."""
    n = M.dims(cfg)
    d, H, K, hd, ff = n["d"], n["H"], n["K"], n["hd"], n["ff"]
    return [(d, (H + 2 * K) * hd), (H * hd, d), (d, ff), (d, ff), (ff, d)]


# every projection is W8A8
matmul_shapes = linear_shapes


def _shapes(cfg: dict) -> dict:
    n = M.dims(cfg)
    L, d, H, K, hd, ff, V = (n[k] for k in ("L", "d", "H", "K", "hd", "ff",
                                             "V"))
    return {"embed": (V, d), "wqkv": (L, d, (H + 2 * K) * hd),
            "bqkv": (L, (H + 2 * K) * hd), "wo": (L, H * hd, d),
            "w_up": (L, d, ff), "w_gate": (L, d, ff), "w_down": (L, ff, d),
            "ln1": (L, d), "ln2": (L, d), "ln_f": (d,)}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, cfg_items: tuple, dtype: str):
    cfg = dict(cfg_items)
    n = M.dims(cfg)
    sh = _shapes(cfg)
    ks = dict(zip(sh, jax.random.split(key, len(sh))))
    dt = jnp.dtype(dtype)
    out_scale = 1.0 / np.sqrt(2 * n["L"])

    def normal(name, std):
        return (jax.random.normal(ks[name], sh[name], jnp.float32)
                * std).astype(dt)

    def gain(name):
        return (1.0 + GAIN_STD * jax.random.normal(
            ks[name], sh[name], jnp.float32)).astype(dt)

    attn = {"wqkv": normal("wqkv", 1.0 / np.sqrt(n["d"])),
            "wo": normal("wo", out_scale / np.sqrt(n["H"] * n["hd"]))}
    if cfg["qkv_bias"]:
        attn["bqkv"] = normal("bqkv", BIAS_STD)
    mlp = {"w_up": normal("w_up", 1.0 / np.sqrt(n["d"])),
           "w_gate": normal("w_gate", 1.0 / np.sqrt(n["d"])),
           "w_down": normal("w_down", out_scale / np.sqrt(n["ff"]))}
    out = {"embed": {"w": normal("embed", EMBED_STD)},
           "layers": {"ln1": {"g": gain("ln1")}, "attn": attn,
                      "ln2": {"g": gain("ln2")}, "mlp": mlp},
           "ln_f": {"g": gain("ln_f")}}
    if not cfg["tie_word_embeddings"]:
        # a key of its own, folded in after the split, so that every other
        # leaf is what a tied configuration draws
        head = jax.random.normal(jax.random.fold_in(key, len(sh)),
                                 (n["d"], n["V"]), jnp.float32)
        out["head"] = {"w": (head / np.sqrt(n["d"])).astype(dt)}
    return out


_DRAWN = ("num_hidden_layers", "hidden_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "intermediate_size",
          "vocab_size", "qkv_bias", "tie_word_embeddings")


def make_weights(cfg: dict, seed: int):
    """Weights from the seed, in the program's dense-decoder layout."""
    items = tuple((k, cfg.get(k)) for k in _DRAWN)
    return _make(M.seed_key(seed), items, cfg["torch_dtype"])
