"""Configuration files and seeded weights.

A configuration file (``bench/configs/<name>.json``) holds the model's
published sizes under the keys of its public ``config.json``, how it is
served, and the name of its plain reference (``bench/references/<name>.py``).

The benchmark makes the weights itself, from the run's seed, on the device
in one jitted call and in the type they are served in (bfloat16), laid out
as the program's dense decoder takes them. The reference draws the same
weights again after the window, so it takes nothing the program made.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# init scales of the seeded weights (also stated in each config's "assumed")
EMBED_STD = 0.02
BIAS_STD = 0.02
GAIN_STD = 0.1
# calibration: batches of rows x length tokens (stated in each "assumed")
CALIB_BATCHES, CALIB_ROWS, CALIB_LEN = 2, 4, 256


def load_config(name: str, directory: str = os.path.join(HERE, "configs")
                ) -> dict:
    with open(os.path.join(directory, f"{name}.json")) as f:
        return json.load(f)


def load_reference(cfg: dict):
    """The configuration's plain reference module, found by name."""
    path = os.path.join(HERE, "references", f"{cfg['reference']}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{cfg['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "d": d, "H": H,
            "K": int(cfg["num_key_value_heads"]), "hd": d // H,
            "ff": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"])}


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import Family, ModelConfig
    n = dims(cfg)
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only gated SiLU MLPs are served")
    return ModelConfig(
        name=cfg["name"], family=Family.DENSE, n_layers=n["L"],
        d_model=n["d"], n_heads=n["H"], n_kv_heads=n["K"], d_ff=n["ff"],
        vocab_size=n["V"], qkv_bias=bool(cfg["qkv_bias"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        max_seq_len=int(cfg["max_position_embeddings"]),
        dtype=cfg["torch_dtype"])


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _shapes(cfg: dict) -> dict:
    n = dims(cfg)
    L, d, H, K, hd, ff, V = (n[k] for k in ("L", "d", "H", "K", "hd", "ff",
                                             "V"))
    return {"embed": (V, d), "wqkv": (L, d, (H + 2 * K) * hd),
            "bqkv": (L, (H + 2 * K) * hd), "wo": (L, H * hd, d),
            "w_up": (L, d, ff), "w_gate": (L, d, ff), "w_down": (L, ff, d),
            "ln1": (L, d), "ln2": (L, d), "ln_f": (d,)}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, cfg_items: tuple, dtype: str):
    cfg = dict(cfg_items)
    n = dims(cfg)
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
    return {"embed": {"w": normal("embed", EMBED_STD)},
            "layers": {"ln1": {"g": gain("ln1")}, "attn": attn,
                       "ln2": {"g": gain("ln2")}, "mlp": mlp},
            "ln_f": {"g": gain("ln_f")}}


def _hashable(cfg: dict) -> tuple:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "vocab_size",
            "qkv_bias")
    return tuple((k, cfg[k]) for k in keys)


def make_weights(cfg: dict, seed: int):
    """Weights from the seed, in the program's dense-decoder layout."""
    if not cfg["tie_word_embeddings"]:
        raise ValueError(f"{cfg['name']}: untied heads are not drawn yet")
    return _make(seed_key(seed), _hashable(cfg), cfg["torch_dtype"])


def cushion_tokens(cfg: dict, seed: int) -> np.ndarray:
    """The seeded cushion prefix: its KV is the served cushion."""
    m = int(cfg["serving"]["cushion_len"])
    rng = np.random.default_rng([int(seed), 1])
    return rng.integers(0, dims(cfg)["V"], size=m, dtype=np.int32)


def calibration_tokens(cfg: dict, seed: int):
    """Calibration batches for the static activation scales, on device."""
    key = jax.random.fold_in(seed_key(seed), 2)
    toks = jax.random.randint(key, (CALIB_BATCHES, CALIB_ROWS, CALIB_LEN), 0,
                              dims(cfg)["V"], dtype=jnp.int32)
    return [{"tokens": toks[i]} for i in range(CALIB_BATCHES)]
