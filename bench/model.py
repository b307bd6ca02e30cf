"""Configuration files, their layouts and references, and seeded inputs.

A configuration file (``bench/configs/<name>.json``) holds the model's
published sizes under the keys of its public ``config.json``, how it is
served, and two names: its weight layout (``bench/layouts/<layout>.py``)
and its plain reference (``bench/references/<reference>.py``). A new
architecture brings a configuration, a layout and a reference as new files.

The layout makes the weights from the run's seed, on the device in one
jitted call and in the type they are served in, laid out as the program
takes them. The reference draws the same weights again after the window,
so it takes nothing the program made.
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
# where layouts are looked for, in order
LAYOUT_DIRS = (os.path.join(HERE, "layouts"),)
# calibration: batches of rows x length tokens (stated in each "assumed")
CALIB_BATCHES, CALIB_ROWS, CALIB_LEN = 2, 4, 256


def load_config(name: str, directory: str = os.path.join(HERE, "configs")
                ) -> dict:
    with open(os.path.join(directory, f"{name}.json")) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(cfg: dict):
    """The configuration's plain reference module, found by name."""
    path = os.path.join(HERE, "references", f"{cfg['reference']}.py")
    return _module(path, f"bench_reference_{cfg['reference']}")


def load_layout(cfg: dict):
    """The configuration's weight layout module, found by name."""
    for d in LAYOUT_DIRS:
        path = os.path.join(d, f"{cfg['layout']}.py")
        if os.path.exists(path):
            return _module(path, f"bench_layout_{cfg['layout']}")
    raise FileNotFoundError(f"{cfg['name']}: no layout {cfg['layout']!r} "
                            f"in {LAYOUT_DIRS}")


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "d": d, "H": H,
            "K": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // H),
            "ff": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"])}


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return load_layout(cfg).program_config(cfg)


def make_weights(cfg: dict, seed: int):
    """Weights from the seed, in the layout the configuration names."""
    return load_layout(cfg).make_weights(cfg, seed)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


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
