"""OLMoE's weights (allenai/OLMoE-1B-7B-0924, ``OlmoeForCausalLM``), as the
program's ``Family.MOE`` takes them and ``references/olmoe.py`` reads them.

Per layer: RMSNorm gains ``ln1`` and ``ln2``; the fused QKV projection
``wqkv`` (no bias), the gains of the RMSNorms over the whole q and k
projections (``q_norm``, ``k_norm``), the output projection ``wo``; a
float32 router ``(d, E)`` and the stacked experts ``w_up``, ``w_gate``
``(E, d, ff)`` and ``w_down`` ``(E, ff, d)``. Then ``ln_f`` and an untied
head. The layout functions are those ``layouts/dense_decoder.py`` lists.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import model as M

EMBED_STD = 0.02
GAIN_STD = 0.1
# what the program's configuration has to take to serve this block
PROGRAM_FIELDS = {"ModelConfig": ("norm_eps", "qk_norm"),
                  "MoEConfig": ("norm_topk_prob",)}


def sizes(cfg: dict) -> dict:
    """dims() and the experts: E experts, top k."""
    return {**M.dims(cfg), "E": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"])}


def program_config(cfg: dict):
    """The program's ``ModelConfig``. A program whose configuration cannot
    state QK-norm, the RMSNorm eps or the raw top-k weights is refused: it
    would serve another model."""
    from repro.configs import base
    for cls, names in PROGRAM_FIELDS.items():
        have = {f.name for f in dataclasses.fields(getattr(base, cls))}
        missing = [n for n in names if n not in have]
        if missing:
            raise ValueError(f"{cfg['name']}: the program's {cls} has no "
                             f"{', '.join(missing)}")
    if (cfg["hidden_act"] != "silu" or cfg["attention_bias"]
            or cfg["clip_qkv"] is not None):
        raise ValueError(f"{cfg['name']}: only SwiGLU experts without QKV "
                         f"bias or clipping are served")
    n = sizes(cfg)
    return base.ModelConfig(
        name=cfg["name"], family=base.Family.MOE, n_layers=n["L"],
        d_model=n["d"], n_heads=n["H"], n_kv_heads=n["K"], d_ff=n["ff"],
        vocab_size=n["V"], d_head=int(cfg.get("head_dim") or 0),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        max_seq_len=int(cfg["max_position_embeddings"]),
        dtype=cfg["serving"]["dtype"],
        norm_eps=float(cfg["rms_norm_eps"]), qk_norm=True,
        moe=base.MoEConfig(num_experts=n["E"], top_k=n["k"],
                           norm_topk_prob=bool(cfg["norm_topk_prob"])))


def linear_shapes(cfg: dict) -> List[Tuple[int, int]]:
    """(K, N) of the W8A8 (``w8a8_matmul``) projections of one layer: QKV
    and O. The experts run in ``w8a8_gmm`` (``moe_costs.py``)."""
    n = M.dims(cfg)
    d, H, K, hd = n["d"], n["H"], n["K"], n["hd"]
    return [(d, (H + 2 * K) * hd), (H * hd, d)]


def matmul_shapes(cfg: dict) -> List[Tuple[int, int]]:
    """Every (K, N) one token multiplies in one layer: the projections, the
    router and its top-k experts' up, gate and down."""
    n = sizes(cfg)
    d, ff = n["d"], n["ff"]
    return (linear_shapes(cfg) + [(d, n["E"])]
            + n["k"] * [(d, ff), (d, ff), (ff, d)])


def _normal(key, shape, std, dt):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, n_items: tuple, dtype: str):
    n = dict(n_items)
    L, d, H, K, hd, ff, V, E = (n[k] for k in ("L", "d", "H", "K", "hd",
                                               "ff", "V", "E"))
    dt = jnp.dtype(dtype)
    ks = iter(jax.random.split(key, 16))
    out_scale = 1.0 / np.sqrt(2 * L)

    def gain(shape):
        return (1.0 + _normal(next(ks), shape, GAIN_STD, jnp.float32)
                ).astype(dt)

    def experts(shape, std):
        # one layer at a time, so that no float32 stack is ever whole
        return jax.lax.map(lambda k: _normal(k, shape, std, dt),
                           jax.random.split(next(ks), L))

    attn = {"wqkv": _normal(next(ks), (L, d, (H + 2 * K) * hd),
                            1.0 / np.sqrt(d), dt),
            "wo": _normal(next(ks), (L, H * hd, d),
                          out_scale / np.sqrt(H * hd), dt),
            "q_norm": {"g": gain((L, H * hd))},
            "k_norm": {"g": gain((L, K * hd))}}
    moe = {"router": _normal(next(ks), (L, d, E), 1.0 / np.sqrt(d),
                             jnp.float32),
           "w_up": experts((E, d, ff), 1.0 / np.sqrt(d)),
           "w_gate": experts((E, d, ff), 1.0 / np.sqrt(d)),
           "w_down": experts((E, ff, d), out_scale / np.sqrt(ff))}
    out = {"embed": {"w": _normal(next(ks), (V, d), EMBED_STD, dt)},
           "layers": {"ln1": {"g": gain((L, d))}, "attn": attn,
                      "ln2": {"g": gain((L, d))}, "moe": moe},
           "ln_f": {"g": gain((d,))}}
    if not n["tied"]:
        out["head"] = {"w": _normal(next(ks), (d, V), 1.0 / np.sqrt(d), dt)}
    return out


def make_weights(cfg: dict, seed: int):
    """Weights from the seed, in the program's MoE layout."""
    n = {**sizes(cfg), "tied": bool(cfg["tie_word_embeddings"])}
    return _make(M.seed_key(seed), tuple(sorted(n.items())),
                 cfg["serving"]["dtype"])
