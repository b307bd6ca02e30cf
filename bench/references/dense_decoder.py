"""Plain reference of a dense decoder (Llama / Qwen2 layout), in float32.

Straight ``jax.numpy`` at the highest matmul precision, with no kernel, no
cache, no quantization and no batching: RMSNorm, rotary position
embedding with the half rotation, causal grouped-query attention, a gated
SiLU MLP and an LM head tied to the embedding or of its own, as the
published Llama and Qwen2 code describes them. It reads the weights in the
layout of ``bench/layouts/dense_decoder.py`` and imports nothing of the
program.

``logits_at`` runs one sequence (the cushion prefix, the prompt and the
served tokens) and returns the logits at the rows asked for. Sequences are
padded at the end to a fixed length, which leaves every earlier row
unchanged under the causal mask, so one compiled program serves a cell.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, w, pos, n):
    S = x.shape[0]
    H, K, hd = n["H"], n["K"], n["hd"]
    h = _rms(x, w["ln1"], n["eps"])
    qkv = h @ w["wqkv"]
    if "bqkv" in w:
        qkv = qkv + w["bqkv"]
    q = qkv[:, :H * hd].reshape(S, H, hd)
    k = qkv[:, H * hd:(H + K) * hd].reshape(S, K, hd)
    v = qkv[:, (H + K) * hd:].reshape(S, K, hd)
    q, k = _rope(q, pos, n["theta"]), _rope(k, pos, n["theta"])
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hst,thd->shd", p, v).reshape(S, H * hd)
    x = x + a @ w["wo"]
    h = _rms(x, w["ln2"], n["eps"])
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _flat(weights):
    """The dense layout's stacked layer leaves, as float32."""
    f = lambda a: a.astype(jnp.float32)
    lay = weights["layers"]
    out = {"ln1": f(lay["ln1"]["g"]), "ln2": f(lay["ln2"]["g"]),
           "wqkv": f(lay["attn"]["wqkv"]), "wo": f(lay["attn"]["wo"]),
           "w_up": f(lay["mlp"]["w_up"]), "w_gate": f(lay["mlp"]["w_gate"]),
           "w_down": f(lay["mlp"]["w_down"])}
    if "bqkv" in lay["attn"]:
        out["bqkv"] = f(lay["attn"]["bqkv"])
    return out


@functools.partial(jax.jit, static_argnums=(3,))
def _logits_at(weights, tokens, rows, n_items):
    n = dict(n_items)
    with jax.default_matmul_precision("highest"):
        emb = weights["embed"]["w"].astype(jnp.float32)
        x = emb[tokens]
        pos = jnp.arange(tokens.shape[0])

        def body(x, w):
            return _layer(x, w, pos, n), None

        x, _ = jax.lax.scan(body, x, _flat(weights))
        x = _rms(x[rows], weights["ln_f"]["g"].astype(jnp.float32),
                 n["eps"])
        if "head" in weights:
            return x @ weights["head"]["w"].astype(jnp.float32)
        return x @ emb.T


def sizes(cfg: dict) -> tuple:
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return (("H", H), ("K", int(cfg["num_key_value_heads"])),
            ("hd", int(cfg.get("head_dim") or d // H)),
            ("eps", float(cfg["rms_norm_eps"])),
            ("theta", float(cfg["rope_theta"])))


def logits_at(weights, cfg: dict, tokens: np.ndarray, rows: np.ndarray,
              length: int, n_rows: int):
    """(n_rows, V) float32 logits of ``tokens`` (1-D) at ``rows``. The
    sequence is padded to ``length`` and the rows to ``n_rows`` (repeats of
    the last row), so every call of a cell has one shape."""
    toks = np.zeros((length,), np.int32)
    toks[:len(tokens)] = tokens
    r = np.full((n_rows,), rows[-1], np.int32)
    r[:len(rows)] = rows
    return _logits_at(weights, jnp.asarray(toks), jnp.asarray(r),
                      sizes(cfg))[:len(rows)]
