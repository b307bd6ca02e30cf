"""Plain reference of OLMoE (allenai/OLMoE-1B-7B-0924, ``OlmoeForCausalLM``)
in float32.

Straight ``jax.numpy`` at the highest matmul precision, with no kernel, no
cache, no quantization and no batching, as the published modeling code
describes the block: RMSNorm with the configuration's eps; q and k
projections each RMS-normalized over their whole width before the head
split and the rotary embedding (half rotation); causal attention; a router
softmax over every expert, the top k experts of each token weighted by
their probabilities (renormalized only where ``norm_topk_prob`` says so),
each expert a SwiGLU ``w_down(silu(w_gate x) * w_up x)``; an untied LM
head. Every token reaches its own top k experts: nothing is dropped. The
experts run one at a time over every position, weighted by the position's
router weight for that expert, which is 0 where the expert is not among its
top k. It reads the weights in the layout of ``bench/layouts/olmoe.py``,
casting a layer at a time, and imports nothing of the program.

``logits_at`` has the signature of ``references/dense_decoder.py``.
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


def _experts(h, lay, n):
    """Sum over every token's top k experts, weighted by the router."""
    S = h.shape[0]
    probs = jax.nn.softmax(h @ lay["moe"]["router"].astype(jnp.float32), -1)
    top_w, top_idx = jax.lax.top_k(probs, n["k"])
    if n["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[jnp.arange(S)[:, None],
                                      top_idx].set(top_w)       # (S, E)
    f = lambda a: a.astype(jnp.float32)

    def one(y, e):
        m = lay["moe"]
        up = h @ f(m["w_up"][e])
        gate = h @ f(m["w_gate"][e])
        out = (jax.nn.silu(gate) * up) @ f(m["w_down"][e])
        return y + weight[:, e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(n["E"]))
    return y


def _layer(x, lay, pos, n):
    S = x.shape[0]
    H, K, hd, eps = n["H"], n["K"], n["hd"], n["eps"]
    f = lambda a: a.astype(jnp.float32)
    att = lay["attn"]
    h = _rms(x, f(lay["ln1"]["g"]), eps)
    qkv = h @ f(att["wqkv"])
    q = _rms(qkv[:, :H * hd], f(att["q_norm"]["g"]), eps).reshape(S, H, hd)
    k = _rms(qkv[:, H * hd:(H + K) * hd], f(att["k_norm"]["g"]),
             eps).reshape(S, K, hd)
    v = qkv[:, (H + K) * hd:].reshape(S, K, hd)
    q, k = _rope(q, pos, n["theta"]), _rope(k, pos, n["theta"])
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hst,thd->shd", p, v).reshape(S, H * hd)
    x = x + a @ f(att["wo"])
    return x + _experts(_rms(x, f(lay["ln2"]["g"]), eps), lay, n)


@functools.partial(jax.jit, static_argnums=(3,))
def _logits_at(weights, tokens, rows, n_items):
    n = dict(n_items)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"]["w"][tokens].astype(jnp.float32)
        pos = jnp.arange(tokens.shape[0])

        def body(x, lay):
            return _layer(x, lay, pos, n), None

        x, _ = jax.lax.scan(body, x, weights["layers"])
        x = _rms(x[rows], weights["ln_f"]["g"].astype(jnp.float32),
                 n["eps"])
        if "head" in weights:
            return x @ weights["head"]["w"].astype(jnp.float32)
        return x @ weights["embed"]["w"].astype(jnp.float32).T


def sizes(cfg: dict) -> tuple:
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return (("H", H), ("K", int(cfg["num_key_value_heads"])),
            ("hd", int(cfg.get("head_dim") or d // H)),
            ("E", int(cfg["num_experts"])),
            ("k", int(cfg["num_experts_per_tok"])),
            ("norm_topk", bool(cfg["norm_topk_prob"])),
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
