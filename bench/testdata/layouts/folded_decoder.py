"""A weight layout that lives only beside the tests, to show that a
configuration brings its layout as a new file. The program's dense decoder
with an LM head of its own; each leaf is drawn from the seed's key folded
with the leaf's index. The program's configuration and the projections are
the dense decoder layout's."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

import model as M

_DENSE = M.load_layout({"name": "folded_decoder", "layout": "dense_decoder"})
program_config = _DENSE.program_config
linear_shapes = _DENSE.linear_shapes
matmul_shapes = _DENSE.matmul_shapes


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, n_items: tuple, bias: bool, dtype: str):
    n = dict(n_items)
    L, d, H, K, hd, ff, V = (n[k] for k in ("L", "d", "H", "K", "hd", "ff",
                                             "V"))
    dt = jnp.dtype(dtype)
    index = iter(range(16))

    def draw(shape, std, mean=0.0):
        z = jax.random.normal(jax.random.fold_in(key, next(index)), shape)
        return (mean + std * z).astype(dt)

    attn = {"wqkv": draw((L, d, (H + 2 * K) * hd), 1 / np.sqrt(d)),
            "wo": draw((L, H * hd, d), 1 / np.sqrt(2 * L * H * hd))}
    if bias:
        attn["bqkv"] = draw((L, (H + 2 * K) * hd), 0.02)
    mlp = {"w_up": draw((L, d, ff), 1 / np.sqrt(d)),
           "w_gate": draw((L, d, ff), 1 / np.sqrt(d)),
           "w_down": draw((L, ff, d), 1 / np.sqrt(2 * L * ff))}
    return {"embed": {"w": draw((V, d), 0.02)},
            "head": {"w": draw((d, V), 1 / np.sqrt(d))},
            "layers": {"ln1": {"g": draw((L, d), 0.1, 1.0)}, "attn": attn,
                       "ln2": {"g": draw((L, d), 0.1, 1.0)}, "mlp": mlp},
            "ln_f": {"g": draw((d,), 0.1, 1.0)}}


def make_weights(cfg: dict, seed: int):
    if cfg["tie_word_embeddings"]:
        raise ValueError(f"{cfg['name']}: this layout draws its own head")
    return _make(M.seed_key(seed), tuple(sorted(M.dims(cfg).items())),
                 bool(cfg["qkv_bias"]), cfg["torch_dtype"])
