"""The comparison that decides ``correct``.

Once the window has closed, a sample of the served requests, drawn from the
seed, always holding the request with the most positions, is run through
the configuration's plain float32 reference: the cushion prefix, the prompt
and every token served to the request. At each served position the reference
gives its best logit; the number compared is the widest gap, over every
served token of the sample, by which the served token's reference logit
lies below that best. Greedy decoding at the configuration's precision
serves the reference's best token or one within the rounding of the int8
path, so the gap stays small; a wrong token, or a path at lower precision,
opens it.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import model as M


@dataclasses.dataclass
class Served:
    """One request as served: its prompt and the tokens it was given."""
    uid: int
    prompt: np.ndarray
    tokens: np.ndarray

    @property
    def positions(self) -> int:
        return len(self.prompt) + len(self.tokens)


def sample(served: List[Served], seed: int, min_tokens: int,
           min_requests: int, max_requests: int) -> List[Served]:
    """The request with the most positions, then others drawn from the seed
    until the sample holds both ``min_tokens`` served tokens and
    ``min_requests`` requests, or ``max_requests`` requests."""
    pool = [s for s in served if len(s.tokens)]
    if not pool:
        return []
    pool.sort(key=lambda s: s.uid)
    first = max(pool, key=lambda s: (s.positions, -s.uid))
    rest = [s for s in pool if s is not first]
    order = np.random.default_rng([int(seed), 3]).permutation(len(rest))
    out = [first]
    for i in order:
        if len(out) >= max_requests or (
                len(out) >= min_requests
                and sum(len(s.tokens) for s in out) >= min_tokens):
            break
        out.append(rest[int(i)])
    return out


def sequence(cushion: np.ndarray, s: Served):
    """Tokens fed to the reference and the rows that predict each served
    token: served token j follows position m + T - 1 + j."""
    seq = np.concatenate([cushion, s.prompt, s.tokens[:-1]]).astype(np.int32)
    first = len(cushion) + len(s.prompt) - 1
    return seq, np.arange(first, first + len(s.tokens), dtype=np.int32)


def widest_gap(ref_logits, tokens: np.ndarray) -> float:
    ref = jnp.asarray(ref_logits, jnp.float32)
    gap = ref.max(axis=-1) - ref[jnp.arange(ref.shape[0]),
                                 jnp.asarray(tokens)]
    return float(jnp.max(gap))


def reference_gaps(cfg: dict, seed: int, picked: List[Served],
                   cushion: np.ndarray, length: int, n_rows: int,
                   control: Optional[Callable] = None) -> dict:
    """Widest gap of the served tokens (and, where ``control`` is given,
    of the tokens the control puts first at the same positions) against
    the reference, over the sampled requests."""
    ref_mod = M.load_reference(cfg)
    weights = M.make_weights(cfg, seed)
    served_gap, control_gap, n_tok = 0.0, 0.0, 0
    for s in picked:
        seq, rows = sequence(cushion, s)
        ref = ref_mod.logits_at(weights, cfg, seq, rows, length, n_rows)
        served_gap = max(served_gap, widest_gap(ref, s.tokens))
        n_tok += len(s.tokens)
        if control is not None:
            ctl_first = control(seq, rows)
            control_gap = max(control_gap, widest_gap(ref, ctl_first))
        del ref
    out = {"widest_gap": served_gap, "tokens_compared": n_tok,
           "requests_compared": len(picked)}
    if control is not None:
        out["control_gap"] = control_gap
    jax.block_until_ready(weights)
    del weights
    return out
