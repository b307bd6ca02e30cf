"""Operations and bytes of the kernels and of the model, from shapes.

Everything here is computed from the configuration's sizes, the matrix
products its layout names (``linear_shapes``, ``matmul_shapes``) and the
counts the harness records (slots, context lengths, prompt lengths). Bytes
are the ones the algorithm has to move at the stored precision; padding the
hardware adds is not counted.
"""
from __future__ import annotations

from typing import List, Tuple

import model

KV_BYTES = 2        # the served KV pool is bfloat16


def linear_shapes(cfg: dict) -> List[Tuple[int, int]]:
    """(K, N) of the W8A8 projections one token multiplies in one layer,
    as the configuration's layout gives them."""
    return model.load_layout(cfg).linear_shapes(cfg)


def linear_params(cfg: dict) -> int:
    """Weights every token multiplies: every matrix of a layer that the
    layout names, W8A8 or not, in every layer, and the LM head."""
    n = model.dims(cfg)
    shapes = model.load_layout(cfg).matmul_shapes(cfg)
    per_layer = sum(k * nn for k, nn in shapes)
    return n["L"] * per_layer + n["d"] * n["V"]


def w8a8_call(M: int, K: int, N: int) -> Tuple[float, float]:
    """int8 x (M,K) times int8 w (K,N) into f32 (M,N), with the (N,) int32
    column sums of the epilogue."""
    return 2.0 * M * K * N, float(M * K + K * N + 4 * M * N + 4 * N)


def w8a8_step_calls(cfg: dict, B: int) -> List[Tuple[float, float]]:
    """The W8A8 calls of one decode step over B slots: every projection of
    every layer, and the LM head."""
    n = model.dims(cfg)
    calls = [w8a8_call(B, k, nn) for k, nn in linear_shapes(cfg)] * n["L"]
    return calls + [w8a8_call(B, n["d"], n["V"])]


def bound_s(ops: float, nbytes: float, ops_peak: float,
            bytes_peak: float) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / ops_peak, nbytes / bytes_peak)


def flash_decode_step(cfg: dict, B: int, sum_ctx: int) -> Tuple[float, float]:
    """Paged decode attention of one step over every layer: B queries, each
    reading the bfloat16 keys and values of its own context (cushion
    included)."""
    n = model.dims(cfg)
    L, H, K, hd = n["L"], n["H"], n["K"], n["hd"]
    ops = 4.0 * H * hd * sum_ctx
    nbytes = 2.0 * K * hd * KV_BYTES * sum_ctx + 2.0 * B * H * hd * 2
    return L * ops, L * nbytes


def decode_flops(cfg: dict, n_tokens: int, sum_ctx: int) -> float:
    """Model operations of n decode tokens whose contexts sum to sum_ctx."""
    n = model.dims(cfg)
    return (2.0 * linear_params(cfg) * n_tokens
            + 4.0 * n["L"] * n["H"] * n["hd"] * sum_ctx)


def prefill_flops(cfg: dict, T: int, m: int) -> float:
    """Model operations of a T-token prompt after an m-position cushion:
    every projection for every prompt token, causal attention over the
    cushion and the prompt, and the LM head on the last position."""
    n = model.dims(cfg)
    per_tok = 2.0 * (linear_params(cfg) - n["d"] * n["V"])
    keys = T * m + T * (T + 1) / 2.0
    return (per_tok * T + 4.0 * n["L"] * n["H"] * n["hd"] * keys
            + 2.0 * n["d"] * n["V"])
