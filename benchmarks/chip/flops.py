"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change how its
own work is counted. Sizes come from a configuration file's keys (the same
ones `reference.dims` reads).
"""
from __future__ import annotations

from typing import Dict

from benchmarks.chip.reference import dims


def matmul_params_per_token(cfg: Dict) -> float:
    """Weights each token multiplies with: attention projections, the
    SwiGLU MLP and the output head. The embedding lookup is a gather and
    counts nothing."""
    m = dims(cfg)
    D, F, H, KV, hd = m["D"], m["F"], m["H"], m["KV"], m["hd"]
    attn = D * H * hd * 2 + D * KV * hd * 2
    return m["L"] * (attn + 3 * D * F) + D * m["V"]


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward plus backward model FLOPs per trained token: 6 per weight it
    meets, and causal attention's QK^T and PV at the mean context of S/2
    (2 matmuls x 2 FLOPs x S/2 x H*hd per layer, forward; x3 with backward).
    Recomputation is not counted."""
    m = dims(cfg)
    attn = m["L"] * 6 * seq_len * m["H"] * m["hd"]
    return 6.0 * matmul_params_per_token(cfg) + attn
