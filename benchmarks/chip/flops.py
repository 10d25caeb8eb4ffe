"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change how its
own work is counted. A model's weights per token come from the
configuration's reference model module (`Registry.model`: the dense model of
`reference.py` unless the configuration names its own); the attention counts
from its `dims` and the run's shapes.
"""
from __future__ import annotations

from types import ModuleType
from typing import Dict, Optional


def _model(cfg: Dict, model: Optional[ModuleType]) -> ModuleType:
    if model is not None:
        return model
    from benchmarks.chip.registry import Registry
    return Registry().model(cfg)


def matmul_params_per_token(cfg: Dict,
                            model: Optional[ModuleType] = None) -> float:
    """Weights each token multiplies with, as the configuration's model
    module counts them (`model`, else found by `cfg`'s `"reference"`)."""
    return _model(cfg, model).matmul_params_per_token(cfg)


def train_flops_per_token(cfg: Dict, seq_len: int,
                          model: Optional[ModuleType] = None) -> float:
    """Forward plus backward model FLOPs per trained token: 6 per weight it
    meets, and causal attention's QK^T and PV at the mean context of S/2
    (2 matmuls x 2 FLOPs x S/2 x H*hd per layer, forward; x3 with backward).
    Recomputation is not counted."""
    model = _model(cfg, model)
    m = model.dims(cfg)
    attn = m["L"] * 6 * seq_len * m["H"] * m["hd"]
    return 6.0 * model.matmul_params_per_token(cfg) + attn


def attention_fwd_flops(B: int, H: int, S: int, hd: int) -> float:
    """One call of a causal attention forward kernel over B sequences of S
    tokens, H query heads of size hd: QK^T and PV, 2 FLOPs a multiply-add,
    on the lower half of the S x S scores. Any number of KV heads."""
    return 4.0 * B * H * S * S * hd / 2


def attention_bwd_flops(B: int, H: int, S: int, hd: int) -> float:
    """One call of its backward kernel: dV, dP, dQ and dK, with the scores
    QK^T recomputed, 2.5 times the forward."""
    return 2.5 * attention_fwd_flops(B, H, S, hd)
