"""Declarative budget registry for the static-analysis pass.

Everything the auditor/linter enforces that is a *number or a list* lives
here, so adding a method or a kernel family means declaring its contract in
one place — not editing checker code:

  * ``ENGINE_DISPATCH_BUDGETS`` — exact ``pallas_call`` dispatch counts for
    the jitted engine transitions, per (method, fused_updates, impl mode).
    ROADMAP item-1 authors: a new ``@register_method`` strategy MUST add its
    rows (``register_dispatch_budget``) or ``python -m repro.analysis``
    fails with a coverage error.
  * ``SERVE_DISPATCH_BUDGETS`` / ``SEGMENT_SCAN_PALLAS_CALLS`` — the serve
    decode/prefill steps and the trainer's fused segment scan.
  * ``BANNED_PRIMITIVES`` — primitives that must never appear inside a
    jitted protocol-plane program (host callbacks stall the device pipeline;
    ``debug_print`` is what ``jax.debug.print`` lowers to).
  * ``KERNEL_CONTRACTS`` — per kernel family: upper bounds for tile dims the
    linter cannot resolve statically (the TPU-target shapes), and a VMEM
    footprint budget for the sum of all declared BlockSpec tiles
    (TPU VMEM is ~16 MiB/core; every family must fit with headroom).

Counts are audited on the *traced jaxpr*, so they are backend-independent:
``impl="kernel"``/``"pallas"`` entries pin the accelerator program (interpret
mode emits the same ``pallas_call`` primitives), ``"ref"`` entries pin that
the oracle paths stay kernel-free.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Tuple, Union

# Sentinel: expected count == number of (non-None) leaves in the audited
# fragment — the per-leaf kernel path pays one dispatch per fragment leaf.
LEAVES = "leaves"

CountSpec = Union[int, str]
BudgetKey = Tuple[str, bool, str]          # (method, fused_updates, impl)

# ---------------------------------------------------------------------------
# engine transition dispatch budgets
# ---------------------------------------------------------------------------

# Transitions audited per entry are exactly the dict keys — non-overlapped
# methods (diloco/local) park nothing in flight, so only their blocking
# round is traced. impl modes: per-leaf entries use the delay-comp policy
# ("ref" oracle | "kernel"), fused entries use the outer_update policy
# ("ref" | "pallas"). The fused kernel path is the PR-8 guarantee: exactly
# TWO dispatches per delivery/round (one Nesterov, one fused deliver),
# independent of model depth.
ENGINE_DISPATCH_BUDGETS: Dict[BudgetKey, Dict[str, CountSpec]] = {
    ("local", False, "ref"): {"diloco_round": 0},
    ("local", True, "ref"): {"diloco_round": 0},
    ("local", True, "pallas"): {"diloco_round": 2},

    ("diloco", False, "ref"): {"diloco_round": 0},
    ("diloco", True, "ref"): {"diloco_round": 0},
    ("diloco", True, "pallas"): {"diloco_round": 2},

    ("streaming", False, "ref"): {"initiate": 0, "deliver": 0,
                                  "diloco_round": 0},
    ("streaming", False, "kernel"): {"initiate": 0, "deliver": 0},
    ("streaming", True, "ref"): {"initiate": 0, "deliver": 0},
    ("streaming", True, "pallas"): {"initiate": 0, "deliver": 2,
                                    "diloco_round": 2},

    ("cocodc", False, "ref"): {"initiate": 0, "deliver": 0},
    # the per-leaf kernel path pays one delay-comp dispatch PER LEAF
    ("cocodc", False, "kernel"): {"initiate": 0, "deliver": LEAVES},
    ("cocodc", True, "ref"): {"initiate": 0, "deliver": 0},
    ("cocodc", True, "pallas"): {"initiate": 0, "deliver": 2,
                                 "diloco_round": 2},
}


def register_dispatch_budget(method: str, *, fused: bool, impl: str,
                             budget: Dict[str, CountSpec]) -> None:
    """Declare the dispatch budget for a new sync method (the method-author
    half of the audit contract). Keys of `budget` are the transitions to
    trace ("initiate" | "deliver" | "diloco_round"); values are exact
    ``pallas_call`` counts (or the LEAVES sentinel)."""
    for k in budget:
        if k not in ("initiate", "deliver", "diloco_round"):
            raise ValueError(f"unknown transition {k!r} in budget for "
                             f"{method!r}")
    ENGINE_DISPATCH_BUDGETS[(method, fused, impl)] = dict(budget)


def budgeted_methods() -> Tuple[str, ...]:
    """Methods with at least one declared dispatch budget."""
    return tuple(sorted({m for (m, _, _) in ENGINE_DISPATCH_BUDGETS}))


# ---------------------------------------------------------------------------
# serve plane + segment scan
# ---------------------------------------------------------------------------

# attn_impl -> exact pallas_call count per traced step. "flash" decode is ONE
# dispatch: the layer stack runs under lax.scan, so the kernel appears once
# in the traced program regardless of depth.
SERVE_DISPATCH_BUDGETS: Dict[str, Dict[str, int]] = {
    "ref": {"decode": 0, "prefill": 0},
    "flash": {"decode": 1, "prefill": 0},
}

# attn_impl -> exact pallas_call count in the traced segment scan. "ref" is
# pure XLA. "flash" is the attention kernel inside the layer scan, counted
# once per appearance in the program: the forward, its recompute under
# remat, and the fused backward kernel, whatever the depth.
SEGMENT_SCAN_PALLAS_CALLS: Dict[str, int] = {
    "ref": 0,
    "flash": 3,
}

# ---------------------------------------------------------------------------
# banned primitives (jitted protocol plane)
# ---------------------------------------------------------------------------

BANNED_PRIMITIVES = frozenset({
    "pure_callback",        # host round-trip inside the hot path
    "io_callback",
    "callback",
    "debug_callback",       # jax.debug.callback
    "debug_print",          # jax.debug.print
    "infeed", "outfeed",    # legacy host transfers
})

# ---------------------------------------------------------------------------
# kernel family contracts (AST linter)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """Static contract for one ``kernels/<family>/`` package.

    ``dim_bounds`` declares the TPU-target upper bound for every BlockSpec
    tile dimension the linter cannot resolve to a module constant (runtime
    names like ``hd``/``bc``/``block``). Bounds participate in two checks:
    a LAST tile dim must be lane-aligned (% 128 == 0) whether it is a
    resolved constant or a declared bound, and the VMEM footprint estimate
    (sum over every declared tile of prod(dims) * dtype_bytes) must stay
    under ``vmem_budget_bytes``."""
    dim_bounds: Mapping[str, int] = dataclasses.field(default_factory=dict)
    vmem_budget_bytes: int = 8 * 1024 * 1024      # half of ~16 MiB VMEM/core
    dtype_bytes: int = 4                          # f32 operands


KERNEL_CONTRACTS: Dict[str, KernelContract] = {
    # (block, LANES=1024) tiles, block = min(BLOCK_ROWS=256, rows)
    "delay_comp": KernelContract(dim_bounds={"block": 256}),
    # encode: (rows, block) in, (rows, pb)+(rows, LANES=128) out; block is
    # kernel-gated to a multiple of 256 and the engine dials run <= 1024
    "delta_codec": KernelContract(
        dim_bounds={"rows": 256, "block": 1024, "pb": 1024}),
    # one (batch, head) per grid step: whole-sequence (sq | sk, hd) tiles
    # and (1, sq) log-sum-exp rows; sq/sk bound = the longest sequence the
    # training configs trace (paper seq lens << 4096)
    "flash_attention": KernelContract(
        dim_bounds={"hd": 128, "sq": 4096, "sk": 4096}),
    # per-(b, kv-head) decode: (bc, hd) cache tiles over the ring buffer
    "flash_decode": KernelContract(
        dim_bounds={"bc": 512, "hd": 128, "G": 16}),
    # (block, D) rows x model width; D bound = widest registered d_model
    "rms_norm": KernelContract(dim_bounds={"block": 256, "D": 2048}),
    "rglru_scan": KernelContract(dim_bounds={"bt": 256, "bd": 128}),
    "rwkv6_scan": KernelContract(dim_bounds={"bt": 128, "hd": 128}),
    # flat fragment plane: (BLOCK_ROWS=256, LANES=1024) f32 tiles
    "outer_update": KernelContract(dim_bounds={"block": 256}),
}
