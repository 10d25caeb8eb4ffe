"""Device milliseconds per inner step of the MoE layer's work around its
expert matmuls: the segment program's ops under the named scope `mlp`
(norm, router, top-k, sort, gathers, gates and combine, forward, remat and
backward) less the device seconds of the grouped-matmul kernels' events,
which the scope holds too. None where the trace holds no call of those
kernels (a dense model, the oracle, or no chip).

`KERNELS` is one pattern that both kernels' instruction names hold
(`expert_gmm.<n>`, `expert_tgmm.<n>`) and no other instruction of the
program does. It differs from the names `expert_matmul_roofline` lists:
the run joins every reader's `KERNELS` as they come, so a name two readers
listed would count each of its events twice."""

KERNELS = ("expert_",)


def read(run):
    scope = run["trace"].get("scopes", {}).get("mlp")
    steps = run["record"].get("steps")
    kernels = run["trace"].get("kernels", {}).get(KERNELS[0])
    if not scope or not steps or not kernels or not kernels["n"]:
        return None
    return 1e3 * (scope["s"] - kernels["s"]) / steps
