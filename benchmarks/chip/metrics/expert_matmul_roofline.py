"""Share of the chip's bf16 peak that the MoE layer's grouped-matmul kernels
(`expert_gmm`: the expert matmuls forward, in the remat recompute and the
gradient of their input; `expert_tgmm`: the gradient of the expert
weights) reach in the traced window. Each call counts 2 x P x D x F FLOPs,
where P is the pairs it covers at the expected share: one call takes every
region's local batch (the regions fold into the kernel's groups), so P =
regions x local batch x seq x top_k x held / E. The FLOPs of every call of
both kernels (one trace event each) over their device seconds and the bf16
peak of `peaks.json`. None where the trace holds no call (the oracle, no
chip, or a dense model) or there is no peak."""

KERNELS = ("expert_gmm", "expert_tgmm")


def pairs_per_call(mix, d) -> float:
    """(token, expert) pairs one call covers at the expected share."""
    return (mix["num_workers"] * mix["local_batch"] * mix["seq_len"]
            * d["top_k"] * d["E_held"] / d["E"])


def read(run):
    found = [k for k in (run["trace"].get("kernels", {}).get(n)
                         for n in KERNELS) if k and k["n"]]
    peaks, d = run["peaks"], run["dims"]
    if not found or not peaks or "E_held" not in d:
        return None
    seconds = sum(k["s"] for k in found)
    if not seconds:
        return None
    per_call = 2.0 * pairs_per_call(run["mix"], d) * d["D"] * d["F"]
    return 100.0 * per_call * sum(k["n"] for k in found) / (
        seconds * float(peaks["bf16_flops_per_s"]))
