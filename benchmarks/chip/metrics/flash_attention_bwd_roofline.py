"""Share of the chip's bf16 peak that the causal flash attention backward
kernel reaches in the traced window: `flops.attention_bwd_flops` of one
call (2.5 times the forward's), at the batch one call takes (every region's
local batch), the configuration's query heads and head size and the mix's
sequence length, times the calls the trace counts, over the kernel's device
seconds and the bf16 peak of `peaks.json`. None where the trace holds no
call of the kernel (the reference attention, or no chip) or there is no
peak."""
from benchmarks.chip import flops

KERNELS = ("flash_attention_bwd",)


def read(run):
    k = run["trace"].get("kernels", {}).get(KERNELS[0])
    peaks = run["peaks"]
    if not k or not k["n"] or not k["s"] or not peaks:
        return None
    mix, d = run["mix"], run["dims"]
    per_call = flops.attention_bwd_flops(
        mix["num_workers"] * mix["local_batch"], d["H"], mix["seq_len"],
        d["hd"])
    return 100.0 * per_call * k["n"] / (
        k["s"] * float(peaks["bf16_flops_per_s"]))
