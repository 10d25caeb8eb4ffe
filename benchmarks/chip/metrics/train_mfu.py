"""Model FLOP/s utilization of the whole training step: model FLOPs of the
tokens trained in the traced window over the window's seconds and the chip's
bf16 peak (recomputation not counted; `flops.train_flops_per_token`)."""


def read(run):
    rec, peaks = run["record"], run["peaks"]
    if not peaks or not rec.get("steps"):
        return None
    return 100.0 * rec["tokens"] * rec["flops_per_token"] / (
        rec["window_s"] * float(peaks["bf16_flops_per_s"]))
