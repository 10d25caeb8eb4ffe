"""Device milliseconds per inner step of the segment program's ops under the
named scope `adamw`: the inner AdamW's gradient clip and update, which are
not differentiated (`program_trace.reduce_program`'s `scopes`)."""


def read(run):
    scope = run["trace"].get("scopes", {}).get("adamw")
    steps = run["record"].get("steps")
    if not scope or not steps:
        return None
    return 1e3 * scope["s"] / steps
