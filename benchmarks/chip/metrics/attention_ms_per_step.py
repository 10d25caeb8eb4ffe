"""Device milliseconds per inner step of the segment program's ops under the
named scope `attention`: the attention layers (norm, QKV projection,
attention core, output projection), forward, remat and backward
(`program_trace.reduce_program`'s `scopes`)."""


def read(run):
    scope = run["trace"].get("scopes", {}).get("attention")
    steps = run["record"].get("steps")
    if not scope or not steps:
        return None
    return 1e3 * scope["s"] / steps
