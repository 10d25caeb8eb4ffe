"""Device milliseconds of the segment-scan program per inner step."""


def read(run):
    prog = run["trace"]["programs"].get("run_segment")
    steps = run["record"].get("steps")
    if not prog or not steps:
        return None
    return 1e3 * prog["s"] / steps
