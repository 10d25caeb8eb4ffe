"""Host milliseconds per segment from the return of a segment's last
blocking read to the next segment's first scan dispatch, while the device
has nothing queued (the trainer's `telemetry.boundary_s` over its
`segments`, differenced across the window)."""


def read(run):
    t = run["record"].get("telemetry")
    if not t or not t.get("segments"):
        return None
    return 1e3 * t["boundary_s"] / t["segments"]
