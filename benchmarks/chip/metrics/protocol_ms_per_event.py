"""Device milliseconds of the protocol transition programs (initiate and
deliver) per protocol event in the traced window."""


def read(run):
    progs, rec = run["trace"]["programs"], run["record"]
    events = rec.get("initiations", 0) + rec.get("deliveries", 0)
    found = [progs[p]["s"] for p in ("initiate", "deliver") if p in progs]
    if not events or not found:
        return None
    return 1e3 * sum(found) / events
