"""Device: 1 minus the union of the `XLA Ops` intervals over the traced
window (first operation's start to last operation's end)."""


def read(run):
    t = run.get("trace")
    if not t or not t.get("span_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["span_s"])
