"""Router (and everything under it): 95th percentile, over the requests
submitted in the window, of harness submit to first streamed token, in a
closed loop that keeps every slot taken. At that load the tail is the
wait for the replica's lock and for a slot's harvest, and swings by a
fifth from run to run (PR 24), so it is recorded here and judged
nowhere; an open-loop cell below capacity is where a TTFT bound
belongs."""

from benchmark.harness import stats


def read(run):
    t = run.get("ttft_s")
    return stats.quantile(t, 0.95) * 1e3 if t else None
