"""Serving arena (`serving.py` `BatchedDecoder`): the window over the
growth of `tick_count` — device step plus the host's part of a tick,
prefills included, since the tick is synchronous."""


def read(run):
    return run["window_s"] / run["ticks"] * 1e3 if run.get("ticks") else None
