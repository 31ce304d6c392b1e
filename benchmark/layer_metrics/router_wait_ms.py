"""Router (`serving_router.py` `Router` + `LocalReplica`): median of
`Ticket.t_dispatched - t_submit` over the requests that finished."""

import statistics


def read(run):
    waits = run.get("router_wait_s")
    return statistics.median(waits) * 1e3 if waits else None
