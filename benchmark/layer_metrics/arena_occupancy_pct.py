"""Serving arena: growth of `tick_tokens` over growth of
`tick_capacity` — the share of slot-steps that emitted a token."""


def read(run):
    cap = run.get("tick_capacity")
    return 100.0 * run["tick_tokens"] / cap if cap else None
