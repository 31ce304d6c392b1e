"""Plain statistics on host floats: no numpy quirks, no JAX."""

import math
import statistics


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile of ``values`` at ``q`` in [0, 1]
    (the 'inclusive' definition: q=0 is the minimum, q=1 the maximum)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no values")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives
    them: the spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
