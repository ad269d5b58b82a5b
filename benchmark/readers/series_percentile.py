"""A percentile of one of the client's sorted series (`response_ms`,
`fire_lag_ms`, `overhead_ms`) over all the window's activations."""


def read(art, series, q):
    vals = art.get(series) or []
    if not vals:
        return None
    return vals[min(len(vals) - 1, int(q * len(vals)))]
