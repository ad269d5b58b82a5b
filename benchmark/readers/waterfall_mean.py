"""Mean per activation of the waterfall's stage deltas over `stages`
(host clock; the activations that finished inside the window), optionally
less the mean simulated service time of the same activations."""


def read(art, stages, minus_service=False):
    wf = art.get("waterfall")
    if not wf:
        return None
    total_us, n = 0, 0
    for name in stages:
        i = wf["stages"].index(name)
        total_us += wf["sum_us"][i]
        n = max(n, wf["count"][i])
    if n == 0:
        return None
    ms = total_us / n / 1e3
    if minus_service:
        ms -= art["service_ms_mean"]
    return ms
