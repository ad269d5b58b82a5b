"""What a client waits for outside every stamp of the waterfall (host
clocks of both processes, one CLOCK_MONOTONIC): the clients' mean response
time over the window's answered requests, less the waterfall's mean total
from its anchor (the REST handler's entry) to `completion_ack` over the
activations that finished inside the window. Over HTTP that is the socket,
aiohttp on both sides, authentication and the response's serialisation."""


def read(art):
    wf, response = art.get("waterfall"), art.get("response_ms")
    if not wf or not response:
        return None
    inside = [i for i, s in enumerate(wf["stages"]) if s != "record_write"]
    n = max(wf["count"][i] for i in inside)
    if n == 0:
        return None
    return (sum(response) / len(response)
            - sum(wf["sum_us"][i] for i in inside) / n / 1e3)
