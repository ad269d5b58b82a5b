"""The placement step's algorithmic bytes, and the chips' peaks.

`step_bytes` counts what ANY kernel has to move for one fused step
(release fold + health fold + schedule of one batch), whichever kernel
serves it, as a function of the shapes alone:

* the books vector `free_mb` int32[N], read and written;
* the `health` vector, one byte per invoker, read;
* one `conc_free` column int32[N] per DISTINCT action in the batch, read
  and written (an action's concurrency pool lives in its column; no
  kernel can decide with less than the columns the batch names);
* the packed step input: 5 release rows and 9 request rows of B int32
  (the release axis shares the request axis' bucket);
* the decisions out: B + 1 int32.

The action-slot count A does not enter: the [N, A] matrix has A columns
but a batch names at most B of them. It is an argument so that a later
kernel-level metric can be written against the same signature.
"""
from __future__ import annotations

#: published peaks per chip, keyed by `jax.Device.device_kind`
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 16 GB HBM2e at "
                  "819 GB/s per chip",
    },
}


def peak_bytes_per_s(device_kind: str) -> float:
    try:
        return float(PEAKS[device_kind]["hbm_bytes_per_s"])
    except KeyError:
        raise KeyError(
            f"no peak known for device kind {device_kind!r}; add it to "
            f"benchmark/roofline.py PEAKS with its source") from None


def step_bytes(n: int, a: int, b: int, distinct: int) -> int:
    if min(n, a, b) <= 0 or not 0 <= distinct <= min(a, b):
        raise ValueError(f"bad step shape N={n} A={a} B={b} "
                         f"distinct={distinct}")
    books = 2 * 4 * n
    health = n
    columns = 2 * 4 * n * distinct
    packed_in = 4 * (5 + 9) * b
    out = 4 * (b + 1)
    return books + health + columns + packed_in + out


def least_step_seconds(device_kind: str, n: int, a: int, b: int,
                       distinct: int) -> float:
    return step_bytes(n, a, b, distinct) / peak_bytes_per_s(device_kind)
