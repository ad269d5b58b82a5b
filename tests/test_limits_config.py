"""ISSUE 28: the action limits a deployment configures.

`whisk.concurrency-limit` and `whisk.memory` (the reference's
application.conf:376-394) reach `ConcurrencyLimit` and `MemoryLimit`
through `limits_from_config`, which the controller, invoker and standalone
entry points call at boot, under the reference's own names
(`CONFIG_whisk_concurrencyLimit_max`, `CONFIG_whisk_memory_max`, ...).
Through the API both ways: without the configuration an action PUT with
`limits.concurrency: 10` (or 1024 MB) is refused with the reference's
message, with it the same PUT is accepted and read back.
"""
import asyncio
import base64

import aiohttp
import pytest

from openwhisk_tpu.core.entity import (MB, ConcurrencyLimit, LimitViolation,
                                       MemoryLimit, limits_from_config)
from openwhisk_tpu.standalone import GUEST_KEY, GUEST_UUID, make_standalone

AUTH = "Basic " + base64.b64encode(
    f"{GUEST_UUID}:{GUEST_KEY}".encode()).decode()
HDRS = {"Authorization": AUTH, "Content-Type": "application/json"}
PORT = 13491
URL = f"http://127.0.0.1:{PORT}/api/v1/namespaces/_/actions/limited"


@pytest.fixture(autouse=True)
def _limits_as_they_were():
    was = [(cls, cls.MIN, cls.STD, cls.MAX)
           for cls in (ConcurrencyLimit, MemoryLimit)]
    yield
    for cls, lo, std, hi in was:
        cls.MIN, cls.STD, cls.MAX = lo, std, hi


def _put_then_get(limits: dict) -> tuple:
    """Boot the standalone server as its entry point does, PUT one action
    with `limits`, read it back: (PUT status, PUT body, limits read back)."""
    async def go():
        controller = await make_standalone(port=PORT)
        try:
            async with aiohttp.ClientSession() as s:
                body = {"exec": {"kind": "python:3",
                                 "code": "def main(a): return a"},
                        "limits": limits}
                async with s.put(URL, headers=HDRS, json=body) as r:
                    status, answer = r.status, await r.json()
                async with s.get(URL, headers=HDRS) as r:
                    got = (await r.json()).get("limits") \
                        if r.status == 200 else None
                return status, answer, got
        finally:
            await controller.stop()
    return asyncio.run(go())


@pytest.mark.parametrize("env,limits,status,want", [
    # the reference's default: the feature is off, in its own words
    ({}, {"concurrency": 10}, 400,
     "concurrency 10 exceeds allowed threshold of 1"),
    ({"CONFIG_whisk_concurrencyLimit_max": "50"}, {"concurrency": 10}, 200,
     {"concurrency": 10}),
    ({"CONFIG_whisk_concurrencyLimit_max": "50"}, {"concurrency": 51}, 400,
     "concurrency 51 exceeds allowed threshold of 50"),
    ({"CONFIG_whisk_concurrencyLimit_min": "2",
      "CONFIG_whisk_concurrencyLimit_std": "4",
      "CONFIG_whisk_concurrencyLimit_max": "8"}, {"concurrency": 1}, 400,
     "concurrency 1 below allowed threshold of 2"),
    # satellite: memory through the same function (ROADMAP Reach A10)
    ({}, {"memory": 1024}, 400, "memory 1 GB exceeds allowed threshold 512 MB"),
    ({"CONFIG_whisk_memory_max": "2048 m"}, {"memory": 1024}, 200,
     {"memory": 1024}),
], ids=["conc-default", "conc-max50-accepts", "conc-max50-refuses-51",
        "conc-min2-refuses-1", "memory-default", "memory-max2048-accepts"])
def test_a_limit_through_the_api(monkeypatch, env, limits, status, want):
    for key in ("concurrencyLimit", "memory"):
        for bound in ("min", "std", "max"):
            monkeypatch.delenv(f"CONFIG_whisk_{key}_{bound}", raising=False)
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    got_status, answer, read_back = _put_then_get(limits)
    assert got_status == status, answer
    if status == 200:
        assert {k: read_back[k] for k in want} == want
    else:
        assert want in answer["error"] and read_back is None


def test_the_configured_std_is_an_action_s_default(monkeypatch):
    monkeypatch.setenv("CONFIG_whisk_concurrencyLimit_std", "4")
    monkeypatch.setenv("CONFIG_whisk_concurrencyLimit_max", "8")
    monkeypatch.setenv("CONFIG_whisk_memory_std", "512 MB")
    limits_from_config()
    assert ConcurrencyLimit().max_concurrent == 4
    assert MemoryLimit().megabytes == 512
    assert (MemoryLimit.MIN, MemoryLimit.MAX) == (MB(128), MB(512))
    with pytest.raises(LimitViolation):
        ConcurrencyLimit(9)


@pytest.mark.parametrize("env", [
    {"CONFIG_whisk_concurrencyLimit_max": "0"},
    {"CONFIG_whisk_concurrencyLimit_std": "5"},          # std above max 1
    {"CONFIG_whisk_memory_min": "1024 m"},               # min above std
    {"CONFIG_whisk_memory_max": "lots"},
], ids=["max-0", "std-over-max", "memory-min-over-std", "memory-no-size"])
def test_a_configuration_out_of_order_is_a_boot_error(monkeypatch, env):
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    with pytest.raises(ValueError):
        limits_from_config()
    # nothing was half applied
    assert (ConcurrencyLimit.MIN, ConcurrencyLimit.STD,
            ConcurrencyLimit.MAX) == (1, 1, 1)
    assert (MemoryLimit.MIN, MemoryLimit.STD,
            MemoryLimit.MAX) == (MB(128), MB(256), MB(512))
