"""Distributed-mode tests: TCP bus semantics, stable id assignment,
conductor compositions, and a REAL multi-process deployment (broker +
invoker + controller as separate OS processes, driven over HTTP — the
reference only exercises this against full ansible deployments)."""
import asyncio
import base64
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import aiohttp
import pytest

from openwhisk_tpu.database import SqliteArtifactStore
from openwhisk_tpu.invoker.id_assigner import InstanceIdAssigner
from openwhisk_tpu.messaging.tcp import TcpBusServer, TcpMessagingProvider

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TestTcpBus:
    def test_pub_peek_roundtrip(self):
        async def go():
            port = _free_port()
            server = TcpBusServer(port=port)
            await server.start()
            try:
                provider = TcpMessagingProvider(port=port)
                prod = provider.get_producer()
                cons = provider.get_consumer("t1", "g1")
                await prod.send("t1", b"hello")
                await prod.send("t1", b"world")
                batch = await cons.peek(10, timeout=1.0)
                await prod.close()
                await cons.close()
                return [p for (_, _, _, p) in batch]
            finally:
                await server.stop()

        assert asyncio.run(go()) == [b"hello", b"world"]

    def test_groups_compete_and_fanout(self):
        async def go():
            port = _free_port()
            server = TcpBusServer(port=port)
            await server.start()
            try:
                provider = TcpMessagingProvider(port=port)
                prod = provider.get_producer()
                g1a = provider.get_consumer("t", "g1")
                # subscribe first so both groups see subsequent messages
                await g1a.peek(1, timeout=0.05)
                g2 = provider.get_consumer("t", "g2")
                await g2.peek(1, timeout=0.05)
                for i in range(4):
                    await prod.send("t", f"m{i}".encode())
                b1 = await g1a.peek(10, timeout=0.5)
                b2 = await g2.peek(10, timeout=0.5)
                return len(b1), len(b2)
            finally:
                await server.stop()

        n1, n2 = asyncio.run(go())
        assert n1 == 4 and n2 == 4  # distinct groups each get every message

    def test_long_poll_blocks_until_message(self):
        async def go():
            port = _free_port()
            server = TcpBusServer(port=port)
            await server.start()
            try:
                provider = TcpMessagingProvider(port=port)
                prod = provider.get_producer()
                cons = provider.get_consumer("t", "g")
                await cons.peek(1, timeout=0.05)  # register group

                async def later():
                    await asyncio.sleep(0.2)
                    await prod.send("t", b"late")

                asyncio.get_event_loop().create_task(later())
                t0 = time.monotonic()
                batch = await cons.peek(1, timeout=2.0)
                return time.monotonic() - t0, len(batch)
            finally:
                await server.stop()

        dt, n = asyncio.run(go())
        assert n == 1
        assert 0.1 < dt < 1.5  # long-poll, not busy-wait


    def test_two_competing_consumers_get_each_of_1000_messages_once(self):
        """The broker's server side peeks a MemoryConsumer per connection:
        two connections of one group, both parked, share the queue."""
        async def go():
            port = _free_port()
            server = TcpBusServer(port=port)
            await server.start()
            try:
                provider = TcpMessagingProvider(port=port)
                prod = provider.get_producer()
                got = {"c1": [], "c2": []}

                async def consume(name):
                    cons = provider.get_consumer("t", "g", max_peek=16)
                    try:
                        while True:
                            batch = await cons.peek(16, timeout=0.5)
                            cons.commit()
                            got[name].extend(p for *_x, p in batch)
                    finally:
                        await cons.close()

                tasks = [asyncio.ensure_future(consume(n)) for n in got]
                await asyncio.sleep(0.1)      # both parked at the broker
                msgs = [f"m{i}".encode() for i in range(1000)]
                for i in range(0, 1000, 25):
                    await prod.send_many([("t", m, None)
                                          for m in msgs[i:i + 25]])
                deadline = time.monotonic() + 10
                while (len(got["c1"]) + len(got["c2"]) < 1000
                       and time.monotonic() < deadline):
                    await asyncio.sleep(0.01)
                for t in tasks:
                    t.cancel()
                await asyncio.wait(tasks)
                await prod.close()
                return got, msgs
            finally:
                await server.stop()

        got, msgs = asyncio.run(go())
        assert sorted(got["c1"] + got["c2"]) == sorted(msgs)
        assert got["c1"] and got["c2"]

    def test_long_poll_times_out_empty_and_leaves_the_broker_clean(self):
        async def go():
            port = _free_port()
            server = TcpBusServer(port=port)
            await server.start()
            try:
                provider = TcpMessagingProvider(port=port)
                cons = provider.get_consumer("t", "g")
                t0 = time.monotonic()
                batch = await cons.peek(4, timeout=0.2)
                dt = time.monotonic() - t0
                await cons.close()
                loop = asyncio.get_event_loop()
                live = [h for h in loop._scheduled if not h.cancelled()
                        and "expire" in repr(h)]
                return batch, dt, server.bus.topic("t").waiters, live
            finally:
                await server.stop()

        batch, dt, waiters, live = asyncio.run(go())
        assert batch == [] and 0.15 < dt < 1.5
        assert waiters == [] and live == []


class TestIdAssigner:
    def test_stable_assignment(self, tmp_path):
        async def go():
            store = SqliteArtifactStore(str(tmp_path / "ids.db"))
            a = InstanceIdAssigner(store)
            id1 = await a.assign("invoker-a")
            id2 = await a.assign("invoker-b")
            id1_again = await a.assign("invoker-a")
            forced = await a.assign("invoker-c", overwrite_id=9)
            id_next = await a.assign("invoker-d")
            return id1, id2, id1_again, forced, id_next

        id1, id2, id1_again, forced, id_next = asyncio.run(go())
        assert (id1, id2) == (0, 1)
        assert id1_again == 0  # stable across restarts
        assert forced == 9
        assert id_next == 10

    def test_concurrent_assignment_no_duplicates(self, tmp_path):
        async def go():
            store = SqliteArtifactStore(str(tmp_path / "ids2.db"))
            assigners = [InstanceIdAssigner(store) for _ in range(8)]
            ids = await asyncio.gather(*[
                a.assign(f"inv-{i}") for i, a in enumerate(assigners)])
            return ids

        ids = asyncio.run(go())
        assert sorted(ids) == list(range(8))  # CAS loop: no duplicate ids


class TestConductors:
    def test_composition_loop(self):
        """Conductor drives: increment twice then finish (the canonical
        composer pattern, ref PrimitiveActions.scala:208-360)."""
        from tests.test_system_standalone import (AUTH, HDRS, run_system, BASE)
        import aiohttp

        CONDUCTOR = """
def main(args):
    state = args.get('$composer', {'step': 0})
    step = state.get('step', 0)
    if step >= 2:
        return {'params': {'n': args.get('n', 0), 'done': True}}
    return {'action': '_/increment', 'params': {'n': args.get('n', 0)},
            'state': {'step': step + 1}}
"""
        INC = "def main(args):\n    return {'n': args.get('n', 0) + 1}\n"

        async def go(s: aiohttp.ClientSession):
            async with s.put(f"{BASE}/namespaces/_/actions/increment",
                             headers=HDRS,
                             json={"exec": {"kind": "python:3", "code": INC}}) as r:
                assert r.status == 200
            async with s.put(f"{BASE}/namespaces/_/actions/compose", headers=HDRS,
                             json={"exec": {"kind": "python:3", "code": CONDUCTOR},
                                   "annotations": [{"key": "conductor", "value": True}]}) as r:
                assert r.status == 200
            async with s.post(f"{BASE}/namespaces/_/actions/compose?blocking=true",
                              headers=HDRS, json={"n": 5}) as r:
                return r.status, await r.json()

        status, body = run_system(go)
        assert status == 200, body
        assert body["response"]["result"] == {"n": 7, "done": True}
        assert len(body["logs"]) == 5  # 3 conductor + 2 component activations
        assert any(a["key"] == "conductor" and a["value"] is True
                   for a in body["annotations"])

    def test_invalid_conductor_params_is_application_error(self):
        """A conductor returning a non-object `params` must yield an
        application error on the composition, not an HTTP 500."""
        from tests.test_system_standalone import (AUTH, HDRS, run_system, BASE)
        import aiohttp

        BAD = "def main(args):\n    return {'action': '_/x', 'params': 'oops'}\n"

        async def go(s: aiohttp.ClientSession):
            async with s.put(f"{BASE}/namespaces/_/actions/badcond", headers=HDRS,
                             json={"exec": {"kind": "python:3", "code": BAD},
                                   "annotations": [{"key": "conductor",
                                                    "value": True}]}) as r:
                assert r.status == 200
            async with s.post(f"{BASE}/namespaces/_/actions/badcond?blocking=true",
                              headers=HDRS, json={}) as r:
                return r.status, await r.json()

        status, body = run_system(go)
        assert status == 502  # application error, surfaced like any other
        assert "invalid response" in str(body["response"]["result"])

    def test_conductor_as_sequence_component(self):
        """A sequence whose component is a conductor must drive the whole
        composition, not hand the raw control dict to the next component."""
        from tests.test_system_standalone import (AUTH, HDRS, run_system, BASE)
        import aiohttp

        CONDUCTOR = """
def main(args):
    state = args.get('$composer', {'step': 0})
    if state.get('step', 0) >= 1:
        return {'params': {'n': args.get('n', 0)}}
    return {'action': '_/increment', 'params': {'n': args.get('n', 0)},
            'state': {'step': 1}}
"""
        INC = "def main(args):\n    return {'n': args.get('n', 0) + 1}\n"
        DOUBLE = "def main(args):\n    return {'n': args.get('n', 0) * 2}\n"

        async def go(s: aiohttp.ClientSession):
            for name, code, ann in (("increment", INC, []),
                                    ("double", DOUBLE, []),
                                    ("compose1", CONDUCTOR,
                                     [{"key": "conductor", "value": True}])):
                async with s.put(f"{BASE}/namespaces/_/actions/{name}",
                                 headers=HDRS,
                                 json={"exec": {"kind": "python:3", "code": code},
                                       "annotations": ann}) as r:
                    assert r.status == 200
            async with s.put(f"{BASE}/namespaces/_/actions/seqc", headers=HDRS,
                             json={"exec": {"kind": "sequence",
                                            "components": ["/_/compose1",
                                                           "/_/double"]}}) as r:
                assert r.status == 200, await r.text()
            async with s.post(f"{BASE}/namespaces/_/actions/seqc?blocking=true",
                              headers=HDRS, json={"n": 3}) as r:
                return r.status, await r.json()

        status, body = run_system(go)
        assert status == 200, body
        # conductor: 3 -> increment -> 4; then double -> 8
        assert body["response"]["result"] == {"n": 8}


@pytest.mark.slow
class TestMultiProcessDeployment:
    def test_broker_invoker_controller_processes(self, tmp_path):
        """Full distributed slice: 3 OS processes + HTTP client."""
        bus_port = _free_port()
        api_port = _free_port()
        db = str(tmp_path / "whisks.db")
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        procs = []
        try:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "openwhisk_tpu.messaging",
                 "--port", str(bus_port)], env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
            time.sleep(1.5)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "openwhisk_tpu.invoker",
                 "--bus", f"127.0.0.1:{bus_port}", "--db", db,
                 "--unique-name", "test-a", "--memory", "1024"],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "openwhisk_tpu.controller",
                 "--bus", f"127.0.0.1:{bus_port}", "--db", db,
                 "--port", str(api_port), "--balancer", "sharding",
                 "--seed-guest"], env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT))

            from openwhisk_tpu.standalone import GUEST_KEY, GUEST_UUID
            auth = "Basic " + base64.b64encode(
                f"{GUEST_UUID}:{GUEST_KEY}".encode()).decode()
            hdrs = {"Authorization": auth, "Content-Type": "application/json"}
            base = f"http://127.0.0.1:{api_port}/api/v1"

            async def drive():
                async with aiohttp.ClientSession() as s:
                    # wait for the API + a healthy invoker
                    for _ in range(60):
                        try:
                            async with s.get(f"http://127.0.0.1:{api_port}/invokers",
                                             headers=hdrs) as r:
                                if r.status == 200 and "up" in (await r.text()):
                                    break
                        except aiohttp.ClientError:
                            pass
                        await asyncio.sleep(0.5)
                    else:
                        raise AssertionError("fleet never became healthy")
                    async with s.put(f"{base}/namespaces/_/actions/dhello",
                                     headers=hdrs,
                                     json={"exec": {"kind": "python:3",
                                                    "code": "def main(a):\n    return {'via': 'distributed', 'n': a.get('n')}"}}) as r:
                        assert r.status == 200, await r.text()
                    async with s.post(
                            f"{base}/namespaces/_/actions/dhello?blocking=true&result=true",
                            headers=hdrs, json={"n": 42}) as r:
                        return r.status, await r.json()

            status, body = asyncio.run(drive())
            assert status == 200, body
            assert body == {"via": "distributed", "n": 42}
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    p.kill()
