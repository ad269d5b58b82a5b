"""Benchmark: activation placement decisions/sec on the TPU placement kernel.

Measures the steady-state rate of the balancer's device step — ONE fused
program (ops.placement.make_fused_step: previous batch's release fold +
health fold + a B=256 schedule) over the fleet size given by `--fleet`
(default 1024; the north-star config is 65536), exactly the program
TpuBalancer._device_step dispatches per micro-batch. Books are held
constant (each step releases the prior step's placements) so the loop runs
indefinitely.

What runs (default, no args):
  1. XLA kernel, median of 5 timed repeats (+ spread) — the headline number.
  2. Pallas kernel (ops/placement_pallas.py), same protocol — on a TPU
     this is the compiled kernel, on the CPU twin it is interpret mode.
  3. On-device parity: both kernels stepped from identical state over the
     same batch; chosen/forced/books compared exactly.
  4. Balancer-level benchmark: TpuBalancer.publish() -> placement future,
     echo invokers on the in-memory bus — activations/s and p50/p99
     publish->placement latency at client concurrencies c=64/8/1, each with
     a phase breakdown (assembly / dispatch / readback / fan-out ms). Two
     runs: the default backend, and a CPU-backend subprocess — the HOST-PATH
     row (labelled cpu), what the host machinery sustains with the device
     step out of the picture.

The device path runs on a TPU, or on the CPU only when JAX_PLATFORMS names
cpu (utils/config.check_device_platform): there is no CPU re-run of a device
stage, and any failure exits non-zero. A chip belongs to one process at a
time, so the stages that measure in a fresh device-owning child
(e2e_open_loop, sharded_fleet_sweep) run FIRST, one at a time, while this
process has not initialized JAX; CPU-pinned children (labelled cpu) never
touch the chip and run anywhere.

`--kernel xla|pallas` restricts step 1-2 to one kernel; `--quick` skips the
balancer bench; `--sweep` prints an (N invokers x A slots) xla-vs-pallas
rate table to stderr for kernel-selection docs — sweep mode emits NO JSON
line on stdout and ignores --kernel/--quick (it is a diagnostic, not the
driver contract).

Baseline: BASELINE.json targets >= 50,000 placements/s (reference point: the
CPU ShardingContainerPoolBalancer inner loop, which this kernel replaces).
`vs_baseline` = median XLA rate / 50,000. A CPU-oracle rate is also measured
for context (stderr).

Prints ONE JSON line on stdout; every secondary figure rides along as extra
keys (device, kernels, parity_ok, balancer, spread).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import time

import numpy as np

N_INVOKERS = 1024
BATCH = 256
WARMUP = 5
ITERS = 40
REPEATS = 5
TARGET = 50_000.0


def _build_fused(kernel: str):
    """The balancer's fused device program with the requested schedule
    kernel — mirrors TpuBalancer._init_device_state's wrapping."""
    import jax

    from openwhisk_tpu.ops.placement import (PlacementState, make_fused_step,
                                             schedule_batch)

    if kernel == "pallas":
        from openwhisk_tpu.ops.placement_pallas import (schedule_batch_pallas,
                                                        to_transposed)
        interpret = jax.default_backend() == "cpu"

        def sched(st, b):
            ts, *out = schedule_batch_pallas(
                to_transposed(st), b, interpret=interpret)
            return (PlacementState(ts.free_mb, ts.conc_free.T, ts.health),
                    *out)

        return make_fused_step(None, sched)
    if kernel == "pallas_repair":
        from openwhisk_tpu.ops.placement import release_batch_vector
        from openwhisk_tpu.ops.placement_pallas import (
            schedule_batch_repair_pallas, to_transposed)
        interpret = jax.default_backend() == "cpu"

        def sched(st, b):
            ts, *out = schedule_batch_repair_pallas(
                to_transposed(st), b, interpret=interpret)
            return (PlacementState(ts.free_mb, ts.conc_free.T, ts.health),
                    *out)

        return make_fused_step(release_batch_vector, sched)
    if kernel == "repair":
        from openwhisk_tpu.ops.placement import (release_batch_vector,
                                                 schedule_batch_repair)
        return make_fused_step(release_batch_vector, schedule_batch_repair)
    return make_fused_step(None, schedule_batch)


def _bench_kernel(kernel: str, n_invokers: int = N_INVOKERS,
                  action_slots: int = 256, repeats: int = REPEATS,
                  iters: int = ITERS, batch_size: int = BATCH,
                  batch=None) -> dict:
    """Median-of-`repeats` steady-state rate for one kernel."""
    import jax
    import jax.numpy as jnp

    from __graft_entry__ import _example_batch
    from openwhisk_tpu.ops.placement import init_state

    state0 = init_state(n_invokers, [2048] * n_invokers,
                        action_slots=action_slots)
    if batch is None:
        batch = _example_batch(n_invokers, batch_size, seed=7)
    else:
        batch_size = int(batch.valid.shape[0])
    fused = _build_fused(kernel)
    hidx = jnp.zeros((8,), jnp.int32)
    hval = jnp.zeros((8,), bool)
    hmask = jnp.zeros((8,), bool)

    def step(carry):
        state, rel_inv, rel_ok = carry
        state, chosen, forced, _warm, _rounds = fused(
            state, rel_inv, batch.conc_slot, batch.need_mb, batch.max_conc,
            rel_ok, hidx, hval, hmask, batch)
        return (state, jnp.clip(chosen, 0), chosen >= 0), chosen

    carry = (state0, jnp.zeros((batch_size,), jnp.int32),
             jnp.zeros((batch_size,), bool))
    for _ in range(WARMUP):
        carry, chosen = step(carry)
    jax.block_until_ready(carry)

    rates, p50s = [], []
    for _ in range(repeats):
        lat = []
        t0 = time.perf_counter()
        for _ in range(iters):
            t1 = time.perf_counter()
            carry, chosen = step(carry)
            jax.block_until_ready(chosen)
            lat.append(time.perf_counter() - t1)
        dt = time.perf_counter() - t0
        rates.append(batch_size * iters / dt)
        p50s.append(sorted(lat)[len(lat) // 2] * 1e3)

    med = statistics.median(rates)
    return {
        "rate_median": round(med, 1),
        "rate_min": round(min(rates), 1),
        "rate_max": round(max(rates), 1),
        "spread_pct": round(100.0 * (max(rates) - min(rates)) / med, 1),
        "p50_step_ms": round(statistics.median(p50s), 3),
        "repeats": repeats,
    }


def _parity_check(n_invokers: int = 512, action_slots: int = 128) -> bool:
    """Step the XLA and pallas kernels from identical state over the same
    batch ON DEVICE and compare placements and books exactly."""
    import numpy as np

    from __graft_entry__ import _example_batch
    from openwhisk_tpu.ops.placement import init_state

    batch = _example_batch(n_invokers, BATCH, seed=11)
    import jax.numpy as jnp
    hidx = jnp.zeros((8,), jnp.int32)
    hval = jnp.zeros((8,), bool)
    hmask = jnp.zeros((8,), bool)
    no_rel = jnp.zeros((BATCH,), bool)
    rel_inv = jnp.zeros((BATCH,), jnp.int32)

    outs = {}
    for kernel in ("xla", "pallas"):
        state = init_state(n_invokers, [2048] * n_invokers,
                           action_slots=action_slots)
        fused = _build_fused(kernel)
        # two steps: the second exercises release-fold + scheduling on
        # non-trivial books
        state, chosen1, forced1, _, _ = fused(
            state, rel_inv, batch.conc_slot, batch.need_mb, batch.max_conc,
            no_rel, hidx, hval, hmask, batch)
        state, chosen2, forced2, _, _ = fused(
            state, jnp.clip(chosen1, 0), batch.conc_slot, batch.need_mb,
            batch.max_conc, chosen1 >= 0, hidx, hval, hmask, batch)
        outs[kernel] = tuple(np.asarray(x) for x in
                             (chosen1, forced1, chosen2, forced2,
                              state.free_mb, state.conc_free, state.health))

    ok = all(np.array_equal(a, b) for a, b in zip(outs["xla"], outs["pallas"]))
    if not ok:
        for i, name in enumerate(("chosen1", "forced1", "chosen2", "forced2",
                                  "free_mb", "conc_free", "health")):
            if not np.array_equal(outs["xla"][i], outs["pallas"][i]):
                print(f"# PARITY MISMATCH in {name}", file=sys.stderr)
    return ok


def _bench_action(name, memory=256):
    from openwhisk_tpu.core.entity import (ActionLimits, CodeExec, EntityName,
                                           EntityPath, ExecutableWhiskAction,
                                           MB, MemoryLimit, TimeLimit)
    from openwhisk_tpu.core.entity.ids import DocRevision

    a = ExecutableWhiskAction(EntityPath("guest"), EntityName(name),
                              CodeExec(kind="python:3", code="x"),
                              limits=ActionLimits(TimeLimit(5000),
                                                  MemoryLimit(MB(memory))))
    a.rev = DocRevision("1-b")
    return a


async def _echo_invoker(provider, instance, delay=0.0, on_frame=None):
    """An invoker stand-in: consumes its topic, acks every activation
    immediately with a successful record (pure control-plane load). Rides
    the same batch wire as the real InvokerReactive: a columnar dispatch
    frame decodes ONCE, and the whole frame's acks are submitted in one
    sweep so they coalesce into one ack batch frame back.

    `delay` rides as a mutable attribute on the returned feed (the PR 4
    SimInvoker idiom, so tools/loadgen.py's `apply_stragglers` drives
    test stubs and bench feeds through the same knob): a straggler's
    acks sleep `feed.delay` seconds before flushing.

    `on_frame(instance, msgs)` is a synchronous per-frame hook (the
    trace-assembly rider emits invoker-side spans from it, standing in
    for the real InvokerReactive's container span pair)."""
    from openwhisk_tpu.core.entity import (ActivationResponse, EntityPath,
                                           WhiskActivation)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         CombinedCompletionAndResultMessage,
                                         MessageFeed)
    from openwhisk_tpu.messaging.columnar import is_batch_payload
    from openwhisk_tpu.messaging.connector import (decode_batch,
                                                   decode_message)

    topic = instance.as_string
    provider.ensure_topic(topic)
    consumer = provider.get_consumer(topic, topic)
    # the stand-in rides the same ack coalescing as the real
    # InvokerReactive, so the e2e riders measure the shipped completion path
    from openwhisk_tpu.messaging import maybe_coalesce
    producer = maybe_coalesce(provider.get_producer())
    box = {}

    async def handle(payload: bytes):
        if is_batch_payload(payload):
            _kind, msgs = decode_batch(payload)
        else:
            msgs = [decode_message(ActivationMessage.parse, payload,
                                   "activation")]
        if on_frame is not None:
            on_frame(instance, msgs)
        now = time.time()
        by_topic = {}
        for msg in msgs:
            act = WhiskActivation(
                EntityPath(str(msg.user.namespace.name)), msg.action.name,
                msg.user.subject, msg.activation_id, now, now,
                ActivationResponse.success({"ok": True}), duration=1)
            by_topic.setdefault(
                f"completed{msg.root_controller_index.as_string}",
                []).append(CombinedCompletionAndResultMessage(
                    msg.transid, act, instance))
        # straggler injection: read the live knob each frame (riders and
        # tests retune it mid-run, like the PR 4 SimInvoker scenario)
        d = getattr(box["feed"], "delay", 0.0)
        if d:
            await asyncio.sleep(d)
        # send_batch: every ack submits in THIS sweep (one dispatch
        # frame's acks flush as one ack batch frame) with no task per
        # message — asyncio.gather over N send() coroutines minted a
        # Task each, measurable loop churn at thousands of acks/s
        for topic, acks in by_topic.items():
            await producer.send_batch(topic, acks)
        box["feed"].processed()

    feed = MessageFeed(topic, consumer, 256, handle)
    feed.delay = delay
    box["feed"] = feed
    feed.start()
    return feed


async def _echo_fleet(provider, n_invokers, stragglers=None, on_frame=None):
    """Start `n_invokers` echo invokers + a 1 Hz pinger (supervision marks a
    fleet Offline after 10 s of silence, which a cold first compile easily
    outlasts). Returns (feeds, stop) — await stop() to end the pinger.
    `stragglers`: a {index: delay_s} map (or the loadgen SPEC string) —
    those invokers' acks are delayed from the first frame."""
    from openwhisk_tpu.core.entity import MB, InvokerInstanceId
    from openwhisk_tpu.messaging import PingMessage
    from tools.loadgen import parse_stragglers

    slow = parse_stragglers(stragglers)
    producer = provider.get_producer()
    provider.ensure_topic("health")
    feeds, instances = [], []
    for i in range(n_invokers):
        inst = InvokerInstanceId(i, user_memory=MB(8192))
        instances.append(inst)
        feeds.append(await _echo_invoker(provider, inst,
                                         delay=slow.get(i, 0.0),
                                         on_frame=on_frame))
        await producer.send("health", PingMessage(inst))
    stop_ping = asyncio.Event()

    async def pinger():
        while not stop_ping.is_set():
            for inst in instances:
                await producer.send("health", PingMessage(inst))
            try:
                await asyncio.wait_for(stop_ping.wait(), 1.0)
            except asyncio.TimeoutError:
                pass

    ping_task = asyncio.ensure_future(pinger())

    async def stop():
        stop_ping.set()
        await ping_task

    return feeds, stop


def _balancer_bench(n_invokers: int = 16, total: int = 2000,
                    concurrency: int = 64, kernel: str = "auto",
                    flight_recorder: bool = True,
                    telemetry: bool = True,
                    profiling: bool = True,
                    anomaly: bool = True,
                    waterfall: bool = True,
                    fleet_observatory: bool = True) -> dict:
    """TpuBalancer.publish() end-to-end on the in-memory bus with echo
    invokers: the full host path (slot alloc, micro-batch assembly, device
    step, promise fan-out, bus send) that the raw kernel number omits.

    CLOSED-loop by construction (`concurrency` workers behind a
    semaphore): the system sets the arrival rate, so the percentiles
    suffer coordinated omission under saturation — the row says so
    (`mode: "closed_loop"`) and rides as a comparison beside the
    `e2e_open_loop` headline (tools/loadgen.py), which measures from
    scheduled arrival instead."""
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.core.entity import (ActivationId, ControllerInstanceId,
                                           Identity)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         MemoryMessagingProvider)
    from openwhisk_tpu.ops.profiler import KernelProfiler, ProfilingConfig
    from openwhisk_tpu.utils.transaction import TransactionId
    from openwhisk_tpu.utils.waterfall import GLOBAL_WATERFALL

    make_action = _bench_action

    async def go() -> dict:
        provider = MemoryMessagingProvider()
        # the profiler wraps the jitted entry points at construction, so
        # the OFF run must disable it BEFORE the balancer builds them
        prof = KernelProfiler(ProfilingConfig(enabled=profiling))
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          kernel=kernel, profiler=prof)
        bal.flight_recorder.enabled = flight_recorder
        bal.telemetry.enabled = telemetry
        bal.anomaly.enabled = anomaly
        # the waterfall plane is process-global (its stages span layers):
        # toggle + reset it per run so the overhead rider's OFF half is a
        # true no-op and the ON half starts from clean aggregates
        GLOBAL_WATERFALL.enabled = waterfall
        GLOBAL_WATERFALL.reset()
        # the event log is process-global like the waterfall; structural
        # events are rare by design, so the ON half measures the ambient
        # cost of the armed plane (the `enabled` branch at call sites)
        from openwhisk_tpu.utils.eventlog import GLOBAL_EVENT_LOG
        GLOBAL_EVENT_LOG.enabled = fleet_observatory
        GLOBAL_EVENT_LOG.reset()
        await bal.start()
        feeds, stop_fleet = await _echo_fleet(provider, n_invokers)
        # wait until supervision has actually registered the fleet (a fixed
        # sleep races the first device-program compile on slow channels)
        from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
        for _ in range(120):
            health = await bal.invoker_health()
            if sum(h.status == HEALTHY for h in health) >= n_invokers:
                break
            await asyncio.sleep(0.25)
        else:
            raise RuntimeError("balancer bench: fleet never became healthy")

        actions = [make_action(f"bench{i}", memory=128) for i in range(8)]
        ident = Identity.generate("guest")
        lat: list = []
        e2e: list = []
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            action = actions[i % len(actions)]
            msg = ActivationMessage(
                TransactionId(), action.fully_qualified_name, action.rev.rev,
                ident, ActivationId.generate(), ControllerInstanceId("0"),
                True, {})
            async with sem:
                if waterfall:
                    GLOBAL_WATERFALL.begin(msg.activation_id.asString)
                t0 = time.perf_counter()
                promise = await bal.publish(action, msg)
                lat.append(time.perf_counter() - t0)
                await promise
                # completion-based e2e beside the publish()-only number:
                # publish() resolves at PLACEMENT, so its percentiles miss
                # the produce/pickup/ack half of the path entirely
                e2e.append(time.perf_counter() - t0)

        # warmup: two rounds so the power-of-two schedule/release bucket
        # shapes the measured run will hit are already compiled
        for _ in range(2):
            await asyncio.gather(*[one(i) for i in range(min(128, total))])
        lat.clear()
        e2e.clear()
        if waterfall:
            GLOBAL_WATERFALL.reset()  # drop warmup compile outliers
        # fresh metrics: the warmup rounds polluted the phase histograms
        # with first-call jit-compile outliers (hundreds of ms dispatches)
        bal.metrics = type(bal.metrics)()
        t0 = time.perf_counter()
        await asyncio.gather(*[one(i) for i in range(total)])
        wall = time.perf_counter() - t0
        await stop_fleet()
        await bal.close()
        for f in feeds:
            await f.stop()

        lat.sort()
        e2e.sort()
        phases = {}
        for ph in ("assembly", "dispatch", "readback", "fanout"):
            st = bal.metrics.histogram_stats(f"loadbalancer_tpu_{ph}_ms")
            if st:
                phases[ph] = {"p50_ms": round(st["p50"], 3),
                              "mean_ms": round(st["mean"], 3)}
        bs = bal.metrics.histogram_stats("loadbalancer_tpu_batch_size")
        rounds = bal.metrics.histogram_stats("loadbalancer_repair_rounds")
        return {
            # closed loop: arrivals are gated on completions, so these
            # percentiles under-report queueing delay at saturation
            # (coordinated omission) — the open-loop rider is the headline
            "mode": "closed_loop",
            "activations_per_sec": round(total / wall, 1),
            "publish_p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
            "publish_p99_ms": round(lat[int(len(lat) * 0.99)] * 1e3, 3),
            "e2e_p50_ms": round(e2e[len(e2e) // 2] * 1e3, 3),
            "e2e_p99_ms": round(e2e[int(len(e2e) * 0.99)] * 1e3, 3),
            "concurrency": concurrency,
            "n_invokers": n_invokers,
            "phases": phases,
            "batch_size_mean": round(bs["mean"], 1) if bs else None,
            "repair_rounds_mean": round(rounds["mean"], 2) if rounds else None,
            # the PR-5 acceptance gate: the hot-path overhaul must add ZERO
            # unexpected recompiles (PR-3 watchdog clean)
            "recompiles_unexpected": prof.compiles_unexpected,
        }

    return asyncio.run(go())


def _mc_worker(instance: int, cluster_size: int, port: int, total: int,
               concurrency: int, n_invokers: int) -> None:
    """Subprocess entry for the multi-controller stage: ONE TpuBalancer
    (cluster-sharded capacity: each controller gets user_memory/cluster_size
    per invoker, the reference's getInvokerSlot) publishing over the TCP bus
    against the parent's shared echo fleet. Protocol: print READY after
    warmup, wait for GO on stdin, run, print one JSON line."""
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
    from openwhisk_tpu.core.entity import (ActivationId, ControllerInstanceId,
                                           Identity)
    from openwhisk_tpu.messaging import ActivationMessage
    from openwhisk_tpu.messaging.tcp import TcpMessagingProvider
    from openwhisk_tpu.utils.transaction import TransactionId

    async def go():
        provider = TcpMessagingProvider(port=port)
        bal = TpuBalancer(provider, ControllerInstanceId(str(instance)),
                          cluster_size=cluster_size,
                          managed_fraction=1.0, blackbox_fraction=0.0)
        await bal.start()
        for _ in range(240):
            health = await bal.invoker_health()
            if sum(h.status == HEALTHY for h in health) >= n_invokers:
                break
            await asyncio.sleep(0.25)
        else:
            raise RuntimeError(f"worker {instance}: fleet never healthy")
        actions = [_bench_action(f"mc{instance}_{i}", memory=128)
                   for i in range(8)]
        ident = Identity.generate("guest")
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            action = actions[i % len(actions)]
            msg = ActivationMessage(
                TransactionId(), action.fully_qualified_name, action.rev.rev,
                ident, ActivationId.generate(),
                ControllerInstanceId(str(instance)), True, {})
            async with sem:
                promise = await bal.publish(action, msg)
                await promise

        for _ in range(2):
            await asyncio.gather(*[one(i) for i in range(min(128, total))])
        print("READY", flush=True)
        await asyncio.to_thread(sys.stdin.readline)  # GO
        t0 = time.time()
        await asyncio.gather(*[one(i) for i in range(total)])
        t1 = time.time()
        await bal.close()
        print(json.dumps({"instance": instance, "total": total,
                          "t0": t0, "t1": t1,
                          "rate": round(total / (t1 - t0), 1)}), flush=True)

    asyncio.run(go())


def _multi_controller_bench(n_controllers: int, total_per: int = 1500,
                            concurrency: int = 64, n_invokers: int = 16
                            ) -> dict:
    """Control-plane scale-out: N controller processes (cluster-sharded
    capacity over one shared echo fleet) publishing concurrently over the
    TCP bus; reports per-controller and AGGREGATE activations/s. Every
    worker builds its own TpuBalancer and a chip belongs to one process,
    so the workers are pinned to the CPU backend and the block is labelled
    `backend: cpu`: this stage measures control-plane scale-out, not the
    device."""
    import os
    import socket

    from openwhisk_tpu.messaging.tcp import TcpBusServer, TcpMessagingProvider

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    async def go() -> dict:
        server = TcpBusServer(port=port)
        await server.start()
        # the echo fleet is co-located with the broker: attach it to the
        # broker's in-process MemoryBus directly (same queues the TCP
        # workers see) instead of round-tripping localhost TCP into our own
        # process — co-located components take the in-process fast path
        from openwhisk_tpu.messaging import MemoryMessagingProvider
        provider = MemoryMessagingProvider()
        provider.bus = server.bus
        feeds, stop_fleet = await _echo_fleet(provider, n_invokers)
        procs = []

        async def read_line(p):
            line = await p.stdout.readline()
            return line.decode().strip()

        try:
            for i in range(n_controllers):
                code = (f"import bench; bench._mc_worker({i}, "
                        f"{n_controllers}, {port}, {total_per}, "
                        f"{concurrency}, {n_invokers})")
                procs.append(await asyncio.create_subprocess_exec(
                    sys.executable, "-c", code,
                    stdin=asyncio.subprocess.PIPE,
                    stdout=asyncio.subprocess.PIPE,
                    stderr=asyncio.subprocess.DEVNULL,
                    env=dict(os.environ, JAX_PLATFORMS="cpu"),
                    cwd=os.path.dirname(os.path.abspath(__file__))))
            ready = await asyncio.wait_for(
                asyncio.gather(*[read_line(p) for p in procs]), timeout=600)
            if any(r != "READY" for r in ready):
                raise RuntimeError(f"workers not ready: {ready}")
            for p in procs:
                p.stdin.write(b"GO\n")
                await p.stdin.drain()
            results = [json.loads(await asyncio.wait_for(read_line(p), 600))
                       for p in procs]
        finally:
            for p in procs:
                if p.returncode is None:
                    p.kill()
                await p.wait()
            await stop_fleet()
            for f in feeds:
                await f.stop()
            await server.stop()

        wall = max(r["t1"] for r in results) - min(r["t0"] for r in results)
        return {
            "n_controllers": n_controllers,
            "backend": "cpu",
            "aggregate_activations_per_sec": round(
                sum(r["total"] for r in results) / wall, 1),
            "per_controller": [r["rate"] for r in results],
            "concurrency_per_controller": concurrency,
            "n_invokers": n_invokers,
        }

    return asyncio.run(go())


def _balancer_rows() -> dict:
    """The balancer stage at three client concurrencies: c=64 is the
    throughput row, c=8 the mid point, c=1 isolates the batching window's
    idle-latency cost (SURVEY §7's batching-vs-latency tension as a
    measured number)."""
    return {
        "c64": _balancer_bench(total=2000, concurrency=64),
        "c8": _balancer_bench(total=600, concurrency=8),
        "c1": _balancer_bench(total=150, concurrency=1),
    }


def _subprocess_json(expr: str, marker: str, label: str,
                     pin_cpu: bool = False, force_devices: bool = False,
                     timeout_s: int = 1200) -> Optional[dict]:
    """Evaluate one `bench.*` expression in a FRESH subprocess and parse
    its marker-prefixed JSON stdout line. Two uses share this runner:
    `pin_cpu` pins the subprocess to the CPU backend (an explicit CPU row,
    labelled cpu by its caller; `force_devices` adds the 8-virtual-device
    XLA flag for runs needing the full CPU mesh), while the default
    INHERITS the current backend env — process isolation for riders whose
    measurement a lived-in process skews (a prior kernel bench leaves dead
    executables and GC pressure behind, and in an open-loop window those
    stalls read exactly like saturation; measured). A backend-inheriting
    child OWNS the device: start it only while this process has not
    initialized JAX (see _run)."""
    import os
    import subprocess
    env_lines = ["import os, json"]
    if pin_cpu:
        env_lines.append("os.environ['JAX_PLATFORMS'] = 'cpu'")
        if force_devices:
            env_lines.append(
                "os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + "
                "' --xla_force_host_platform_device_count=8'")
    code = "\n".join(env_lines + [
        "from openwhisk_tpu.utils.config import boot_jax",
        "boot_jax()",
        "import bench",
        f"print('{marker}:' + json.dumps({expr}))",
    ]) + "\n"
    try:
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=timeout_s)
        for line in out.stdout.splitlines():
            if line.startswith(marker + ":"):
                return json.loads(line[len(marker) + 1:])
        print(f"# {label} failed: {out.stderr[-400:]}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — auxiliary measure
        print(f"# {label} failed: {e!r}", file=sys.stderr)
    return None


def _cpu_subprocess_json(expr: str, marker: str, label: str,
                         force_devices: bool = False) -> Optional[dict]:
    """CPU-pinned variant of _subprocess_json: an explicit CPU row."""
    return _subprocess_json(expr, marker, label, pin_cpu=True,
                            force_devices=force_devices)


def _balancer_host_rows() -> Optional[dict]:
    """The same balancer rows forced onto the CPU backend in a subprocess:
    the HOST-PATH measure (labelled cpu) — what the host machinery itself
    sustains with the device step out of the picture."""
    return _cpu_subprocess_json("bench._balancer_rows()", "BENCHJSON",
                                "balancer host-path run",
                                force_devices=True)


def _plane_overhead(flag: str, key: str, repeats: int = 3, total: int = 1000,
                    concurrency: int = 64) -> Optional[dict]:
    """The observability tax, shared rider body: best XLA-kernel
    placement rate through the full balancer path with one plane ON vs
    OFF. Every plane lives somewhere on the dispatch/completion path, so
    the balancer-level rate — not the raw kernel step — is where its cost
    can show. `flag` is the _balancer_bench kwarg that toggles the plane,
    `key` names the result fields (`rate_{key}_on/off`). Acceptance gate
    for each plane: overhead_pct <= 5 (ISSUEs 1-4, 16).

    Each arm is judged by its BEST repeat after one discarded warmup run:
    throughput noise on a shared host is one-sided (GC, scheduling and
    first-compile hiccups only ever slow a run down, never speed it up),
    so best-of-N converges on the true marginal cost where a median of 3
    can report a double-digit phantom overhead for a plane that provably
    records nothing on the measured path."""
    try:
        _balancer_bench(total=total, concurrency=concurrency,
                        kernel="xla", **{flag: False})  # warmup, discarded
        on_rates, off_rates = [], []
        for _ in range(repeats):
            on_rates.append(_balancer_bench(
                total=total, concurrency=concurrency, kernel="xla",
                **{flag: True})["activations_per_sec"])
            off_rates.append(_balancer_bench(
                total=total, concurrency=concurrency, kernel="xla",
                **{flag: False})["activations_per_sec"])
        on = max(on_rates)
        off = max(off_rates)
        return {
            f"rate_{key}_on": round(on, 1),
            f"rate_{key}_off": round(off, 1),
            "overhead_pct": round(100.0 * (off - on) / off, 2) if off else None,
            "repeats": repeats,
            "agg": "best_of_n_after_warmup",
        }
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# {key}_overhead failed: {e!r}", file=sys.stderr)
        return None


# Named wrappers: one module-level entry point per plane.

def _flight_recorder_overhead(**kw) -> Optional[dict]:
    return _plane_overhead("flight_recorder", "recorder", **kw)


def _telemetry_overhead(**kw) -> Optional[dict]:
    return _plane_overhead("telemetry", "telemetry", **kw)


def _profiling_overhead(**kw) -> Optional[dict]:
    return _plane_overhead("profiling", "profiling", **kw)


def _anomaly_overhead(**kw) -> Optional[dict]:
    return _plane_overhead("anomaly", "anomaly", **kw)


def _waterfall_overhead(**kw) -> Optional[dict]:
    """ISSUE 7 gate: per-activation stage stamping must cost <= 5% through
    the full balancer path (same protocol as the other four planes)."""
    return _plane_overhead("waterfall", "waterfall", **kw)


def _fleet_observatory_overhead(repeats: int = 20, total: int = 1000,
                                concurrency: int = 64) -> Optional[dict]:
    """ISSUE 16 gate: the fleet observatory is scrape-pull-only — with no
    scraper attached its steady-state cost is the armed EventLog (one
    bool branch at structural call sites, which a placement-only bench
    never even takes), so the expected overhead is ~0.

    That makes the shared `_plane_overhead` protocol (fresh fixture per
    arm per repeat) the wrong instrument: on a shared host the balancer
    rate swings 4x run-to-run, and a between-run comparison of a ~0%
    effect reports pure noise with either sign. This rider instead builds
    the fixture ONCE and alternates armed/disarmed measured segments
    back-to-back inside the same process — each pair shares the host's
    momentary throughput mode, so the paired ratio isolates the plane's
    marginal cost. Segment order flips every repeat to cancel drift.
    Individual pairs still carry tens-of-percent host jitter at ~0.5 s
    segment lengths, so the verdict is a 20%-trimmed mean over many
    pairs — per-pair noise is zero-mean once paired, and the trim guards
    the tails a mean can't."""
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.core.entity import (ActivationId, ControllerInstanceId,
                                           Identity)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         MemoryMessagingProvider)
    from openwhisk_tpu.utils.eventlog import GLOBAL_EVENT_LOG
    from openwhisk_tpu.utils.transaction import TransactionId

    async def go() -> dict:
        provider = MemoryMessagingProvider()
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          kernel="xla")
        await bal.start()
        feeds, stop_fleet = await _echo_fleet(provider, 16)
        from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
        for _ in range(120):
            health = await bal.invoker_health()
            if sum(h.status == HEALTHY for h in health) >= 16:
                break
            await asyncio.sleep(0.25)
        else:
            raise RuntimeError("fleet observatory rider: fleet unhealthy")

        actions = [_bench_action(f"fo{i}", memory=128) for i in range(8)]
        ident = Identity.generate("guest")
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            action = actions[i % len(actions)]
            msg = ActivationMessage(
                TransactionId(), action.fully_qualified_name, action.rev.rev,
                ident, ActivationId.generate(), ControllerInstanceId("0"),
                True, {})
            async with sem:
                promise = await bal.publish(action, msg)
                await promise

        async def segment() -> float:
            t0 = time.perf_counter()
            await asyncio.gather(*[one(i) for i in range(total)])
            return total / (time.perf_counter() - t0)

        try:
            # warmup: compile + settle before any measured segment
            await segment()
            was = GLOBAL_EVENT_LOG.enabled
            pairs = []
            on_rates, off_rates = [], []
            for k in range(repeats):
                order = (True, False) if k % 2 == 0 else (False, True)
                rate = {}
                for armed in order:
                    GLOBAL_EVENT_LOG.enabled = armed
                    GLOBAL_EVENT_LOG.reset()
                    rate[armed] = await segment()
                GLOBAL_EVENT_LOG.enabled = was
                on_rates.append(rate[True])
                off_rates.append(rate[False])
                pairs.append(100.0 * (rate[False] - rate[True])
                             / rate[False])
        finally:
            await stop_fleet()
            await bal.close()
            for f in feeds:
                await f.stop()
        trim = max(1, len(pairs) // 5)
        kept = sorted(pairs)[trim:-trim] if len(pairs) > 2 * trim else pairs
        return {
            "rate_fleet_observatory_on": round(max(on_rates), 1),
            "rate_fleet_observatory_off": round(max(off_rates), 1),
            "overhead_pct": round(statistics.mean(kept), 2),
            "pair_overheads_pct": [round(p, 2) for p in pairs],
            "repeats": repeats,
            "agg": "trimmed_mean_paired_segments",
        }

    try:
        return asyncio.run(go())
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# fleet_observatory_overhead failed: {e!r}", file=sys.stderr)
        return None


def _trace_assembly(clean: int = 192, stragglers_n: int = 12,
                    n_invokers: int = 8) -> Optional[dict]:
    """ISSUE 18 acceptance: a spillover burst with injected stragglers
    through the tail-sampled trace observatory, four legs in one fixture:

      clean bulk   reason-free traffic keeps at the deterministic floor
                   (keep_floor=0.05 -> every 20th completion);
      stragglers   a delayed-fleet salvo lands above the live tail
                   threshold -> 100% kept with reason `slow`;
      spillover    non-blocking overflow diverts b0 -> b1; every spilled
                   trace is kept, and at least one assembles into a tree
                   spanning >= 3 processes whose origin stage spans
                   telescope to the waterfall total;
      dead peer    GET /admin/trace/{id} through a real Controller with
                   a dead member answers 200 + members_missing (never a
                   500), and every OpenMetrics exemplar rendered during
                   the run resolves to a kept trace.
    """
    import base64
    import dataclasses
    import re

    import aiohttp
    from aiohttp import web as aioweb

    from openwhisk_tpu.controller.core import Controller
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
    from openwhisk_tpu.controller.loadbalancer.lean import LeanBalancer
    from openwhisk_tpu.controller.loadbalancer.partitions import PartitionRing
    from openwhisk_tpu.controller.loadbalancer.spillover import (
        SpilloverReceiver, SpilloverSender)
    from openwhisk_tpu.core.entity import (MB, ActivationId,
                                           ControllerInstanceId, Identity,
                                           WhiskAuthRecord)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         MemoryMessagingProvider)
    from openwhisk_tpu.utils.logging import NullLogging
    from openwhisk_tpu.utils.tracestore import (GLOBAL_TRACE_STORE,
                                                assemble_trace,
                                                synthetic_span)
    from openwhisk_tpu.utils.tracing import GLOBAL_TRACER, trace_id_of
    from openwhisk_tpu.utils.transaction import TransactionId
    from openwhisk_tpu.utils.waterfall import GLOBAL_WATERFALL, N_STAGES

    store = GLOBAL_TRACE_STORE
    CTL_PORT, PEER_PORT = 13981, 13982

    async def go() -> dict:
        was_enabled, was_cfg = store.enabled, store.config
        was_floor = store._floor_every
        wf_was = GLOBAL_WATERFALL.enabled
        # arm the plane with a floor crisp enough to assert exactly
        store.enabled = True
        store.config = dataclasses.replace(store.config, keep_floor=0.05,
                                           keep_ring=1024)
        store._floor_every = 20
        store.reset()
        store.attach()
        GLOBAL_WATERFALL.enabled = True
        GLOBAL_WATERFALL.reset()

        provider = MemoryMessagingProvider()
        ring = PartitionRing(8)
        b0 = TpuBalancer(provider, ControllerInstanceId("0"),
                         managed_fraction=1.0, blackbox_fraction=0.0,
                         kernel="xla")
        b1 = TpuBalancer(provider, ControllerInstanceId("1"),
                         managed_fraction=1.0, blackbox_fraction=0.0,
                         kernel="xla")
        for b in (b0, b1):
            b.set_partition_mode(ring)
            await b.start()
        for pid in range(8):
            b0.set_partition_leadership(pid, 2, True)
            b1.partition_epochs[pid] = 2  # peer knowledge, not ownership

        # invoker-side spans: the echo stand-in emits one per message
        # (the real InvokerReactive's container span pair rides the same
        # store.active gate)
        def invoker_spans(instance, msgs):
            if not store.active:
                return
            now = time.time()
            for m in msgs:
                tid = trace_id_of(getattr(m, "trace_context", None))
                if tid:
                    store.emit(synthetic_span(
                        tid, "invoker_run", now, now,
                        tags={"proc": f"invoker{instance.instance}"}))

        feeds, stop_fleet = await _echo_fleet(provider, n_invokers,
                                              on_frame=invoker_spans)
        for bal in (b0, b1):
            for _ in range(120):
                health = await bal.invoker_health()
                if sum(h.status == HEALTHY for h in health) >= n_invokers:
                    break
                await asyncio.sleep(0.25)
            else:
                raise RuntimeError("trace assembly rider: fleet unhealthy")

        hot_action = _bench_action("ta_hot", memory=128)

        class _Membership:
            instance = ControllerInstanceId("0")

            @staticmethod
            def least_loaded_peer():
                return 1

        class _Store:
            @staticmethod
            async def get_action(name, rev=None):
                class Doc:
                    @staticmethod
                    def to_executable():
                        return hot_action

                return Doc()

        b0.spillover_sink = SpilloverSender(provider, _Membership())
        receiver = SpilloverReceiver(provider, ControllerInstanceId("1"),
                                     b1, _Store())
        receiver.start()

        actions = [_bench_action(f"ta{i}", memory=128) for i in range(4)]
        ident = Identity.generate("guest")
        sem = asyncio.Semaphore(24)

        async def one(action):
            # the invoke.py driver shape: controller span -> trace
            # context on the message -> waterfall adoption. Everything
            # opens INSIDE the semaphore: a context anchored at burst
            # submit would fold the whole gather's queue wait into the
            # row total and drag the live p99 to the leg duration.
            async with sem:
                transid = TransactionId()
                span = GLOBAL_TRACER.start_span("controller_activation",
                                                transid)
                msg = ActivationMessage(
                    transid, action.fully_qualified_name, action.rev.rev,
                    ident, ActivationId.generate(),
                    ControllerInstanceId("0"), True, {},
                    trace_context=GLOBAL_TRACER.get_trace_context(transid))
                tid = trace_id_of(msg.trace_context)
                GLOBAL_WATERFALL.adopt(msg.activation_id.asString,
                                       GLOBAL_WATERFALL.open(),
                                       trace_id=tid)
                promise = await b0.publish(action, msg)
                GLOBAL_TRACER.finish_span(
                    transid, {"activationId": msg.activation_id.asString,
                              "proc": "controller0"}, span=span)
                await promise
            return tid

        async def settle(target):
            for _ in range(300):
                if store.stats()["seen"] >= target:
                    return
                await asyncio.sleep(0.05)

        out = {}
        try:
            # warmup: the first batches pay kernel compile (seconds) —
            # folded into the live histogram they'd drag the p99 bucket
            # above the straggler salvo. Drive a burst, then zero both
            # planes so the measured legs see steady-state latencies only.
            await asyncio.gather(*[one(actions[i % 4]) for i in range(64)])
            GLOBAL_WATERFALL.reset()
            store.reset()
            # exemplars pinned during warmup reference traces the reset
            # just purged — drop the phase aggregates with them, so the
            # every-rendered-exemplar-resolves gate only sees pins made
            # after the store went clean
            for bal in (b0, b1):
                with bal.profiler._phase_lock:
                    bal.profiler._phases.clear()

            # -- leg 1: the clean bulk keeps at the floor exactly ---------
            clean_tids = await asyncio.gather(
                *[one(actions[i % 4]) for i in range(clean)])
            await settle(clean)
            floor_kept = [t for t in clean_tids
                          if (store.get(t) or {}).get("reason") == "floor"]
            expected = clean // 20
            assert expected // 2 <= len(floor_kept) <= expected + 1, \
                f"floor keeps {len(floor_kept)} vs expected ~{expected}"

            # -- leg 2: stragglers keep 100% with reason `slow` -----------
            # the live threshold is whatever the clean leg's p99 bucket
            # settled at (XLA recompiles for fresh batch geometries can
            # legitimately push it to ~1s): the salvo's injected delay
            # scales to sit clearly above it, like a real straggler does
            threshold = store.tail_threshold_ms()
            assert threshold < 2500.0, \
                f"tail threshold {threshold}ms never settled"
            delay_s = min(3.0, threshold / 1000.0 * 1.5 + 0.1)
            for f in feeds:
                f.delay = delay_s
            straggler_tids = await asyncio.gather(
                *[one(actions[0]) for _ in range(stragglers_n)])
            for f in feeds:
                f.delay = 0.0
            await settle(clean + stragglers_n)
            slow_kept = [t for t in straggler_tids
                         if "slow" in (store.get(t) or {}).get("reasons",
                                                               ())]
            straggler_keep_pct = 100.0 * len(slow_kept) / stragglers_n
            assert straggler_keep_pct == 100.0, \
                f"straggler keep {straggler_keep_pct}%"

            # -- leg 3: spillover -> >= 3-process assembled tree ----------
            i = 0
            while ring.partition_of(f"sp{i}") != 4:
                i += 1
            spill_ident = Identity.generate(f"sp{i}")
            depth_was = b0.spillover_depth
            b0.spillover_depth = 2
            pairs, spill_tids = [], []
            for _ in range(8):
                transid = TransactionId()
                span = GLOBAL_TRACER.start_span("controller_activation",
                                                transid)
                msg = ActivationMessage(
                    transid, hot_action.fully_qualified_name,
                    hot_action.rev.rev, spill_ident,
                    ActivationId.generate(), ControllerInstanceId("0"),
                    False, {},
                    trace_context=GLOBAL_TRACER.get_trace_context(transid))
                GLOBAL_WATERFALL.adopt(
                    msg.activation_id.asString, GLOBAL_WATERFALL.open(),
                    trace_id=trace_id_of(msg.trace_context))
                GLOBAL_TRACER.finish_span(
                    transid, {"activationId": msg.activation_id.asString,
                              "proc": "controller0"}, span=span)
                spill_tids.append(trace_id_of(msg.trace_context))
                pairs.append((hot_action, msg))
            outs = b0.publish_many(pairs)
            await asyncio.gather(*outs)
            b0.spillover_depth = depth_was

            # both halves of a spilled trace land in the SAME ring here
            # (one process, one global store) — scan entries() for them,
            # the way two processes' /admin/trace/local answers would
            def halves_of():
                by_tid = {}
                for e in store.entries():
                    by_tid.setdefault(e.get("trace_id"), []).append(e)
                return by_tid

            kept_spilled = []
            for _ in range(300):
                by_tid = halves_of()
                kept_spilled = [
                    t for t in spill_tids
                    if any("spilled" in e["reasons"]
                           for e in by_tid.get(t, ()))]
                if b0.spilled_rows and len(kept_spilled) >= b0.spilled_rows:
                    break
                await asyncio.sleep(0.05)
            assert b0.spilled_rows >= 1, "no rows spilled past the depth"
            assert len(kept_spilled) >= b0.spilled_rows, \
                f"{b0.spilled_rows} spilled, {len(kept_spilled)} kept"

            await asyncio.sleep(0.5)  # let the peer halves complete too
            by_tid = halves_of()
            assembled, stage_sum, wf_total = None, None, None
            for t in kept_spilled:
                halves = by_tid.get(t, [])
                rows = [e["waterfall"] for e in halves
                        if e.get("waterfall")]
                if not rows:
                    continue
                a = assemble_trace(t, halves)
                if len(a["processes"]) < 3 or len(halves) < 2:
                    continue
                # telescoping: each half's present deltas sum back to its
                # own measured total (each delta floors to µs
                # independently, so the bound is one µs per stage)
                ok = all(abs(sum(d for d in r["deltas_us"] if d >= 0)
                             - r["total_us"]) <= N_STAGES for r in rows)
                assert ok, f"stage deltas do not telescope for {t}"
                wf_total = max(r["total_us"] for r in rows)
                stage_sum = sum(d for r in rows
                                for d in r["deltas_us"] if d >= 0)
                assembled = a
                break
            assert assembled is not None, \
                "no spilled trace assembled to >= 3 processes (2 halves)"

            # -- leg 4a: every rendered OM exemplar resolves --------------
            ex_tids = set()
            for bal in (b0, b1):
                text = bal.profiler.prometheus_text(openmetrics=True)
                ex_tids.update(re.findall(r'trace_id="([0-9a-f]+)"', text))
            if b0.profiler.enabled:
                assert ex_tids, "profiler on but no exemplars rendered"
            unresolved = [t for t in ex_tids if store.get(t) is None]
            assert not unresolved, \
                f"{len(unresolved)} rendered exemplars not kept"

            # -- leg 4b: dead-peer assembly over real HTTP ----------------
            async def noop_factory(invoker_id, prov):
                class _S:
                    async def stop(self):
                        pass

                return _S()

            logger = NullLogging()
            cprov = MemoryMessagingProvider()
            lb = LeanBalancer(cprov, ControllerInstanceId("0"),
                              noop_factory, logger=logger,
                              metrics=logger.metrics, user_memory=MB(512))
            ctl = Controller(ControllerInstanceId("0"), cprov,
                             logger=logger, load_balancer=lb)
            admin = Identity.generate("guest")
            await ctl.auth_store.put(WhiskAuthRecord(
                admin.subject, [admin.namespace], [admin.authkey]))

            async def peer_local(request):
                # a live peer that never kept the trace: found=false,
                # which must NOT read as a missing member
                return aioweb.json_response(
                    {"trace_id": request.match_info["trace_id"],
                     "found": False, "entry": None})

            papp = aioweb.Application()
            papp.router.add_get("/admin/trace/local/{trace_id}",
                                peer_local)
            prunner = aioweb.AppRunner(papp)
            await prunner.setup()
            await aioweb.TCPSite(prunner, "127.0.0.1", PEER_PORT).start()

            class _FleetStub:
                def peer_directory(self):
                    return {1: f"http://127.0.0.1:{PEER_PORT}",
                            2: "http://127.0.0.1:9"}  # dead peer

                async def stop(self):
                    pass

            await ctl.start(port=CTL_PORT)
            ctl.membership = _FleetStub()
            hdrs = {"Authorization": "Basic " + base64.b64encode(
                admin.authkey.compact.encode()).decode()}
            target = assembled["trace_id"]
            try:
                base = f"http://127.0.0.1:{CTL_PORT}"
                async with aiohttp.ClientSession() as s:
                    async with s.get(f"{base}/admin/trace/{target}",
                                     headers=hdrs) as r:
                        http_status = r.status
                        http_body = await r.json()
                    async with s.get(f"{base}/admin/traces?reason=slow",
                                     headers=hdrs) as r:
                        list_status = r.status
                        list_body = await r.json()
            finally:
                await prunner.cleanup()
                await ctl.stop()
            assert http_status == 200, f"assembly answered {http_status}"
            assert http_body["found"] is True
            assert http_body["members_missing"] == [2], \
                f"members_missing {http_body.get('members_missing')}"
            assert list_status == 200 and len(list_body["traces"]) \
                >= stragglers_n

            stats = store.stats()
            out = {
                "clean": clean,
                "keep_floor": 0.05,
                "floor_kept": len(floor_kept),
                "floor_expected": expected,
                "tail_threshold_ms": round(threshold, 3),
                "straggler_delay_s": round(delay_s, 3),
                "straggler_keep_pct": round(straggler_keep_pct, 1),
                "spilled_rows": int(b0.spilled_rows),
                "spilled_kept": len(kept_spilled),
                "assembled_processes": assembled["processes"],
                "stage_sum_us": int(stage_sum),
                "waterfall_total_us": int(wf_total),
                "dead_peer_status": http_status,
                "members_missing": http_body["members_missing"],
                "exemplars_rendered": len(ex_tids),
                "exemplars_resolved": True,
                "kept_total": stats["kept_total"],
                "dropped_total": stats["dropped_total"],
            }
        finally:
            await stop_fleet()
            await receiver.stop()
            await b0.close()
            await b1.close()
            for f in feeds:
                await f.stop()
            store.detach()
            store.enabled = was_enabled
            store.config = was_cfg
            store._floor_every = was_floor
            store.reset()
            GLOBAL_WATERFALL.enabled = wf_was
            GLOBAL_WATERFALL.reset()
        return out

    try:
        return asyncio.run(go())
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# trace_assembly failed: {e!r}", file=sys.stderr)
        return None


def _trace_plane_overhead(repeats: int = 20, total: int = 2000,
                          concurrency: int = 64) -> Optional[dict]:
    """ISSUE 18 gate: the armed trace observatory's marginal cost on the
    traced blocking-publish path, <= 5% by acceptance. Same paired-segment
    protocol as `_fleet_observatory_overhead` (fixture built ONCE,
    armed/disarmed segments back-to-back, order flipped per repeat,
    20%-trimmed mean over the pairs): the driver makes real spans + trace
    contexts + waterfall adoptions in BOTH arms (that cost is the tracing
    spine's, paid since PR 2), so the pair isolates exactly what this PR
    added — the reporter tee, the completion-time verdict, and the floor
    keeps' serialization."""
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.core.entity import (ActivationId, ControllerInstanceId,
                                           Identity)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         MemoryMessagingProvider)
    from openwhisk_tpu.utils.tracestore import GLOBAL_TRACE_STORE
    from openwhisk_tpu.utils.tracing import GLOBAL_TRACER, trace_id_of
    from openwhisk_tpu.utils.transaction import TransactionId
    from openwhisk_tpu.utils.waterfall import GLOBAL_WATERFALL

    store = GLOBAL_TRACE_STORE

    async def go() -> dict:
        was_enabled = store.enabled
        wf_was = GLOBAL_WATERFALL.enabled
        GLOBAL_WATERFALL.enabled = True
        GLOBAL_WATERFALL.reset()
        store.enabled = True
        store.reset()
        store.attach()
        provider = MemoryMessagingProvider()
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          kernel="xla")
        await bal.start()
        feeds, stop_fleet = await _echo_fleet(provider, 16)
        from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
        for _ in range(120):
            health = await bal.invoker_health()
            if sum(h.status == HEALTHY for h in health) >= 16:
                break
            await asyncio.sleep(0.25)
        else:
            raise RuntimeError("trace plane rider: fleet unhealthy")

        actions = [_bench_action(f"tp{i}", memory=128) for i in range(8)]
        ident = Identity.generate("guest")
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            action = actions[i % len(actions)]
            transid = TransactionId()
            span = GLOBAL_TRACER.start_span("controller_activation",
                                            transid)
            msg = ActivationMessage(
                transid, action.fully_qualified_name, action.rev.rev,
                ident, ActivationId.generate(), ControllerInstanceId("0"),
                True, {},
                trace_context=GLOBAL_TRACER.get_trace_context(transid))
            GLOBAL_WATERFALL.adopt(msg.activation_id.asString,
                                   GLOBAL_WATERFALL.open(),
                                   trace_id=trace_id_of(msg.trace_context))
            async with sem:
                promise = await bal.publish(action, msg)
                GLOBAL_TRACER.finish_span(
                    transid, {"proc": "controller0"}, span=span)
                await promise

        async def segment() -> float:
            t0 = time.perf_counter()
            await asyncio.gather(*[one(i) for i in range(total)])
            return total / (time.perf_counter() - t0)

        try:
            await segment()  # warmup: compile + settle
            pairs = []
            on_rates, off_rates = [], []
            for k in range(repeats):
                order = (True, False) if k % 2 == 0 else (False, True)
                rate = {}
                for armed in order:
                    if armed:
                        store.enabled = True
                        store.reset()
                        store.attach()
                    else:
                        store.detach()
                        store.enabled = False
                    rate[armed] = await segment()
                on_rates.append(rate[True])
                off_rates.append(rate[False])
                pairs.append(100.0 * (rate[False] - rate[True])
                             / rate[False])
        finally:
            await stop_fleet()
            await bal.close()
            for f in feeds:
                await f.stop()
            store.detach()
            store.enabled = was_enabled
            store.reset()
            GLOBAL_WATERFALL.enabled = wf_was
            GLOBAL_WATERFALL.reset()
        trim = max(1, len(pairs) // 5)
        kept = sorted(pairs)[trim:-trim] if len(pairs) > 2 * trim else pairs
        return {
            "rate_trace_plane_on": round(max(on_rates), 1),
            "rate_trace_plane_off": round(max(off_rates), 1),
            "overhead_pct": round(statistics.mean(kept), 2),
            "target_pct": 5.0,
            "pair_overheads_pct": [round(p, 2) for p in pairs],
            "repeats": repeats,
            "agg": "trimmed_mean_paired_segments",
        }

    try:
        return asyncio.run(go())
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# trace_plane_overhead failed: {e!r}", file=sys.stderr)
        return None


def _incident_capture(clean: int = 240, straggler_salvo: int = 64,
                      n_invokers: int = 8) -> Optional[dict]:
    """ISSUE 19 acceptance: an injected straggler (the loadgen
    `--stragglers` helper) drives the straggler alert to firing against a
    journaled balancer with the incident recorder armed, and the FIRING
    transition must auto-freeze exactly ONE forensic bundle (debounce)
    joining >= 5 planes. Four legs in one fixture:

      capture      straggler alert fires -> one bundle on disk with the
                   alert context, anomaly score matrix, waterfall, >= 1
                   kept trace and the journal window, written off-loop;
      debounce     a second straggler invoker's own FIRING transition
                   inside the window coalesces into the same bundle;
      time-travel  the bundle's journal window replays through
                   JournalDebugger: break-on-activation-id stops at the
                   placing batch, run_to_end re-derives the books with 0
                   parity mismatches, diff_books matches the captured
                   books bit-exact;
      fleet        GET /admin/fleet/incidents through a real Controller
                   with a live + a dead peer answers 200 with member
                   provenance and members_missing (never a 500).
    """
    import base64
    import tempfile

    import aiohttp
    from aiohttp import web as aioweb

    from openwhisk_tpu.controller.core import Controller
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
    from openwhisk_tpu.controller.loadbalancer.journal import PlacementJournal
    from openwhisk_tpu.controller.loadbalancer.lean import LeanBalancer
    from openwhisk_tpu.controller.loadbalancer.timetravel import \
        JournalDebugger
    from openwhisk_tpu.core.entity import (MB, ActivationId,
                                           ControllerInstanceId, Identity,
                                           WhiskAuthRecord)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         MemoryMessagingProvider)
    from openwhisk_tpu.utils.blackbox import GLOBAL_INCIDENTS, read_bundle
    from openwhisk_tpu.utils.logging import NullLogging
    from openwhisk_tpu.utils.tracestore import GLOBAL_TRACE_STORE
    from openwhisk_tpu.utils.tracing import GLOBAL_TRACER, trace_id_of
    from openwhisk_tpu.utils.transaction import TransactionId
    from openwhisk_tpu.utils.waterfall import GLOBAL_WATERFALL
    from tools.loadgen import apply_stragglers

    store = GLOBAL_TRACE_STORE
    CTL_PORT, PEER_PORT = 13983, 13984
    inc_dir = tempfile.mkdtemp(prefix="bench-incidents-")
    jdir = tempfile.mkdtemp(prefix="bench-incidents-wal-")
    # the recorder + a fast-firing straggler rule are armed via env
    # BEFORE the balancer exists (plane wiring reads config at
    # construction); everything is restored in the finally
    env_overrides = {
        "CONFIG_whisk_incidents_enabled": "true",
        "CONFIG_whisk_incidents_directory": inc_dir,
        # one incident -> ONE bundle across the whole rider (camelCase:
        # the env parser splits on _, so debounce_s would nest wrong)
        "CONFIG_whisk_incidents_debounceS": "600",
        # the built-in straggler rule holds for 30 s before firing — an
        # operator tightening it for a drill is exactly this override
        "CONFIG_whisk_alerts_rules":
            '{"straggler": {"threshold": 2.0, "for_s": 0}}',
    }
    env_was = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)

    async def go() -> dict:
        was_enabled, was_floor = store.enabled, store._floor_every
        wf_was = GLOBAL_WATERFALL.enabled
        store.enabled = True
        store._floor_every = 20
        store.reset()
        store.attach()
        GLOBAL_WATERFALL.enabled = True
        GLOBAL_WATERFALL.reset()

        provider = MemoryMessagingProvider()
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          kernel="xla")
        assert GLOBAL_INCIDENTS.stats()["installed"], \
            "recorder must arm at balancer construction"
        bal.attach_journal(PlacementJournal(jdir))
        await bal.start()
        feeds, stop_fleet = await _echo_fleet(provider, n_invokers)
        for _ in range(120):
            health = await bal.invoker_health()
            if sum(h.status == HEALTHY for h in health) >= n_invokers:
                break
            await asyncio.sleep(0.25)
        else:
            raise RuntimeError("incident rider: fleet unhealthy")

        actions = [_bench_action(f"ic{i}", memory=128) for i in range(4)]
        ident = Identity.generate("guest")
        sem = asyncio.Semaphore(32)

        async def one(i):
            # the traced invoke.py driver shape, so completions feed the
            # tail sampler and the bundle gets real kept traces
            async with sem:
                action = actions[i % len(actions)]
                transid = TransactionId()
                span = GLOBAL_TRACER.start_span("controller_activation",
                                                transid)
                msg = ActivationMessage(
                    transid, action.fully_qualified_name, action.rev.rev,
                    ident, ActivationId.generate(),
                    ControllerInstanceId("0"), True, {},
                    trace_context=GLOBAL_TRACER.get_trace_context(transid))
                GLOBAL_WATERFALL.adopt(
                    msg.activation_id.asString, GLOBAL_WATERFALL.open(),
                    trace_id=trace_id_of(msg.trace_context))
                promise = await bal.publish(action, msg)
                GLOBAL_TRACER.finish_span(
                    transid, {"activationId": msg.activation_id.asString,
                              "proc": "controller0"}, span=span)
                await promise

        out = {}
        try:
            # -- leg 1: drive to firing, capture one bundle ---------------
            # clean bulk first: per-invoker latency estimates must be warm
            # (min_samples) before a straggler can z-score against them
            await asyncio.gather(*[one(i) for i in range(clean)])
            # two delayed invokers: each (rule, invoker) instance fires on
            # its own -> the SECOND transition proves the debounce
            applied = apply_stragglers(feeds, {0: 0.6, 1: 0.6})
            assert len(applied) == 2
            salvo = 0
            for _ in range(20):  # keep driving until the alert lands
                await asyncio.gather(*[one(i) for i in range(
                    straggler_salvo)])
                salvo += straggler_salvo
                if GLOBAL_INCIDENTS.stats()["captured"] >= 1:
                    break
            apply_stragglers(feeds, {0: 0.0, 1: 0.0})
            for _ in range(200):  # the capture worker writes off-loop
                st = GLOBAL_INCIDENTS.stats()
                if st["captured"] >= 1 and st["bundles"] >= 1:
                    break
                await asyncio.sleep(0.1)
            stats = GLOBAL_INCIDENTS.stats()
            assert stats["captured"] >= 1, f"no capture: {stats}"
            # let any queued coalesced triggers settle, then the debounce
            # verdict: ONE bundle, everything else folded into it
            await asyncio.sleep(1.0)
            bundles = sorted(
                n for n in os.listdir(inc_dir) if n.endswith(".wbb"))
            assert len(bundles) == 1, f"debounce leak: {bundles}"
            bundle_path = os.path.join(inc_dir, bundles[0])
            payload = read_bundle(bundle_path)
            assert payload is not None, "bundle unreadable"
            assert payload["reason"].startswith("alert:straggler"), payload[
                "reason"]
            planes = {k: v for k, v in payload["planes"].items()
                      if v is not None}
            assert len(planes) >= 5, f"planes: {sorted(planes)}"
            for need in ("alerts", "anomaly_scores", "waterfall",
                         "traces", "journal", "books"):
                assert need in planes, f"missing plane {need}"
            assert planes["traces"], "no kept trace overlapped the window"
            recs = planes["journal"]["records"]
            assert recs, "journal window empty"

            # -- leg 2: time-travel replay of the bundle's window ---------
            batch_aids = [a for r in recs if r.get("t") == "batch"
                          for a in (r.get("aids") or [])]
            assert batch_aids, "no batch records in the window"
            dbg = JournalDebugger.from_bundle(payload)
            try:
                stop = dbg.run_to_activation(batch_aids[0])
                assert stop is not None, "break-on-activation-id missed"
                assert batch_aids[0] in stop["aids"]
                replay_stats = dbg.run_to_end()
                diff = dbg.diff_books()
            finally:
                await dbg.aclose()
            assert replay_stats["parity_mismatches"] == 0, replay_stats
            assert diff["match"], diff

            # -- leg 3: federated serving with a dead peer ----------------
            async def noop_factory(invoker_id, prov):
                class _S:
                    async def stop(self):
                        pass

                return _S()

            logger = NullLogging()
            cprov = MemoryMessagingProvider()
            lb = LeanBalancer(cprov, ControllerInstanceId("0"),
                              noop_factory, logger=logger,
                              metrics=logger.metrics, user_memory=MB(512))
            ctl = Controller(ControllerInstanceId("0"), cprov,
                             logger=logger, load_balancer=lb)
            admin = Identity.generate("guest")
            await ctl.auth_store.put(WhiskAuthRecord(
                admin.subject, [admin.namespace], [admin.authkey]))

            async def peer_incidents(request):
                return aioweb.json_response(
                    {"incidents": [{"id": "inc-peer-0001", "ts": 1.0,
                                    "reason": "alert:straggler"}],
                     "stats": {}})

            papp = aioweb.Application()
            papp.router.add_get("/admin/incidents", peer_incidents)
            prunner = aioweb.AppRunner(papp)
            await prunner.setup()
            await aioweb.TCPSite(prunner, "127.0.0.1", PEER_PORT).start()

            class _FleetStub:
                def peer_directory(self):
                    return {1: f"http://127.0.0.1:{PEER_PORT}",
                            2: "http://127.0.0.1:9"}  # dead peer

                async def stop(self):
                    pass

            await ctl.start(port=CTL_PORT)
            ctl.membership = _FleetStub()
            hdrs = {"Authorization": "Basic " + base64.b64encode(
                admin.authkey.compact.encode()).decode()}
            try:
                base = f"http://127.0.0.1:{CTL_PORT}"
                async with aiohttp.ClientSession() as s:
                    async with s.get(f"{base}/admin/fleet/incidents",
                                     headers=hdrs) as r:
                        fleet_status = r.status
                        fleet_body = await r.json()
                    async with s.get(
                            f"{base}/admin/incident/{payload['id']}",
                            headers=hdrs) as r:
                        get_status = r.status
                        get_body = await r.json()
            finally:
                await prunner.cleanup()
                await ctl.stop()
            assert fleet_status == 200, f"fleet answered {fleet_status}"
            members = {row["member"] for row in fleet_body["incidents"]}
            assert 0 in members and 1 in members, members
            assert fleet_body["members_missing"] == [2], fleet_body
            assert get_status == 200 and get_body["member"] == "local"

            out = {
                "straggler_invokers": 2,
                "straggler_delay_s": 0.6,
                "salvo_activations": salvo,
                "trigger_reason": payload["reason"],
                "bundles_written": len(bundles),
                "coalesced": stats["coalesced"],
                "planes_captured": len(planes),
                "planes": sorted(planes),
                "plane_errors": payload["plane_errors"],
                "journal_window": [planes["journal"]["from_seq"],
                                   planes["journal"]["to_seq"]],
                "journal_records": len(recs),
                "break_aid_found": True,
                "replay_parity_mismatches":
                    replay_stats["parity_mismatches"],
                "replay_books_match": diff["match"],
                "fleet_status": fleet_status,
                "fleet_members": sorted(members),
                "members_missing": fleet_body["members_missing"],
            }
        finally:
            await stop_fleet()
            await bal.close()
            for f in feeds:
                await f.stop()
            store.detach()
            store.enabled = was_enabled
            store._floor_every = was_floor
            store.reset()
            GLOBAL_WATERFALL.enabled = wf_was
            GLOBAL_WATERFALL.reset()
        return out

    try:
        return asyncio.run(go())
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# incident_capture failed: {e!r}", file=sys.stderr)
        return None
    finally:
        for k, v in env_was.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _incident_overhead(repeats: int = 20, total: int = 1000,
                       concurrency: int = 64) -> Optional[dict]:
    """ISSUE 19 gate: the ARMED-but-idle incident recorder's marginal
    cost on the blocking-publish path, <= 5% by acceptance (expected ~0:
    arming costs one forced EventLog bool plus an alert-transition
    listener that a healthy run never invokes — nothing per placement).
    Same paired-segment protocol as `_fleet_observatory_overhead`
    (fixture built ONCE, armed/disarmed segments back-to-back, order
    flipped per repeat, 20%-trimmed mean over the pairs); install/
    uninstall runs BETWEEN segments so thread start/join never lands in
    a measured window."""
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.core.entity import (ActivationId, ControllerInstanceId,
                                           Identity)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         MemoryMessagingProvider)
    from openwhisk_tpu.utils.blackbox import GLOBAL_INCIDENTS
    from openwhisk_tpu.utils.transaction import TransactionId

    import tempfile
    inc_dir = tempfile.mkdtemp(prefix="bench-incover-")
    env_overrides = {
        "CONFIG_whisk_incidents_enabled": "true",
        "CONFIG_whisk_incidents_directory": inc_dir,
    }
    env_was = {k: os.environ.get(k) for k in env_overrides}

    async def go() -> dict:
        provider = MemoryMessagingProvider()
        # env not yet flipped: the balancer must NOT auto-own the
        # recorder — the rider arms/disarms it per segment
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          kernel="xla")
        os.environ.update(env_overrides)
        await bal.start()
        feeds, stop_fleet = await _echo_fleet(provider, 16)
        from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
        for _ in range(120):
            health = await bal.invoker_health()
            if sum(h.status == HEALTHY for h in health) >= 16:
                break
            await asyncio.sleep(0.25)
        else:
            raise RuntimeError("incident overhead rider: fleet unhealthy")

        actions = [_bench_action(f"io{i}", memory=128) for i in range(8)]
        ident = Identity.generate("guest")
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            action = actions[i % len(actions)]
            msg = ActivationMessage(
                TransactionId(), action.fully_qualified_name, action.rev.rev,
                ident, ActivationId.generate(), ControllerInstanceId("0"),
                True, {})
            async with sem:
                promise = await bal.publish(action, msg)
                await promise

        async def segment() -> float:
            t0 = time.perf_counter()
            await asyncio.gather(*[one(i) for i in range(total)])
            return total / (time.perf_counter() - t0)

        token = object()
        try:
            await segment()  # warmup: compile + settle
            pairs = []
            on_rates, off_rates = [], []
            for k in range(repeats):
                order = (True, False) if k % 2 == 0 else (False, True)
                rate = {}
                for armed in order:
                    if armed:
                        assert GLOBAL_INCIDENTS.install(balancer=bal,
                                                        owner=token)
                    else:
                        GLOBAL_INCIDENTS.uninstall(owner=token)
                    rate[armed] = await segment()
                GLOBAL_INCIDENTS.uninstall(owner=token)
                on_rates.append(rate[True])
                off_rates.append(rate[False])
                pairs.append(100.0 * (rate[False] - rate[True])
                             / rate[False])
        finally:
            GLOBAL_INCIDENTS.uninstall(owner=token)
            await stop_fleet()
            await bal.close()
            for f in feeds:
                await f.stop()
        trim = max(1, len(pairs) // 5)
        kept = sorted(pairs)[trim:-trim] if len(pairs) > 2 * trim else pairs
        return {
            "rate_incidents_on": round(max(on_rates), 1),
            "rate_incidents_off": round(max(off_rates), 1),
            "overhead_pct": round(statistics.mean(kept), 2),
            "target_pct": 5.0,
            "pair_overheads_pct": [round(p, 2) for p in pairs],
            "repeats": repeats,
            "agg": "trimmed_mean_paired_segments",
        }

    try:
        return asyncio.run(go())
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# incident_overhead failed: {e!r}", file=sys.stderr)
        return None
    finally:
        for k, v in env_was.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _placement_quality(total: int = 400, concurrency: int = 32,
                       n_invokers: int = 8,
                       stragglers: str = "3:0.25") -> Optional[dict]:
    """ISSUE 17 A/B: the placement-quality plane under a straggler.

    Two arms over the same workload shape, fresh fixture each (EWMAs
    must not leak between arms): `straggler` injects ack delay on one
    invoker via the shared PR 4 helper (tools/loadgen.apply_stragglers),
    so the anomaly plane flags it and the shadow counterfactual runs the
    penalty-demoted probe geometry; `clean` runs the identical drive
    with no injection, where the penalty vector stays zero and the
    shadow MUST be bit-identical to production (divergent_rows == 0 is
    the end-to-end restatement of the parity property the tier-1 fuzz
    asserts). The pair is the plane's payoff evidence: regret +
    divergence with the shadow penalty effectively on vs off."""
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
    from openwhisk_tpu.controller.loadbalancer.quality import (QualityConfig,
                                                               QualityPlane)
    from openwhisk_tpu.core.entity import (ActivationId, ControllerInstanceId,
                                           Identity)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         MemoryMessagingProvider)
    from openwhisk_tpu.utils.transaction import TransactionId
    from tools.loadgen import apply_stragglers

    async def arm(spec) -> dict:
        provider = MemoryMessagingProvider()
        qp = QualityPlane(QualityConfig(enabled=True, shadow_every_n=4))
        # prewarm off: background compiles are pure GIL contention inside
        # the measured window (the PR-5 lesson, same as the anomaly e2e)
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          kernel="xla", quality=qp, prewarm=False)
        await bal.start()
        feeds, stop_fleet = await _echo_fleet(provider, n_invokers)
        for _ in range(120):
            health = await bal.invoker_health()
            if sum(h.status == HEALTHY for h in health) >= n_invokers:
                break
            await asyncio.sleep(0.25)
        else:
            raise RuntimeError("placement quality rider: fleet unhealthy")
        applied = apply_stragglers(feeds, spec)

        actions = [_bench_action(f"pq{i}", memory=128) for i in range(8)]
        ident = Identity.generate("guest")
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            action = actions[i % len(actions)]
            msg = ActivationMessage(
                TransactionId(), action.fully_qualified_name, action.rev.rev,
                ident, ActivationId.generate(), ControllerInstanceId("0"),
                True, {})
            async with sem:
                promise = await bal.publish(action, msg)
                await promise

        try:
            # warmup compiles (production + shadow + scorer shapes)
            await asyncio.gather(*[one(i) for i in range(min(64, total))])
            # drive in rounds with supervision ticks between them: the
            # anomaly detector harvests one tick late, and the straggler
            # flags become the shadow penalty only on the NEXT refresh
            rounds = 5
            per = max(1, total // rounds)
            for _ in range(rounds):
                await asyncio.gather(*[one(i) for i in range(per)])
                bal._telemetry_tick()
                await asyncio.sleep(0.1)
            # two settle ticks + one more driven round so shadow batches
            # actually run WITH the refreshed penalty in effect
            for _ in range(2):
                bal._telemetry_tick()
                await asyncio.sleep(0.1)
            await asyncio.gather(*[one(i) for i in range(per)])
            report = await asyncio.to_thread(
                qp.quality_report, bal._telemetry_invoker_names())
        finally:
            await stop_fleet()
            await bal.close()
            for f in feeds:
                await f.stop()
        return {
            "stragglers": {str(k): v for k, v in applied.items()},
            "penalized_invokers": int((bal._shadow_penalty_np > 0).sum()),
            "regret_sum_ms": report.get("regret_sum_ms"),
            "regret_p99_le_ms": report.get("regret_p99_le_ms"),
            "fleet_imbalance_cov": report.get("fleet_imbalance_cov"),
            "shadow_batches": report.get("shadow_batches"),
            "shadow_rows": report.get("shadow_rows"),
            "divergent_rows": report.get("divergent_rows"),
            "divergence_ratio": report.get("divergence_ratio"),
            "counters": report.get("counters"),
            "per_invoker": report.get("invokers"),
        }

    try:
        with_straggler = asyncio.run(arm(stragglers))
        clean = asyncio.run(arm(None))
        return {
            "straggler": with_straggler,
            "clean": clean,
            # the pair's headline: how differently the penalized geometry
            # places under a real straggler vs the zero-penalty identity
            "shadow_divergence_ratio": with_straggler["divergence_ratio"],
            "clean_divergent_rows": clean["divergent_rows"],
        }
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# placement_quality failed: {e!r}", file=sys.stderr)
        return None


def _placement_quality_overhead(repeats: int = 20, total: int = 1000,
                                concurrency: int = 64) -> Optional[dict]:
    """ISSUE 17 gate (<= 5%): the quality plane's marginal cost through
    the full balancer path — the per-batch scorer dispatch plus one
    shadow pass every N batches. Same paired-segment protocol as
    `_fleet_observatory_overhead` (fixture ONCE, armed/disarmed segments
    back-to-back, order flipped per repeat, 20%-trimmed mean of paired
    ratios): the effect is small and between-run host jitter is 4x, so
    only a paired design measures it. The disarmed half parks the shadow
    fn and flips `enabled`, which is exactly what the off-switch does on
    the dispatch path — production decisions are bit-exact either way
    (tier-1-asserted), so the pair measures pure observability tax."""
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
    from openwhisk_tpu.controller.loadbalancer.quality import (QualityConfig,
                                                               QualityPlane)
    from openwhisk_tpu.core.entity import (ActivationId, ControllerInstanceId,
                                           Identity)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         MemoryMessagingProvider)
    from openwhisk_tpu.utils.transaction import TransactionId

    async def go() -> dict:
        provider = MemoryMessagingProvider()
        qp = QualityPlane(QualityConfig(enabled=True, shadow_every_n=16))
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          kernel="xla", quality=qp)
        await bal.start()
        feeds, stop_fleet = await _echo_fleet(provider, 16)
        for _ in range(120):
            health = await bal.invoker_health()
            if sum(h.status == HEALTHY for h in health) >= 16:
                break
            await asyncio.sleep(0.25)
        else:
            raise RuntimeError("placement quality overhead: fleet unhealthy")

        actions = [_bench_action(f"pqo{i}", memory=128) for i in range(8)]
        ident = Identity.generate("guest")
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            action = actions[i % len(actions)]
            msg = ActivationMessage(
                TransactionId(), action.fully_qualified_name, action.rev.rev,
                ident, ActivationId.generate(), ControllerInstanceId("0"),
                True, {})
            async with sem:
                promise = await bal.publish(action, msg)
                await promise

        async def segment() -> float:
            t0 = time.perf_counter()
            await asyncio.gather(*[one(i) for i in range(total)])
            return total / (time.perf_counter() - t0)

        shadow_fn = bal._shadow_fn

        def set_armed(armed: bool) -> None:
            # the off-switch's dispatch-path effect, minus a rebuild:
            # enabled=False skips the scorer, a parked shadow fn skips
            # the counterfactual
            qp.enabled = armed
            bal._shadow_fn = shadow_fn if armed else None

        try:
            await segment()  # warmup: production + shadow + scorer compiles
            pairs = []
            on_rates, off_rates = [], []
            for k in range(repeats):
                order = (True, False) if k % 2 == 0 else (False, True)
                rate = {}
                for armed in order:
                    set_armed(armed)
                    rate[armed] = await segment()
                set_armed(True)
                on_rates.append(rate[True])
                off_rates.append(rate[False])
                pairs.append(100.0 * (rate[False] - rate[True])
                             / rate[False])
        finally:
            await stop_fleet()
            await bal.close()
            for f in feeds:
                await f.stop()
        trim = max(1, len(pairs) // 5)
        kept = sorted(pairs)[trim:-trim] if len(pairs) > 2 * trim else pairs
        return {
            "rate_placement_quality_on": round(max(on_rates), 1),
            "rate_placement_quality_off": round(max(off_rates), 1),
            "overhead_pct": round(statistics.mean(kept), 2),
            "pair_overheads_pct": [round(p, 2) for p in pairs],
            "repeats": repeats,
            "shadow_every_n": qp.shadow_every_n,
            "agg": "trimmed_mean_paired_segments",
        }

    try:
        return asyncio.run(go())
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# placement_quality_overhead failed: {e!r}", file=sys.stderr)
        return None


def _e2e_open_loop_measure(rate0: float = 32.0, duration: float = 2.5,
                           max_doublings: int = 9) -> Optional[dict]:
    """The in-process body of the e2e_open_loop rider (run it in a fresh
    subprocess via _e2e_open_loop — see _subprocess_json for why)."""
    from tools.loadgen import sweep_balancer
    return sweep_balancer(rate0=rate0, duration=duration,
                          max_doublings=max_doublings)


def _latest_bench_round() -> Optional[tuple]:
    """(filename, unwrapped round dict) of the newest BENCH_*.json beside
    this script, or None. "Newest" is the name sort — the driver numbers
    rounds r01, r02, ... monotonically."""
    import glob
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    rounds = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
    if not rounds:
        return None
    path = rounds[-1]
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    from tools.bench_compare import unwrap_round
    return os.path.basename(path), unwrap_round(doc)


def _compared_to(rider_key: str, new_block: dict,
                 latest: Optional[tuple] = None) -> Optional[dict]:
    """The `compared_to` satellite (ISSUE 12): diff one rider's fresh
    block against the same rider in the newest prior BENCH_*.json via
    tools/bench_compare's headline rules. ADVISORY by contract — the
    block reports regressions, it never fails the rider (the judgment
    tool for a round stays the bench_compare CLI). `latest` lets a
    caller that already loaded the baseline pass it in (one read, one
    consistent baseline)."""
    try:
        if latest is None:
            latest = _latest_bench_round()
        if latest is None:
            return None
        fname, old_round = latest
        old_block = old_round.get(rider_key)
        if not isinstance(old_block, dict):
            return {"baseline": fname, "skipped": f"no {rider_key} block "
                    "in the baseline round"}
        from tools.bench_compare import compare
        out = compare({rider_key: old_block}, {rider_key: new_block})
        headlines = [r for r in out["headlines"]
                     if not r["verdict"].startswith("skipped")]
        return {
            "baseline": fname,
            "advisory": True,
            "headlines": headlines,
            "regressions": out["regressions"],
        }
    except Exception as e:  # noqa: BLE001 — advisory must stay advisory
        print(f"# compared_to({rider_key}) failed: {e!r}", file=sys.stderr)
        return None


def _e2e_fleet_mesh_measure(rate0: float = 32.0, duration: float = 2.0,
                            max_doublings: int = 6) -> Optional[dict]:
    """The fleet-mesh comparison point (ROADMAP item 2c): the SAME
    coordinated-omission-correct open-loop sweep, against a balancer in
    fleet-mesh mode (invoker state sharded over the ('fleet',) mesh).
    Runs in a CPU-pinned 8-virtual-device subprocess — the honest
    virtual mesh, same posture as the sharded_fleet_sweep rider; a clean
    DEVICE round of this row stays on the ROADMAP item 2 list."""
    from tools.loadgen import sweep_balancer
    row = sweep_balancer(rate0=rate0, duration=duration,
                         max_doublings=max_doublings, fleet_mesh=True)
    keep = {k: row.get(k) for k in (
        "sustained", "sustained_activations_per_sec",
        "sustained_offered_rate", "p50_ms", "p99_ms", "fleet_shards",
        "gc_tuned")}
    keep["mode"] = "open_loop"
    keep["fleet_mesh"] = True
    return keep


def _e2e_multiproc_measure(rate: float = 128.0, procs: int = 2,
                           duration: float = 1.5) -> Optional[dict]:
    """The --procs fleet-merged point (ISSUE 16): N front-end worker
    generators at rate/N each funneling one shared balancer process, the
    parent reaping ONE fleet-merged host snapshot (raw
    integer bucket counts merged bucket-wise, the federation's own merge
    math) instead of N per-worker blobs. The kept headline is
    fleet_merged_sustained_per_sec — gated in tools/bench_compare.py."""
    from tools.loadgen import multiproc_fixed_rate
    row = multiproc_fixed_rate(rate=rate, procs=procs, duration=duration,
                               host_observatory=True)
    keep = {k: row.get(k) for k in (
        "mode", "procs", "sustained", "sustained_activations_per_sec",
        "fleet_merged_sustained_per_sec", "offered_rate", "p50_ms",
        "p99_ms")}
    hf = row.get("host_fleet") or {}
    keep["host_fleet_members"] = hf.get("members")
    keep["host_fleet_lag_p99_le_ms"] = (hf.get("loop_lag")
                                        or {}).get("p99_le_ms")
    return keep


def _funnel_10k_measure(duration: float = 2.0) -> Optional[dict]:
    """ISSUE 20 rider body: the SHARED multi-process deployment — N
    front-end worker processes funneling one device-owning balancer
    process over the TCP bus — swept over front-end process count at
    4k/8k/12k offered/s. Each point is a merged-schedule verdict
    (topology "shared": one balancer really placed every row, so the
    merged rate IS the system number). The funnel's depth bound surfaces
    as 429s at the front door, which the per-worker verdicts count as
    errors — an over-driven point fails honestly instead of queueing
    unboundedly. The 12k rung doubles as the recorded 10k/s attempt,
    sustained or not."""
    import os
    from tools.loadgen import multiproc_fixed_rate
    cpus = os.cpu_count() or 1
    # front-end process ladder: 2 always (the minimum real multi-process
    # point, timesliced honestly on a small box), 4 when the box has the
    # cores to give each front end one
    proc_ladder = [2] if cpus < 6 else [2, 4]
    rates = (4096.0, 8192.0, 12288.0)
    points = []
    best = None
    attempt_10k = None
    for procs in proc_ladder:
        skip_rest = False
        for rate in rates:
            if skip_rest and not (rate >= 10000.0 and attempt_10k is None):
                continue
            row = multiproc_fixed_rate(rate=rate, procs=procs,
                                       duration=duration)
            point = {k: row.get(k) for k in (
                "topology", "procs", "offered_rate", "sustained",
                "sustained_activations_per_sec",
                "fleet_merged_sustained_per_sec", "completed", "p50_ms",
                "p99_ms")}
            point["worker_verdicts"] = [
                {"worker": w.get("worker"),
                 "sustained": w.get("sustained"),
                 "blames": w.get("blames"),
                 "error": w.get("error"),
                 "failed": (w.get("verdict") or {}).get("failed")}
                for w in row.get("per_worker") or []]
            points.append(point)
            if rate >= 10000.0:
                attempt_10k = point
            if point["sustained"]:
                if (best is None or
                        (point["fleet_merged_sustained_per_sec"] or 0) >
                        (best["fleet_merged_sustained_per_sec"] or 0)):
                    best = point
            else:
                # higher rates at this proc count fail harder — skip
                # them, EXCEPT the >=10k rung runs once regardless so
                # the 10k/s attempt is on the record either way
                skip_rest = True
    # headline honesty: a sustained point's merged rate, else the best
    # observed merged rate explicitly flagged unsustained
    if best is not None:
        head, sustained = best, True
    else:
        head = max(points,
                   key=lambda p: p["fleet_merged_sustained_per_sec"] or 0)
        sustained = False
    return {
        "mode": "funnel_10k",
        "topology": "shared",
        "single_process_baseline_per_sec": 4043.0,
        "funnel_sustained_per_sec": head["fleet_merged_sustained_per_sec"],
        "funnel_frontend_procs": head["procs"],
        "sustained": sustained,
        "offered_rates_swept": list(rates),
        "frontend_proc_ladder": proc_ladder,
        "cpus": cpus,
        "attempt_10k": attempt_10k,
        "points": points,
    }


def _funnel_10k() -> Optional[dict]:
    """The ISSUE 20 rider: real multi-process 10k/s attempt through the
    front-end->balancer admission funnel. Pure control-plane/host work —
    always CPU-pinned (and tagged so), like the host-path rows."""
    out = _cpu_subprocess_json("bench._funnel_10k_measure()", "RIDERJSON",
                               "funnel_10k", force_devices=True)
    if out is not None:
        out["backend"] = "cpu"
        cmp_block = _compared_to("funnel_10k", out)
        if cmp_block is not None:
            out["compared_to"] = cmp_block
    return out


def _e2e_open_loop(rate0: float = 32.0, duration: float = 2.5,
                   max_doublings: int = 9) -> Optional[dict]:
    """The ISSUE 7 headline rider: open-loop offered-rate sweep against the
    live balancer path (tools/loadgen.py) — max sustainable activations/s
    with e2e p50/p99 measured from SCHEDULED arrival time (coordinated-
    omission-correct, unlike the closed-loop `balancer` rows) plus the
    waterfall's per-stage budget saying where the per-activation time
    goes. Acceptance: the stage medians sum to ~the e2e median (no
    unaccounted gap) and the budget names the stage to attack next.
    Runs in a fresh backend-inheriting subprocess that owns the device
    (the caller has not initialized JAX yet); a child that fails returns
    None — there is no CPU re-run. The `compared_to` block (ISSUE 12)
    diffs this run against the newest prior BENCH_*.json round —
    advisory, never fails the rider."""
    expr = (f"bench._e2e_open_loop_measure({rate0}, {duration}, "
            f"{max_doublings})")
    out = _subprocess_json(expr, "RIDERJSON", "e2e_open_loop")
    if out is not None:
        # fleet-mesh comparison row (ROADMAP item 2c): same open-loop
        # judge, sharded balancer, 8-way virtual CPU mesh (tagged cpu —
        # never mistakable for a device number)
        mesh = _cpu_subprocess_json("bench._e2e_fleet_mesh_measure()",
                                    "RIDERJSON", "e2e fleet-mesh point",
                                    force_devices=True)
        if mesh is not None:
            mesh["backend"] = "cpu"
            out["fleet_mesh_point"] = mesh
        # --procs fleet-merged point (ISSUE 16): the parent reaps ONE
        # merged snapshot across its worker generators; headline gates
        # as fleet_merged_sustained_per_sec in bench_compare
        mp = _cpu_subprocess_json("bench._e2e_multiproc_measure()",
                                  "RIDERJSON", "e2e multiproc point")
        if mp is not None:
            mp["backend"] = "cpu"
            out["multiproc_point"] = mp
        cmp_block = _compared_to("e2e_open_loop", out)
        if cmp_block is not None:
            out["compared_to"] = cmp_block
    return out


def _bus_coalesce_speedup(n_messages: int = 2048, wave: int = 64,
                          e2e_rates: tuple = (256.0, 512.0),
                          e2e_duration: float = 2.0) -> Optional[dict]:
    """ISSUE 8 rider, two halves:

    1. BUS MICRO: `n_messages` concurrent produces over a live TCP bus
       (waves of `wave`, the shape of a readback fan-out), serial
       per-message `pub` vs the CoalescingProducer's `pubN` frames —
       msgs/s both ways and the speedup.
    2. E2E SCOREBOARD: fixed-rate open-loop runs with the ISSUE 8 knobs ON
       (defaults) vs OFF at each of `e2e_rates` — the waterfall's
       `produce` stage p50/p99 and the generator throughput side by side.
       256/s is the PR 6 baseline's sustained rate (both paths sustain:
       the produce p99 comparison is apples to apples); 512/s is past the
       serial ceiling (the coalesced path holds throughput and the serial
       produce stage absorbs the backlog)."""
    from openwhisk_tpu.messaging.coalesce import CoalescingProducer
    from openwhisk_tpu.messaging.tcp import TcpBusServer, TcpMessagingProvider

    async def _produce_half(coalesced: bool) -> float:
        server = TcpBusServer("127.0.0.1", 0)
        await server.start()
        port = server._server.sockets[0].getsockname()[1]
        provider = TcpMessagingProvider("127.0.0.1", port)
        # bound broker-side retention so the un-consumed backlog stays small
        server.bus.topic("t").set_retention_bytes(128 * 1024)
        producer = provider.get_producer()
        if coalesced:
            producer = CoalescingProducer(producer, max_batch=wave,
                                          window_ms=0.0)
        payload = b"x" * 256
        t0 = time.monotonic()
        for _ in range(n_messages // wave):
            await asyncio.gather(*[producer.send("t", payload)
                                   for _ in range(wave)])
        rate = n_messages / (time.monotonic() - t0)
        await producer.close()
        await server.stop()
        return rate

    try:
        serial = asyncio.run(_produce_half(False))
        coalesced = asyncio.run(_produce_half(True))
        e2e = []
        for rate in e2e_rates:
            # one fresh subprocess per point: a sweep leaves dead jit
            # executables and GC pressure behind, and a later in-process
            # run inherits stalls that read as saturation (measured)
            on = _cpu_subprocess_json(
                f"bench._bus_e2e_point(True, {rate}, {e2e_duration})",
                "RIDERJSON", f"bus e2e knobs-on @{rate}")
            off = _cpu_subprocess_json(
                f"bench._bus_e2e_point(False, {rate}, {e2e_duration})",
                "RIDERJSON", f"bus e2e knobs-off @{rate}")
            if on is None or off is None:
                continue
            row = {"rate": rate, "knobs_on": on, "knobs_off": off}
            if on["produce_p99_ms"] and off["produce_p99_ms"]:
                row["produce_p99_ratio_off_over_on"] = round(
                    off["produce_p99_ms"] / on["produce_p99_ms"], 2)
            e2e.append(row)
        return {
            "n_messages": n_messages,
            "wave": wave,
            "serial_msgs_per_sec": round(serial, 1),
            "coalesced_msgs_per_sec": round(coalesced, 1),
            "speedup": round(coalesced / serial, 2) if serial else None,
            "e2e": e2e,
        }
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# bus_coalesce_speedup failed: {e!r}", file=sys.stderr)
        return None


def _host_obs_point(enabled: bool, rate: float, duration: float) -> dict:
    """One fixed-rate open-loop measurement with the host hot-loop
    observatory ON or OFF (run in a fresh CPU-pinned subprocess via
    _cpu_subprocess_json: the observatory knobs are env-driven and its
    planes are process-global, so each half must own its process). ON
    attaches the observatory snapshot — loop lag, GC shares, serde shares,
    self-time census — as `host`."""
    import os
    v = "true" if enabled else "false"
    os.environ["CONFIG_whisk_hostProfiling_enabled"] = v
    from tools.loadgen import sweep_balancer
    row = sweep_balancer(fixed_rate=rate, duration=duration,
                         host_observatory=enabled)
    out = {
        # CPU-twin by construction (CPU-pinned subprocess): say so, per
        # the "never mistake a CPU number for a device number" rule
        "backend": "cpu",
        "offered_rate": rate,
        "sustained": row.get("sustained"),
        "activations_per_sec": row.get("sustained_activations_per_sec"),
        "p50_ms": row.get("p50_ms"),
        "p99_ms": row.get("p99_ms"),
        "completed": (row.get("headline") or {}).get("completed"),
    }
    if enabled:
        out["host"] = row.get("host")
    return out


def _host_profiling_overhead(rate: float = 1024.0, duration: float = 2.5,
                             repeats: int = 2) -> Optional[dict]:
    """ISSUE 11 gate: ALL FOUR host-observatory planes (lag probe, gc
    callbacks, task-factory interposer + serde accounting, sampler) must
    cost <= 5% at the PR 7 open-loop sustained rate (~1000/s on the CPU
    twin). Unlike the closed-loop plane riders, this one measures at the
    open-loop saturation edge — where added per-activation host work shows
    up as lost completions, not hidden queueing."""
    try:
        on_rates, off_rates = [], []
        p99_on, p99_off = [], []
        for _ in range(repeats):
            on = _cpu_subprocess_json(
                f"bench._host_obs_point(True, {rate}, {duration})",
                "RIDERJSON", "host profiling on")
            off = _cpu_subprocess_json(
                f"bench._host_obs_point(False, {rate}, {duration})",
                "RIDERJSON", "host profiling off")
            if on and off and on.get("activations_per_sec") \
                    and off.get("activations_per_sec"):
                on_rates.append(on["activations_per_sec"])
                off_rates.append(off["activations_per_sec"])
                if on.get("p99_ms") is not None:
                    p99_on.append(on["p99_ms"])
                if off.get("p99_ms") is not None:
                    p99_off.append(off["p99_ms"])
        if not on_rates:
            return None
        on_med = statistics.median(on_rates)
        off_med = statistics.median(off_rates)
        return {
            "rate_host_profiling_on": round(on_med, 1),
            "rate_host_profiling_off": round(off_med, 1),
            "overhead_pct": (round(100.0 * (off_med - on_med) / off_med, 2)
                             if off_med else None),
            # medians like the rates: one repeat's GC spike must not read
            # as the observatory's latency cost
            "p99_on_ms": statistics.median(p99_on) if p99_on else None,
            "p99_off_ms": statistics.median(p99_off) if p99_off else None,
            "offered_rate": rate,
            "mode": "open_loop",
            "repeats": len(on_rates),
        }
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# host_profiling_overhead failed: {e!r}", file=sys.stderr)
        return None


def _host_observatory(rate: float = 4096.0, duration: float = 3.0
                      ) -> Optional[dict]:
    """ISSUE 11 payoff rider: the open-loop generator at the columnar
    hot path's sustained offered rate (ISSUE 12: 4096 offered / ~3.3k
    sustained on the 1-core twin, up from PR 7's 1024) with the
    observatory ON — one JSON block with loop-lag p50/p99, the GC pause
    share, per-hop serde shares, the top-5 self-time frames, and the
    `stage_shares` table the ROADMAP "no dominant host stage" claim is
    judged against (compared_to diffs the prior round's table in)."""
    try:
        point = _cpu_subprocess_json(
            f"bench._host_obs_point(True, {rate}, {duration})",
            "RIDERJSON", "host_observatory")
        if point is None:
            return None
        host = point.get("host") or {}
        lag = host.get("loop_lag") or {}
        gc_block = host.get("gc") or {}
        sampler = host.get("sampler") or {}
        top = (sampler.get("top") or [])[:5]
        serde_share = {
            f"{row['hop']}/{row['direction']}": row["share_pct"]
            for row in (host.get("serde") or [])}
        tasks = host.get("tasks") or {}
        completed = point.get("completed") or 0
        # the ISSUE 12 stage-share table: the per-plane shares the
        # "no dominant host stage" ROADMAP claim is judged against —
        # recorded as a measured artifact next to the headline, with the
        # prior round's table diffed in via compared_to below
        worst_serde = max(serde_share.values(), default=0.0)
        gc_share = gc_block.get("pause_share_pct") or 0.0
        stage_shares = {
            "serde_worst_hop_pct": worst_serde,
            "serde_by_hop_pct": serde_share,
            "gc_pause_pct": gc_share,
            "loop_lag_p50_ms": lag.get("p50_ms"),
            "loop_lag_p99_ms": lag.get("p99_ms"),
            "tasks_per_activation": (round(tasks.get("created", 0)
                                           / completed, 2)
                                     if completed else None),
            "no_plane_above_25pct": bool(worst_serde <= 25.0
                                         and gc_share <= 25.0),
        }
        out = {
            "backend": "cpu",
            "offered_rate": rate,
            "sustained": point.get("sustained"),
            "sustained_activations_per_sec": point.get(
                "activations_per_sec"),
            "e2e_p99_ms": point.get("p99_ms"),
            "loop_lag_p50_ms": lag.get("p50_ms"),
            "loop_lag_p99_ms": lag.get("p99_ms"),
            "loop_lag_max_ms": lag.get("max_ms"),
            "gc_pause_share_pct": gc_block.get("pause_share_pct"),
            "gc_pauses_in_dispatch": gc_block.get("overlapping_dispatch"),
            "serde_share_pct": serde_share,
            "stage_shares": stage_shares,
            "top_self_time": top,
            "distinct_hot_frames": len(sampler.get("top") or []),
            "worst_stalls": (host.get("stalls") or {}).get("worst", [])[:5],
            "tasks": host.get("tasks"),
        }
        # before/after: the prior round's stage-share table beside this
        # one (advisory, like the e2e compared_to) — ONE baseline read
        # shared with the headline diff, so both halves describe the
        # same round
        latest = _latest_bench_round()
        cmp_block = _compared_to("host_observatory", out, latest=latest)
        if cmp_block is not None:
            if latest is not None:
                prior = (latest[1].get("host_observatory") or {})
                cmp_block["before_stage_shares"] = (
                    prior.get("stage_shares")
                    or {"serde_by_hop_pct": prior.get("serde_share_pct"),
                        "gc_pause_pct": prior.get("gc_pause_share_pct"),
                        "loop_lag_p99_ms": prior.get("loop_lag_p99_ms")})
            out["compared_to"] = cmp_block
        return out
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# host_observatory failed: {e!r}", file=sys.stderr)
        return None


def _bus_e2e_point(knobs_on: bool, rate: float, duration: float) -> dict:
    """One fixed-rate open-loop measurement for the bus_coalesce_speedup
    scoreboard (run in a fresh subprocess via _cpu_subprocess_json — the
    ISSUE 8 knobs are env-driven, read at balancer/producer construction,
    so setting them here before the sweep builds its target is enough).
    The toggle covers bus coalescing ONLY:
    loadgen enters at balancer.publish, so the admission plane is not on
    this measured path (it is exercised by the HTTP burst drive in the
    verify recipe and tests/test_admission.py instead)."""
    import os
    # set BOTH branches explicitly: a knobs-off env inherited from the
    # operator's shell would otherwise silently turn the on-vs-off
    # scoreboard into serial-vs-serial
    os.environ["CONFIG_whisk_bus_coalesce_enabled"] = (
        "true" if knobs_on else "false")
    from tools.loadgen import sweep_balancer
    row = sweep_balancer(fixed_rate=rate, duration=duration)
    budget = row.get("stage_budget") or {}
    return {
        # this scoreboard is CPU-twin by construction (CPU-pinned
        # subprocess): say so, per the "never mistake a CPU number for a
        # device number" rule
        "backend": "cpu",
        "offered_rate": rate,
        "sustained": row.get("sustained"),
        "activations_per_sec": row.get("sustained_activations_per_sec"),
        "e2e_p99_ms": row.get("p99_ms"),
        "produce_p50_ms": (budget.get("stage_medians_ms") or {}
                           ).get("produce"),
        "produce_p99_ms": (budget.get("p99_decomposition_ms") or {}
                           ).get("produce"),
    }


def _rider_batch(n_invokers: int, b: int, seed: int = 23):
    """`_example_batch` with the ACTION POOL scaled to the batch: the
    headline protocol (B=256 over 64 actions) holds the per-action burst
    at 4, so the repair_vs_scan sweep keeps that ratio as B grows — B
    sweeps batch WIDTH, not convoy depth. (The convoy shape — many
    requests of one action, deliberately overflowing invokers in a
    sequential chain — is measured separately as the `convoy` row: it is
    the repair kernel's worst case and the reason the `auto` knob
    exists.)"""
    import jax.numpy as jnp

    from openwhisk_tpu.models.sharding_policy import (generate_hash,
                                                      pairwise_coprimes)
    from openwhisk_tpu.ops.placement import RequestBatch

    n_actions = max(1, b // 4)
    rng = np.random.RandomState(seed)
    managed = max(int(0.9 * n_invokers), 1)
    steps = pairwise_coprimes(managed)
    cols = {k: np.zeros((b,), np.int32) for k in
            ("offset", "size", "home", "step_inv", "need_mb", "conc_slot",
             "max_conc", "rand")}
    for i in range(b):
        # EXACT bursts of b/n_actions consecutive requests per action —
        # how a real arrival burst convoys through the FIFO queue (random
        # draws would Poisson-spread the bursts: a 6-request 512 MB action
        # self-overflows its home invoker, turning the row into a chain
        # benchmark — that shape is the `convoy` row's job)
        a = i * n_actions // b
        h = generate_hash(f"ns{a % 8}", f"action{a}")
        step = steps[h % len(steps)]
        cols["offset"][i] = 0
        cols["size"][i] = managed
        cols["home"][i] = h % managed
        cols["step_inv"][i] = pow(step, -1, managed) if managed > 1 else 0
        cols["need_mb"][i] = [128, 256, 512][a % 3]
        cols["conc_slot"][i] = a % 256
        cols["max_conc"][i] = 1
        cols["rand"][i] = rng.randint(0, managed)
    return RequestBatch(*(jnp.asarray(cols[k]) for k in
                          ("offset", "size", "home", "step_inv", "need_mb",
                           "conc_slot", "max_conc", "rand")),
                        valid=jnp.ones((b,), bool))


def _repair_parity_rounds(batch_size: int, n_invokers: int = 1024,
                          action_slots: int = 256, steps: int = 4,
                          batch=None, kernel: str = "repair") -> tuple:
    """Chained-step parity of a repair pair (`kernel`: "repair" or
    "pallas_repair") against the scan oracle over the SAME batch (each
    step releases the prior step's placements, so later steps run on books
    the earlier ones dirtied) + the per-step repair-round counts. Returns
    (parity_ok, rounds)."""
    import jax.numpy as jnp

    from __graft_entry__ import _example_batch
    from openwhisk_tpu.ops.placement import init_state

    batch = batch if batch is not None else _example_batch(
        n_invokers, batch_size, seed=17)
    hidx = jnp.zeros((8,), jnp.int32)
    hval = jnp.zeros((8,), bool)
    hmask = jnp.zeros((8,), bool)
    outs, rounds = {}, []
    for k in ("xla", kernel):
        state = init_state(n_invokers, [2048] * n_invokers,
                           action_slots=action_slots)
        fused = _build_fused(k)
        rel_inv = jnp.zeros((batch_size,), jnp.int32)
        rel_ok = jnp.zeros((batch_size,), bool)
        acc = []
        for _ in range(steps):
            state, chosen, forced, _warm, r = fused(
                state, rel_inv, batch.conc_slot, batch.need_mb,
                batch.max_conc, rel_ok, hidx, hval, hmask, batch)
            acc.append((np.asarray(chosen), np.asarray(forced)))
            if k != "xla":
                rounds.append(int(r))
            rel_inv, rel_ok = jnp.clip(chosen, 0), chosen >= 0
        outs[k] = (acc, np.asarray(state.free_mb),
                   np.asarray(state.conc_free))
    parity = (
        all(np.array_equal(sc, rc) and np.array_equal(sf, rf)
            for (sc, sf), (rc, rf) in zip(outs["xla"][0], outs[kernel][0]))
        and np.array_equal(outs["xla"][1], outs[kernel][1])
        and np.array_equal(outs["xla"][2], outs[kernel][2]))
    return parity, rounds


def _repair_compile_census(batch_sizes, n_invokers: int = 256) -> dict:
    """The PR-3 watchdog contract over the repair pair's PACKED entry point
    (the same wrapper the balancer dispatches): one compile per (R, H, B)
    bucket signature across repeated calls, zero unexpected recompiles."""
    import jax.numpy as jnp

    from __graft_entry__ import _example_batch
    from openwhisk_tpu.ops.placement import (init_state,
                                             make_fused_step_packed,
                                             release_batch_vector,
                                             schedule_batch_repair)
    from openwhisk_tpu.ops.profiler import (KernelProfiler, ProfilingConfig,
                                            pow2_statics)

    prof = KernelProfiler(ProfilingConfig(enabled=True))
    fn = prof.wrap("repair_step",
                   make_fused_step_packed(release_batch_vector,
                                          schedule_batch_repair),
                   expected=pow2_statics)
    h = 8
    health = np.zeros((3, h), np.int32)
    state = init_state(n_invokers, [2048] * n_invokers, action_slots=64)
    for _ in range(2):
        st = state
        for b in batch_sizes:
            batch = _example_batch(n_invokers, b, seed=19)
            req = np.stack([np.asarray(x, np.int32) for x in
                            (batch.offset, batch.size, batch.home,
                             batch.step_inv, batch.need_mb, batch.conc_slot,
                             batch.max_conc, batch.rand, batch.valid)])
            rel = np.zeros((5, b), np.int32)
            rel[3] = 1
            buf = jnp.asarray(np.concatenate(
                [rel.ravel(), health.ravel(), req.ravel()]))
            st, _ = fn(st, buf, b, h, b)
    census = prof.cache_census()["repair_step"]
    return {"compiles": census["compiles"],
            "signatures": census["signatures"],
            "calls": census["calls"],
            "recompiles_unexpected": prof.compiles_unexpected}


def _repair_vs_scan(batch_sizes=(64, 256, 1024), n_invokers: int = 1024,
                    repeats: int = 3, iters: int = 12) -> Optional[dict]:
    """The PR-5/PR-10 tentpole rider: speculate-and-repair vs the reference
    scan at the kernel level, per batch size — median steady-state rates
    through the SAME fused-step protocol as the headline number (action
    pool scaled with B, see _rider_batch), chained-step parity against the
    scan oracle, repair-round stats, and the packed entry point's compile
    census (speculation must not reintroduce shape churn). Each row also
    carries the FUSED PALLAS repair kernel (`pallas_repair_*`): on real
    TPU hardware that is the production candidate (acceptance: >= the XLA
    repair rate); on the CPU twin it is interpret mode — tagged
    `pallas_backend: "interpret"` and EXCLUDED from any headline reading,
    parity still asserted. A `convoy` row measures the documented worst
    case — the largest B over the headline's FIXED 64-action pool, i.e.
    deep same-action overflow chains — where the scan is expected to win.
    Acceptance: repair >= scan at
    B=64 and >= 2x at B=1024, parity true (pallas included),
    recompiles_unexpected == 0."""
    try:
        import jax
        on_cpu = jax.default_backend() == "cpu"
        rows = {}
        parity_all = True

        def measure(tag, b, n, batch, reps, its):
            nonlocal parity_all
            scan = _bench_kernel("xla", n, 256, reps, its, batch=batch)
            repair = _bench_kernel("repair", n, 256, reps, its, batch=batch)
            parity, rounds = _repair_parity_rounds(b, n, batch=batch)
            parity_all = parity_all and parity
            rows[tag] = {
                "batch": b,
                "n_invokers": n,
                "scan_rate_median": scan["rate_median"],
                "repair_rate_median": repair["rate_median"],
                "speedup": round(
                    repair["rate_median"] / scan["rate_median"], 2)
                if scan["rate_median"] else None,
                "scan_p50_step_ms": scan["p50_step_ms"],
                "repair_p50_step_ms": repair["p50_step_ms"],
                "repair_rounds_mean": round(sum(rounds) / len(rounds), 2),
                "repair_rounds_max": max(rounds),
                "parity": parity,
            }
            # the fused pallas repair kernel rides every row; interpret
            # mode (CPU twin) gets one fast-ish repeat — the number is
            # tagged and never a headline, the PARITY is the contract
            from openwhisk_tpu.ops.placement_pallas import fits_vmem_repair
            if fits_vmem_repair(_next_pow2_local(n), 256, b):
                p_reps, p_its = (1, max(2, its // 4)) if on_cpu else (reps,
                                                                      its)
                pall = _bench_kernel("pallas_repair", n, 256, p_reps, p_its,
                                     batch=batch)
                p_parity, p_rounds = _repair_parity_rounds(
                    b, n, batch=batch, kernel="pallas_repair")
                parity_all = parity_all and p_parity
                rows[tag].update({
                    "pallas_repair_rate_median": pall["rate_median"],
                    "pallas_repair_p50_step_ms": pall["p50_step_ms"],
                    "pallas_repair_rounds_max": max(p_rounds),
                    "pallas_parity": p_parity,
                    "pallas_vs_xla_repair": round(
                        pall["rate_median"] / repair["rate_median"], 2)
                    if repair["rate_median"] else None,
                })

        def _next_pow2_local(n):
            p = 1
            while p < n:
                p *= 2
            return p

        for b in batch_sizes:
            # fleet >> batch is the shape the kernel targets (and the
            # production shape: the north star is 65536 invokers) — hold
            # fleet/batch >= 4 as B grows, reported per row
            n = max(n_invokers, 4 * b)
            iters_b = max(4, min(iters, (256 * iters) // b))
            measure(f"b{b}", b, n, _rider_batch(n, b), repeats, iters_b)
        from __graft_entry__ import _example_batch
        b_max = max(batch_sizes)
        n_max = max(n_invokers, 4 * b_max)
        measure("convoy", b_max, n_max,
                _example_batch(n_max, b_max, seed=7), 1, 3)
        return {"rows": rows, "parity": parity_all,
                "repeats": repeats,
                "pallas_backend": "interpret" if on_cpu else "device",
                "protocol": "per-action burst held at 4 (the headline "
                            "protocol's B=256/64-action ratio) with "
                            "fleet/batch >= 4; the convoy row is the "
                            "fixed-64-action worst case where deep "
                            "same-action overflow chains serialize the "
                            "repair loop (the scan is expected to win it); "
                            "pallas_repair_* numbers on the CPU twin are "
                            "interpret mode and excluded from headlines",
                "compile_census": _repair_compile_census(batch_sizes)}
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# repair_vs_scan failed: {e!r}", file=sys.stderr)
        return None


def _fleet_sweep_row(mesh, fleet: int, batch_size: int, iters: int,
                     repeats: int, action_slots: int = 64) -> dict:
    """One fleet size of the sharded_fleet_sweep: steady-state rate of the
    SHARDED fused step (fleet repair pair over the mesh, previous step's
    placements released each step — the _bench_kernel protocol), exact
    parity vs the SINGLE-DEVICE repair kernel on the same chained steps
    (decisions, forced bits, books, round counts), the packed entry
    point's compile census (one compile per bucket signature, zero
    unexpected — the balancer's watchdog contract), and the MULTICHIP
    dryrun's heal check folded in (releasing every placement must restore
    full capacity)."""
    import jax
    import jax.numpy as jnp

    from openwhisk_tpu.ops.placement import (init_state,
                                             make_fused_step,
                                             make_fused_step_packed,
                                             release_batch_vector,
                                             schedule_batch_repair,
                                             unpack_step_output)
    from openwhisk_tpu.ops.profiler import (KernelProfiler, ProfilingConfig,
                                            pow2_statics)
    from openwhisk_tpu.parallel.fleet_mesh import (fleet_pair, mesh_shards,
                                                   shard_state)

    n_shards = mesh_shards(mesh)
    batch = _rider_batch(fleet, batch_size, seed=29)
    hidx = jnp.zeros((8,), jnp.int32)
    hval = jnp.zeros((8,), bool)
    hmask = jnp.zeros((8,), bool)
    sched, rel, _ = fleet_pair(mesh, "repair")
    fused_sh = make_fused_step(rel, sched)
    fused_1d = _build_fused("repair")

    def init(shard: bool):
        st = init_state(fleet, [2048] * fleet, n_pad=fleet,
                        action_slots=action_slots)
        return shard_state(st, mesh) if shard else st

    # chained-step parity: sharded vs single-device repair over the same
    # dirtied books (2 steps: speculation + release fold both covered)
    outs = {}
    for tag, fused, shard in (("one", fused_1d, False), ("sh", fused_sh,
                                                         True)):
        st = init(shard)
        rel_inv = jnp.zeros((batch_size,), jnp.int32)
        rel_ok = jnp.zeros((batch_size,), bool)
        acc = []
        for _ in range(2):
            st, chosen, forced, _warm, r = fused(
                st, rel_inv, batch.conc_slot, batch.need_mb,
                batch.max_conc, rel_ok, hidx, hval, hmask, batch)
            acc.append((np.asarray(chosen), np.asarray(forced), int(r)))
            rel_inv, rel_ok = jnp.clip(chosen, 0), chosen >= 0
        outs[tag] = (acc, np.asarray(st.free_mb), np.asarray(st.conc_free))
    parity = (
        all(np.array_equal(a, d) and np.array_equal(b, e) and c == f
            for (a, b, c), (d, e, f) in zip(outs["one"][0], outs["sh"][0]))
        and np.array_equal(outs["one"][1], outs["sh"][1])
        and np.array_equal(outs["one"][2], outs["sh"][2]))
    rounds = [r for _, _, r in outs["sh"][0]]

    # steady-state rate of the sharded step (releases chained like
    # _bench_kernel: books stay constant, the loop runs indefinitely)
    state0 = init(True)
    carry = (state0, jnp.zeros((batch_size,), jnp.int32),
             jnp.zeros((batch_size,), bool))

    def step(carry):
        st, rel_inv, rel_ok = carry
        st, chosen, forced, _warm, _r = fused_sh(
            st, rel_inv, batch.conc_slot, batch.need_mb, batch.max_conc,
            rel_ok, hidx, hval, hmask, batch)
        return (st, jnp.clip(chosen, 0), chosen >= 0), chosen

    for _ in range(2):
        carry, chosen = step(carry)
    jax.block_until_ready(chosen)
    rates = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            carry, chosen = step(carry)
            jax.block_until_ready(chosen)
        rates.append(batch_size * iters / (time.perf_counter() - t0))

    # the MULTICHIP dryrun, folded in: release the final outstanding
    # placements and assert the books heal to full capacity
    st, rel_inv, rel_ok = carry
    st = rel(st, rel_inv, batch.conc_slot, batch.need_mb, batch.max_conc,
             rel_ok)
    heal = int(np.asarray(st.free_mb).sum()) == 2048 * fleet

    # compile census over the PACKED entry point (the wrapper the
    # balancer actually dispatches): repeated calls, one compile per
    # signature, zero unexpected recompiles
    prof = KernelProfiler(ProfilingConfig(enabled=True))
    packed = prof.wrap("fleet_step", make_fused_step_packed(rel, sched),
                       expected=pow2_statics)
    req = np.stack([np.asarray(x, np.int32) for x in
                    (batch.offset, batch.size, batch.home, batch.step_inv,
                     batch.need_mb, batch.conc_slot, batch.max_conc,
                     batch.rand, batch.valid)])
    rel_np = np.zeros((5, batch_size), np.int32)
    rel_np[3] = 1
    health = np.zeros((3, 8), np.int32)
    buf = jnp.asarray(np.concatenate(
        [rel_np.ravel(), health.ravel(), req.ravel()]))
    pstate = init(True)
    out = None
    for _ in range(2):
        pstate, out = packed(pstate, buf, batch_size, 8, batch_size)
    jax.block_until_ready(out)
    rounds_packed = unpack_step_output(np.asarray(out), batch_size).rounds

    med = statistics.median(rates)
    return {
        "fleet": fleet,
        "shard_rows": fleet // n_shards,
        "rate_median": round(med, 1),
        "rate_min": round(min(rates), 1),
        "rate_max": round(max(rates), 1),
        "p50_step_ms": round(batch_size / med * 1e3, 3) if med else None,
        "rounds": rounds,
        "rounds_packed": rounds_packed,
        "parity_vs_single_device": parity,
        "books_heal": heal,
        "recompiles_unexpected": prof.compiles_unexpected,
        "repeats": repeats,
    }


def _sharded_fleet_sweep_measure(fleet_sizes=(1024, 4096, 16384),
                                 n_devices: Optional[int] = None,
                                 batch_size: int = 256,
                                 iters: int = 6, repeats: int = 3) -> dict:
    """In-process body of the sharded_fleet_sweep rider (ROADMAP item 2):
    placement rate of the PRODUCTION fleet-mesh pair per fleet size,
    sweeping 1k upward until the device runs out of memory (the HBM
    limit) or the size list ends. The mesh spans the default backend's
    devices (`n_devices=None`: all of them, pow2-floored); a backend with
    one device has no mesh to sweep and the block says so instead of
    borrowing virtual CPU devices."""
    import jax

    from openwhisk_tpu.parallel.fleet_mesh import (make_fleet_mesh,
                                                   mesh_axis, mesh_shards)

    if n_devices is None and len(jax.devices()) < 2:
        return {"skipped": "the default backend has one device: no mesh",
                "n_devices": 1, "backend": jax.default_backend()}
    mesh = make_fleet_mesh(n_devices)
    out = {
        "n_devices": mesh_shards(mesh),
        "mesh_axis": mesh_axis(mesh),
        "device_platform": mesh.devices.flat[0].platform,
        "backend": jax.default_backend(),
        "batch_size": batch_size,
        "rows": [],
    }
    for fleet in fleet_sizes:
        try:
            out["rows"].append(_fleet_sweep_row(mesh, fleet, batch_size,
                                                iters, repeats))
        except Exception as e:  # noqa: BLE001 — the HBM ceiling is a
            # RESULT, not a failure: record where the sweep stopped
            out["hbm_limit"] = {"stopped_at_fleet": fleet,
                                "error": f"{type(e).__name__}: {e}"[:300]}
            break
    out["parity_all"] = all(r.get("parity_vs_single_device")
                            for r in out["rows"]) if out["rows"] else None
    out["recompiles_unexpected"] = sum(
        r.get("recompiles_unexpected", 0) for r in out["rows"])
    return out


def _sharded_fleet_sweep() -> Optional[dict]:
    """ROADMAP item 2 rider: the sweep in a FRESH device-owning process
    (the caller has not initialized JAX yet) over the default backend's
    devices — no probe, no CPU re-run. Advisory `compared_to` vs the
    newest prior round."""
    out = _subprocess_json("bench._sharded_fleet_sweep_measure()",
                           "FLEETJSON", "sharded fleet sweep")
    if out is not None and "skipped" not in out:
        cmp = _compared_to("sharded_fleet_sweep", out)
        if cmp is not None:
            out["compared_to"] = cmp
    return out


def _failover_downtime(rate: float = 128.0, duration: float = 2.0,
                       n_invokers: int = 8) -> Optional[dict]:
    """ISSUE 9 rider: the HA plane's headline number. Drive an open-loop
    burst at a journaled active balancer, snapshot mid-burst, then
    hard-kill it (journaling stops dead, crash semantics: only what the
    fsync batches made durable survives) and promote a standby:
    snapshot restore + deterministic journal-tail replay + first
    successful placement. Reports the restore-path downtime — failure
    DETECTION is deployment config (membership member_timeout_s, default
    5 s) and is excluded, and said so, rather than baked into a number
    that would just echo the timeout knob."""
    import os
    import tempfile

    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.controller.loadbalancer.checkpoint import \
        write_snapshot
    from openwhisk_tpu.controller.loadbalancer.journal import PlacementJournal
    from openwhisk_tpu.controller.loadbalancer.membership import \
        MEMBER_TIMEOUT_S
    from openwhisk_tpu.core.entity import (ActivationId, ControllerInstanceId,
                                           Identity)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         MemoryMessagingProvider)
    from openwhisk_tpu.utils.transaction import TransactionId
    from tools.loadgen import make_schedule

    async def go() -> dict:
        tmp = tempfile.mkdtemp(prefix="failover-bench-")
        snap_path = os.path.join(tmp, "bal.snap")
        jdir = os.path.join(tmp, "wal")
        provider = MemoryMessagingProvider()
        active = TpuBalancer(provider, ControllerInstanceId("0"),
                             managed_fraction=1.0, blackbox_fraction=0.0,
                             kernel="xla", prewarm=False)
        active.attach_journal(PlacementJournal(jdir))
        await active.start()
        feeds, fleet_stop = await _echo_fleet(provider, n_invokers)
        for _ in range(100):
            if sum(active._healthy) >= n_invokers:
                break
            await asyncio.sleep(0.05)
        actions = [_bench_action(f"fo{i}", memory=128) for i in range(4)]
        ident = Identity.generate("guest")

        def msg_for(a, instance="0"):
            return ActivationMessage(
                TransactionId(), a.fully_qualified_name, a.rev.rev, ident,
                ActivationId.generate(), ControllerInstanceId(instance),
                True, {})

        async def one(bal, i, instance="0"):
            a = actions[i % len(actions)]
            try:
                promise = await bal.publish(a, msg_for(a, instance))
                await promise
                return True
            except Exception:  # noqa: BLE001 — a failed send is a sample
                return False

        # open-loop burst; snapshot at the halfway mark so the journal
        # tail carries real post-snapshot work to replay
        offsets = make_schedule(rate, max(1, int(rate * duration)), seed=5)
        t0 = time.monotonic()
        tasks = []
        snapped = False
        for i, off in enumerate(offsets):
            now = time.monotonic() - t0
            if off > now:
                await asyncio.sleep(off - now)
            if not snapped and off >= duration / 2:
                write_snapshot(active, snap_path)
                snapped = True
            tasks.append(asyncio.ensure_future(one(active, i)))
        if not snapped:
            write_snapshot(active, snap_path)
        done = await asyncio.gather(*tasks)
        snapshot_age_ms = (time.monotonic() - t0 - duration / 2) * 1e3
        # HARD KILL: journaling stops here; anything past the last durable
        # fsync batch is lost, exactly as a SIGKILL would lose it
        await asyncio.sleep(0.05)  # let the tail fsync land (linger_s)
        lag_at_kill = active.journal.lag_batches
        active.journal = None
        t_kill = time.monotonic()

        # standby promotion: restore + replay + first placement
        standby = TpuBalancer(provider, ControllerInstanceId("1"),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              kernel="xla", prewarm=False)
        journal = PlacementJournal(jdir)
        t_r0 = time.monotonic()
        import json as _json
        with open(snap_path) as f:
            snap_doc = _json.load(f)
        standby.restore(snap_doc)
        t_restored = time.monotonic()
        stats = standby.replay_journal(
            journal.records(int(snap_doc.get("journal_seq", 0))),
            from_seq=int(snap_doc.get("journal_seq", 0)))
        t_replayed = time.monotonic()
        standby.set_leadership(2, True)
        await standby.start()
        first_ok = await one(standby, 0, instance="1")
        t_first = time.monotonic()
        await active.close()
        await standby.close()
        await fleet_stop()
        for f in feeds:
            await f.stop()
        journal.close()
        return {
            "downtime_ms": round((t_first - t_kill) * 1e3, 1),
            "restore_ms": round((t_restored - t_r0) * 1e3, 1),
            "replay_ms": round((t_replayed - t_restored) * 1e3, 1),
            "first_placement_ms": round((t_first - t_replayed) * 1e3, 1),
            "replayed_records": stats["replayed"],
            "replayed_batches": stats["batches"],
            "replay_parity_mismatches": stats["parity_mismatches"],
            "journal_lag_at_kill": lag_at_kill,
            "snapshot_age_ms": round(snapshot_age_ms, 1),
            "burst_completed": int(sum(done)),
            "burst_offered": len(offsets),
            "first_standby_placement_ok": bool(first_ok),
            "offered_rate": rate,
            "n_invokers": n_invokers,
            "excludes_detection_window": True,
            "detection_timeout_s_default": MEMBER_TIMEOUT_S,
        }

    try:
        return asyncio.run(go())
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# failover_downtime failed: {e!r}", file=sys.stderr)
        return None


def _partition_chaos(rate: float = 64.0, duration: float = 3.0,
                     n_invokers: int = 8, n_partitions: int = 8
                     ) -> Optional[dict]:
    """ISSUE 15 rider: active/active partitioned control under a kill.
    THREE active journaled controllers share a MemoryMessagingProvider
    bus + a fenced echo fleet; the partition ring spreads 8 namespaces
    over them and an open-loop NO-RETRY burst drives all three through
    an edge-like owner-first router (bounded retry on refusal only —
    exactly the 503-safe retry, so a retry can never double-execute).
    Mid-burst one active is killed (membership silenced, its queue
    dropped, its journal detached mid-flight — crash semantics); the
    survivors must detect the silence, claim its partitions at bumped
    epochs, absorb its journal tail filtered to those partitions, and
    keep serving every namespace. A post-kill ZOMBIE salvo is then
    driven at the dead controller's still-live object: its dispatches
    carry superseded epochs — invokers that already heard the bumped
    epoch for the partition discard them, ones that haven't yet run the
    fresh row once (the per-invoker fence is eventually-consistent;
    fenced + executed must cover the whole salvo). Reports downtime
    (detection excluded and reported separately, as in the PR 8
    failover rider), double-executions (duplicate side effects — must
    be 0), zombie salvo accounting, absorbed-partition rate, journal
    seq integrity (zero lost/duplicated per journal), and the retry
    bound."""
    import os
    import shutil
    import tempfile

    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.controller.loadbalancer.journal import PlacementJournal
    from openwhisk_tpu.controller.loadbalancer.membership import (
        ControllerMembership)
    from openwhisk_tpu.controller.loadbalancer.partitions import PartitionRing
    from openwhisk_tpu.core.entity import (ActivationId, ActivationResponse,
                                           ControllerInstanceId, EntityPath,
                                           Identity, InvokerInstanceId, MB,
                                           WhiskActivation)
    from openwhisk_tpu.messaging import (ActivationMessage,
                                         CombinedCompletionAndResultMessage,
                                         MemoryMessagingProvider, MessageFeed,
                                         PingMessage, maybe_coalesce)
    from openwhisk_tpu.messaging.columnar import is_batch_payload
    from openwhisk_tpu.messaging.connector import (decode_batch,
                                                   decode_message)
    from openwhisk_tpu.utils.transaction import TransactionId
    from tools.loadgen import make_schedule

    ring = PartitionRing(n_partitions)

    def ns_for(pid):
        i = 0
        while ring.partition_of(f"ns{i}") != pid:
            i += 1
        return f"ns{i}"

    async def fenced_echo_fleet(provider, n):
        """Echo invokers honoring the per-partition fence — the invoker
        half of the zero-double-execution contract, mirrored from
        invoker/reactive.py's discard rule."""
        executed: list = []        # (activation id, partition)
        fenced = {"discards": 0}
        feeds, instances = [], []
        producer = maybe_coalesce(provider.get_producer())

        async def start_one(inst):
            topic = inst.as_string
            provider.ensure_topic(topic)
            consumer = provider.get_consumer(topic, topic)
            seen_epochs: dict = {}
            box = {}

            async def handle(payload: bytes):
                if is_batch_payload(payload):
                    _kind, msgs = decode_batch(payload)
                else:
                    msgs = [decode_message(ActivationMessage.parse,
                                           payload, "activation")]
                now = time.time()
                by_topic = {}
                for msg in msgs:
                    if msg.fence_epoch is not None \
                            and msg.fence_part is not None:
                        cur = seen_epochs.get(msg.fence_part, -1)
                        if msg.fence_epoch < cur:
                            fenced["discards"] += 1
                            continue  # zombie epoch: no side effect
                        seen_epochs[msg.fence_part] = msg.fence_epoch
                    executed.append((msg.activation_id.asString,
                                     msg.fence_part))
                    act = WhiskActivation(
                        EntityPath(str(msg.user.namespace.name)),
                        msg.action.name, msg.user.subject,
                        msg.activation_id, now, now,
                        ActivationResponse.success({"ok": True}),
                        duration=1)
                    by_topic.setdefault(
                        f"completed{msg.root_controller_index.as_string}",
                        []).append(CombinedCompletionAndResultMessage(
                            msg.transid, act, inst))
                for topic2, acks in by_topic.items():
                    await producer.send_batch(topic2, acks)
                box["feed"].processed()

            feed = MessageFeed(topic, consumer, 256, handle)
            box["feed"] = feed
            feed.start()
            return feed

        provider.ensure_topic("health")
        ping_producer = provider.get_producer()
        for i in range(n):
            inst = InvokerInstanceId(i, user_memory=MB(8192))
            instances.append(inst)
            feeds.append(await start_one(inst))
            await ping_producer.send("health", PingMessage(inst))
        stop_ping = asyncio.Event()

        async def pinger():
            while not stop_ping.is_set():
                for inst in instances:
                    await ping_producer.send("health", PingMessage(inst))
                try:
                    await asyncio.wait_for(stop_ping.wait(), 1.0)
                except asyncio.TimeoutError:
                    pass

        ping_task = asyncio.ensure_future(pinger())

        async def stop():
            stop_ping.set()
            await ping_task
            for f in feeds:
                await f.stop()

        return executed, fenced, stop

    async def go() -> dict:
        tmp = tempfile.mkdtemp(prefix="partition-chaos-")
        provider = MemoryMessagingProvider()
        # fleet observatory (ISSUE 16): all three in-process controllers
        # record into the shared process-global event log (call sites
        # stamp their own instance=), so the kill->silence->claim->
        # absorb->first-placement timeline reconstructs from ONE mono
        # clock and its phase durations telescope exactly
        from openwhisk_tpu.utils.eventlog import GLOBAL_EVENT_LOG
        event_log_was = GLOBAL_EVENT_LOG.enabled
        GLOBAL_EVENT_LOG.enabled = True
        GLOBAL_EVENT_LOG.reset()
        executed, fenced, fleet_stop = await fenced_echo_fleet(
            provider, n_invokers)

        balancers, memberships, journals = {}, {}, {}
        absorb_stats: list = []

        def wire(i):
            bal = TpuBalancer(provider, ControllerInstanceId(str(i)),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              kernel="xla", prewarm=False, cluster_size=3)
            bal.set_partition_mode(ring)
            journal = PlacementJournal(os.path.join(tmp, f"ctrl{i}"))
            bal.attach_journal(journal)

            def on_partitions(gained, lost, bal=bal, me=i):
                for pid, epoch, *_r in lost:
                    bal.set_partition_leadership(pid, epoch, False)
                by_prev: dict = {}
                for pid, epoch, prev in gained:
                    by_prev.setdefault(prev, []).append((pid, epoch))
                for prev, items in by_prev.items():
                    pids = [p for p, _ in items]
                    if prev is not None:
                        t0 = time.monotonic()
                        st = bal.absorb_partitions(
                            pids, PlacementJournal(
                                os.path.join(tmp, f"ctrl{prev}")))
                        st["absorb_ms"] = round(
                            (time.monotonic() - t0) * 1e3, 1)
                        st["by"] = me
                        absorb_stats.append(st)
                    for pid, epoch in items:
                        bal.set_partition_leadership(pid, epoch, True)

            m = ControllerMembership(
                provider, ControllerInstanceId(str(i)), bal,
                heartbeat_s=0.05, member_timeout_s=0.4, ring=ring,
                on_partitions=on_partitions,
                load_hint=lambda b=bal: float(b.total_active_activations))
            balancers[i], memberships[i], journals[i] = bal, m, journal
            return bal, m

        for i in range(3):
            wire(i)
        for bal in balancers.values():
            await bal.start()
        for m in memberships.values():
            m.start()
        for _ in range(200):
            if sum(len(m.owned_partitions)
                   for m in memberships.values()) == n_partitions \
                    and all(sum(b._healthy) >= n_invokers
                            for b in balancers.values()):
                break
            await asyncio.sleep(0.05)
        else:
            raise RuntimeError("ownership/fleet never converged")

        actions = [_bench_action(f"pc{i}", memory=128) for i in range(4)]
        idents = {pid: Identity.generate(ns_for(pid))
                  for pid in range(n_partitions)}
        dead = set()
        retries = {"refused": 0}
        success_t: dict = {pid: [] for pid in range(n_partitions)}

        def msg_for(a, ident, instance):
            return ActivationMessage(
                TransactionId(), a.fully_qualified_name, a.rev.rev, ident,
                ActivationId.generate(), ControllerInstanceId(instance),
                True, {})

        async def one(i):
            """Edge-emulating driver: owner-first rank order, bounded
            retry on REFUSAL ONLY (the 503-safe class); a timeout or a
            dead upstream mid-flight is a failed sample, never a
            retry."""
            pid = i % n_partitions
            a = actions[i % len(actions)]
            candidates = [c for c in ring.rank(pid, [0, 1, 2])
                          if c not in dead] or [0]
            for attempt, c in enumerate(candidates * 2):
                if c in dead:
                    continue
                bal = balancers[c]
                try:
                    promise = await bal.publish(
                        a, msg_for(a, idents[pid], str(c)))
                except Exception:  # noqa: BLE001 — refusal (standby /
                    # unowned partition): pre-state-change, retry-safe
                    retries["refused"] += 1
                    await asyncio.sleep(0.02 * (attempt + 1))
                    continue
                try:
                    await asyncio.wait_for(promise, 10)
                    success_t[pid].append(time.monotonic())
                    return True
                except Exception:  # noqa: BLE001 — placed-but-lost: the
                    return False   # no-retry rule (could double-execute)
            return False

        offsets = make_schedule(rate, max(1, int(rate * duration)), seed=7)
        kill_at = duration / 3.0
        victim = 0
        t0 = time.monotonic()
        t_kill = None
        tasks = []
        for i, off in enumerate(offsets):
            now = time.monotonic() - t0
            if off > now:
                await asyncio.sleep(off - now)
            if t_kill is None and off >= kill_at:
                # SIGKILL semantics, in-process: membership silenced (no
                # leave), queued-but-undispatched work dropped (futures
                # never resolve), journal detached with its buffered
                # tail lost, and the router sees a dead upstream
                m = memberships[victim]
                await m._ticker.stop()
                await m._feed.stop()
                vb = balancers[victim]
                if vb._flush_task:
                    vb._flush_task.cancel()
                vb._pending.clear()
                vb._req_ring.clear()
                vb.journal = None
                dead.add(victim)
                t_kill = time.monotonic()
                GLOBAL_EVENT_LOG.record("chaos_kill", instance=victim,
                                        parts=sorted(
                                            memberships[victim]
                                            .owned_partitions))
            tasks.append(asyncio.ensure_future(one(i)))
        done = await asyncio.gather(*tasks)

        victim_parts = {pid for pid, o
                        in ring.ownership([0, 1, 2]).items()
                        if o == victim}
        survivors_owned = set()
        t_claimed = None
        for _ in range(400):
            survivors_owned = (memberships[1].owned_partitions
                               | memberships[2].owned_partitions)
            if survivors_owned >= victim_parts:
                t_claimed = time.monotonic()
                break
            await asyncio.sleep(0.02)

        # post-claim service proof per absorbed partition + downtime
        t_post = {}
        for pid in sorted(victim_parts):
            idx = 10_000 + pid
            for _ in range(50):
                if await one(idx):
                    t_post[pid] = time.monotonic()
                    break
                await asyncio.sleep(0.05)

        # zombie salvo: the dead object dispatches with superseded
        # epochs; the fleet fence must discard every one
        zombie_aids = []
        vb = balancers[victim]
        for pid in sorted(victim_parts)[:4]:
            a = actions[0]
            msg = msg_for(a, idents[pid], str(victim))
            zombie_aids.append(msg.activation_id.asString)
            try:
                promise = await vb.publish(a, msg)
                await asyncio.wait_for(promise, 2)
            except Exception:  # noqa: BLE001 — expected: fenced acks
                pass           # never come back
        await asyncio.sleep(0.3)

        executed_ids = [aid for aid, _pid in executed]
        # double executions = the SAME activation's side effect landing
        # twice (duplicate aids). Zombie-salvo rows are FRESH aids the
        # dead controller dispatched at a superseded epoch: an invoker
        # that already heard the new epoch for that partition discards
        # them (fenced), one that hasn't yet runs them ONCE — the
        # per-invoker fence is eventually-consistent by design, and a
        # single execution is not a double. Both outcomes are reported;
        # fenced + executed must account for the whole salvo.
        dup_execs = len(executed_ids) - len(set(executed_ids))
        zombie_execs = sum(1 for aid in zombie_aids
                           if aid in set(executed_ids))

        # journal seq integrity: zero lost / duplicated per journal
        lost_seqs = dup_seqs = 0
        journals_checked = 0
        for i in range(3):
            d = os.path.join(tmp, f"ctrl{i}")
            seqs = [int(r["seq"])
                    for r in PlacementJournal(d).records(0)]
            if not seqs:
                continue
            journals_checked += 1
            dup_seqs += len(seqs) - len(set(seqs))
            lost_seqs += (max(seqs) - min(seqs) + 1) - len(set(seqs))

        detection_s = (round(t_claimed - t_kill, 3)
                       if t_claimed and t_kill else None)
        downtime_s = None
        if t_post and t_claimed:
            downtime_s = round(max(t_post.values()) - t_claimed, 3)

        # reconstructed causal timeline (ISSUE 16): decompose the outage
        # into named phases from the recorded structural events. All
        # marks share one process's monotonic clock, so detect + claim +
        # absorb + first_placement sums to the timeline's own
        # (first_placement - kill) downtime EXACTLY; it is reported
        # beside the service-probe downtime above, which measures with
        # probe-loop granularity.
        from openwhisk_tpu.controller.monitoring import reconstruct_phases
        chaos_events = GLOBAL_EVENT_LOG.recent()
        timeline = reconstruct_phases(chaos_events)
        kill_mono = next((e["mono"] for e in chaos_events
                          if e["kind"] == "chaos_kill"), None)
        timeline["events"] = [
            {"kind": e["kind"], "instance": e.get("instance"),
             "t_s": round(e["mono"] - kill_mono, 4)}
            for e in chaos_events
            if kill_mono is not None and e["mono"] >= kill_mono
            and e["kind"] in ("chaos_kill", "member_silent", "part_claim",
                              "part_ownership", "absorb_start",
                              "absorb_end", "first_placement",
                              "fence_discard")]
        GLOBAL_EVENT_LOG.enabled = event_log_was

        for i, m in memberships.items():
            if i != victim:
                await m.stop()
        for b in balancers.values():
            await b.close()
        await fleet_stop()
        for j in journals.values():
            if j is not None:
                j.close()
        shutil.rmtree(tmp, ignore_errors=True)

        return {
            "downtime_s": downtime_s,
            "detection_s": detection_s,
            "timeline": timeline,
            "double_executions": dup_execs,
            "absorbed_rate": round(
                len(survivors_owned & victim_parts)
                / max(1, len(victim_parts)), 3),
            "victim_partitions": sorted(victim_parts),
            "absorbs": absorb_stats,
            "zombie_salvo": len(zombie_aids),
            "zombie_executions": zombie_execs,
            "zombie_fenced_discards": fenced["discards"],
            "journal_lost_seqs": lost_seqs,
            "journal_duplicated_seqs": dup_seqs,
            "journals_checked": journals_checked,
            "edge_retry_refused": retries["refused"],
            "burst_completed": int(sum(bool(x) for x in done)),
            "burst_offered": len(offsets),
            "offered_rate": rate,
            "n_partitions": n_partitions,
            "n_invokers": n_invokers,
            "excludes_detection_window": True,
        }

    try:
        return asyncio.run(go())
    except Exception as e:  # noqa: BLE001 — rider is auxiliary
        print(f"# partition_chaos failed: {e!r}", file=sys.stderr)
        return None


def _cpu_oracle_rate(n: int = N_INVOKERS, reqs: int = 2048) -> float:
    from openwhisk_tpu.models.sharding_policy import (ShardingPolicyState,
                                                      release, schedule)
    st = ShardingPolicyState.build([2048] * n)
    rng = np.random.RandomState(3)
    actions = [(f"ns{a % 8}", f"action{a}", [128, 256, 512][a % 3])
               for a in range(64)]
    t0 = time.perf_counter()
    placed = []
    for _ in range(reqs):
        ns, act, mem = actions[rng.randint(0, 64)]
        c, _ = schedule(st, ns, act, mem)
        placed.append((c, act, mem))
        if len(placed) >= BATCH:
            for c, act, mem in placed:
                if c is not None:
                    release(st, c, act, mem)
            placed.clear()
    return reqs / (time.perf_counter() - t0)


def _sweep() -> None:
    """xla-vs-pallas rate table across fleet/slot configs (stderr)."""
    from openwhisk_tpu.ops.placement_pallas import fits_vmem
    print("# N_invokers  action_slots  xla/s      pallas/s   winner",
          file=sys.stderr)
    for n in (128, 512, 1024, 4096):
        for a in (64, 256):
            x = _bench_kernel("xla", n, a, repeats=3, iters=20)
            if not fits_vmem(n, a):
                print(f"# {n:<11} {a:<13} {x['rate_median']:<10.0f} "
                      f"{'(>VMEM)':<10} xla", file=sys.stderr)
                continue
            p = _bench_kernel("pallas", n, a, repeats=3, iters=20)
            win = "pallas" if p["rate_median"] > x["rate_median"] else "xla"
            print(f"# {n:<11} {a:<13} {x['rate_median']:<10.0f} "
                  f"{p['rate_median']:<10.0f} {win}", file=sys.stderr)


def _host_info() -> dict:
    """Box identity for the one-line JSON (ISSUE 11 satellite): BENCH_r0*
    rounds land on a noisy shared machine — python/cpu/loadavg make rounds
    comparable (a 4x loadavg delta explains a slow round better than any
    code diff does)."""
    import platform
    import subprocess
    la = os.getloadavg()[0] if hasattr(os, "getloadavg") else None
    # which code produced this round (ISSUE 19 satellite): a BENCH json
    # on disk outlives branch switches, so the line must carry its own
    # provenance — bench_compare prints it in the diff header
    commit = None
    try:
        r = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        commit = r.stdout.strip() or None
    except Exception:  # noqa: BLE001 — no git is not an error
        commit = None
    return {
        "python": platform.python_version(),
        "cpu_count": os.cpu_count(),
        "loadavg_1m_start": round(la, 2) if la is not None else None,
        "git_commit": commit,
        "round": os.environ.get("BENCH_ROUND") or None,
    }


def _reap_leaked_processes() -> list:
    """ISSUE 20 satellite: a killed prior session can leave controller,
    invoker, serve-funnel or loadgen worker processes holding ports and
    stealing CPU — which silently skews every number this round reports
    (and a leaked TcpBusServer can collide with a fresh one's port).
    Scan /proc for this repo's long-running process signatures, SIGTERM
    (then SIGKILL after a 5 s grace) everything that is not this process
    or one of its ancestors, and log exactly what was reaped."""
    import os
    import signal
    signatures = ("-m openwhisk_tpu.controller", "-m openwhisk_tpu.invoker",
                  "-m openwhisk_tpu.messaging", "-m openwhisk_tpu.standalone",
                  "openwhisk_tpu/controller/__main__",
                  "openwhisk_tpu/invoker/__main__",
                  "containerpool/actionproxy.py",
                  "--serve-funnel", "tools/loadgen.py")
    keep = set()
    pid = os.getpid()
    while pid > 1:  # never kill ourselves or the driver chain above us
        keep.add(pid)
        try:
            with open(f"/proc/{pid}/stat") as f:
                # field 4 (after the parenthesized comm, which may itself
                # contain spaces) is the ppid
                pid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            break
    try:
        pids = [int(d) for d in os.listdir("/proc") if d.isdigit()]
    except OSError:
        return []
    reaped = []
    for p in pids:
        if p in keep:
            continue
        try:
            with open(f"/proc/{p}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(
                    "utf-8", "replace").strip()
        except OSError:
            continue
        if not any(s in cmd for s in signatures):
            continue
        try:
            os.kill(p, signal.SIGTERM)
        except OSError:
            continue
        reaped.append({"pid": p, "cmd": cmd[:160]})
    if reaped:
        deadline = time.monotonic() + 5.0
        live = {r["pid"] for r in reaped}
        while live and time.monotonic() < deadline:
            time.sleep(0.1)
            live = {p for p in live if os.path.exists(f"/proc/{p}")}
        for p in live:
            try:
                os.kill(p, signal.SIGKILL)
            except OSError:
                pass
        for r in reaped:
            print(f"# reaped leaked process {r['pid']}: {r['cmd']}",
                  file=sys.stderr)
    return reaped


def _run(args) -> Optional[dict]:
    from openwhisk_tpu.utils.config import boot_jax, device_info

    boot_jax()
    if args.sweep:
        _sweep()
        return None

    host_info = _host_info()
    rider_wall_s: dict = {}
    failed_riders: list = []

    def timed_rider(fn_name: str, fn) -> Optional[dict]:
        """Run one rider; its wall-time goes into the `host` block (a slow
        round names the stage that ate it) and a rider that came back with
        nothing is listed in `failed_riders`, which makes the run exit
        non-zero."""
        name = fn_name.lstrip("_")
        t0 = time.monotonic()
        try:
            out = fn()
        finally:
            rider_wall_s[name] = round(time.monotonic() - t0, 1)
        if out is None:
            failed_riders.append(name)
        # progress on stderr: a run killed at a time limit still says
        # which riders finished and what each cost
        print(f"# rider {name}: {rider_wall_s[name]}s"
              + (" FAILED" if out is None else ""), file=sys.stderr,
              flush=True)
        return out

    # A chip belongs to one process at a time: the riders that measure in
    # a fresh DEVICE-OWNING child run here, one at a time, BEFORE this
    # process initializes JAX (device_info below). Everything after runs
    # in this process or in CPU-pinned children that never touch the chip.
    e2e_open_loop = None
    sharded_fleet_sweep = None
    if not args.quick:
        # the headline: the open-loop observatory (sustained
        # activations/s + the per-stage budget the next PR attacks)
        e2e_open_loop = timed_rider("_e2e_open_loop", _e2e_open_loop)
        # ROADMAP item 2: placement rate per fleet size over the
        # ('fleet',) mesh of the default backend's devices
        sharded_fleet_sweep = timed_rider("_sharded_fleet_sweep",
                                          _sharded_fleet_sweep)

    # raises unless this is a TPU, or the CPU named by JAX_PLATFORMS
    device = device_info()
    import jax

    kernels = {}
    if args.kernel in ("xla", "both"):
        kernels["xla"] = _bench_kernel("xla", n_invokers=args.fleet)
    if args.kernel in ("pallas", "both"):
        from openwhisk_tpu.ops.placement_pallas import fits_vmem
        if fits_vmem(args.fleet, 256):
            kernels["pallas"] = _bench_kernel("pallas",
                                              n_invokers=args.fleet)
        else:
            print(f"# pallas skipped: {args.fleet}x256 exceeds the VMEM "
                  "budget (XLA path covers large fleets)", file=sys.stderr)
            if args.kernel == "pallas":
                kernels["xla"] = _bench_kernel("xla", n_invokers=args.fleet)

    parity_ok = _parity_check() if args.kernel == "both" else None

    balancer = None
    balancer_host = None
    host_profiling_overhead = None
    host_observatory = None
    recorder_overhead = None
    telemetry_overhead = None
    profiling_overhead = None
    anomaly_overhead = None
    waterfall_overhead = None
    fleet_observatory_overhead = None
    placement_quality = None
    placement_quality_overhead = None
    funnel_10k = None
    repair_vs_scan = None
    bus_coalesce_speedup = None
    failover_downtime = None
    partition_chaos = None
    trace_assembly = None
    trace_plane_overhead = None
    incident_capture = None
    incident_overhead = None
    if not args.quick:
        # ISSUE 20: the real multi-process deployment — front-end worker
        # processes funneling ONE balancer process over the TCP bus,
        # swept to the 10k/s attempt (always CPU-pinned host work)
        funnel_10k = timed_rider("_funnel_10k", _funnel_10k)
        # the host hot-loop observatory (ISSUE 11): its payoff block is
        # the measured target list the 10k/s vectorization PR attacks,
        # and its overhead gate keeps all four planes under the house 5%
        host_observatory = timed_rider("_host_observatory",
                                       _host_observatory)
        host_profiling_overhead = timed_rider("_host_profiling_overhead",
                                              _host_profiling_overhead)
        bus_coalesce_speedup = timed_rider("_bus_coalesce_speedup",
                                           _bus_coalesce_speedup)
        failover_downtime = timed_rider("_failover_downtime",
                                        _failover_downtime)
        # ISSUE 15: active/active partitioned control under a mid-burst
        # kill — downtime, double-executions (must stay 0), absorption
        partition_chaos = timed_rider("_partition_chaos",
                                      _partition_chaos)
        waterfall_overhead = timed_rider("_waterfall_overhead",
                                         _waterfall_overhead)
        # ISSUE 16: the armed-EventLog ambient cost (scrape-pull-only
        # federation, so steady state should measure ~0)
        fleet_observatory_overhead = timed_rider(
            "_fleet_observatory_overhead", _fleet_observatory_overhead)
        # ISSUE 17: the placement quality plane — straggler A/B payoff
        # (regret + shadow divergence with the penalty on vs off) and its
        # <= 5% paired-overhead gate
        placement_quality = timed_rider("_placement_quality",
                                        _placement_quality)
        placement_quality_overhead = timed_rider(
            "_placement_quality_overhead", _placement_quality_overhead)
        # ISSUE 18: the tail-sampled trace observatory — the acceptance
        # legs (floor-exact clean keep, 100% straggler keep, >= 3-process
        # assembly, dead-peer degradation, exemplar resolution) and the
        # paired <= 5% overhead gate on the traced publish path
        trace_assembly = timed_rider("_trace_assembly", _trace_assembly)
        trace_plane_overhead = timed_rider("_trace_plane_overhead",
                                           _trace_plane_overhead)
        # ISSUE 19: the incident forensics observatory — a straggler-
        # driven alert must freeze exactly one >= 5-plane bundle whose
        # journal window time-travel-replays with zero mismatches, and
        # the armed-idle recorder stays under the house 5% gate
        incident_capture = timed_rider("_incident_capture",
                                       _incident_capture)
        incident_overhead = timed_rider("_incident_overhead",
                                        _incident_overhead)
        repair_vs_scan = timed_rider("_repair_vs_scan", _repair_vs_scan)
        recorder_overhead = timed_rider("_flight_recorder_overhead",
                                        _flight_recorder_overhead)
        telemetry_overhead = timed_rider("_telemetry_overhead",
                                         _telemetry_overhead)
        profiling_overhead = timed_rider("_profiling_overhead",
                                         _profiling_overhead)
        anomaly_overhead = timed_rider("_anomaly_overhead",
                                       _anomaly_overhead)
        rows = _balancer_rows()
        # c64 stays flattened at the top level (older readers); the rows
        # dict carries the per-concurrency detail + phase breakdowns
        balancer = {"backend": jax.default_backend(), **rows["c64"],
                    "rows": rows}
        if jax.default_backend() != "cpu":
            host_rows = _balancer_host_rows()
            if host_rows:
                balancer_host = {"backend": "cpu", **host_rows["c64"],
                                 "rows": host_rows}

    multi = None
    if not args.quick:
        multi = {}
        # the n=1 baseline runs BEFORE AND AFTER the scale-out runs: a
        # shared host drifts minute to minute, so a ratio against a single
        # baseline sample is a coin flip — the scaling factor divides by
        # the mean of the two brackets
        for key, n in (("n1", 1), ("n2", 2), ("n4", 4), ("n1_b", 1)):
            try:
                multi[key] = _multi_controller_bench(n, total_per=2500)
            except Exception as e:  # noqa: BLE001 — stage is auxiliary
                print(f"# multi-controller {key} failed: {e!r}",
                      file=sys.stderr)
        r1s = [multi[k]["aggregate_activations_per_sec"]
               for k in ("n1", "n1_b") if k in multi]
        if r1s and "n2" in multi:
            r1 = sum(r1s) / len(r1s)
            r2 = multi["n2"]["aggregate_activations_per_sec"]
            multi["baseline_n1_mean"] = round(r1, 1)
            multi["baseline_n1_samples"] = len(r1s)  # 1 = a bracket failed
            multi["scaling_1_to_2"] = round(r2 / r1, 2) if r1 else None
            multi["note"] = (
                "CPU-pinned controllers + bus + echo fleet share this "
                "host's cores; real deployments give each controller its "
                "own cores and its own chip")

    cpu_rate = _cpu_oracle_rate()
    # the headline is what the product's kernel="auto" policy resolves to
    # at THIS stage's geometry (fleet padded to a power of two, 256 action
    # slots) — the same resolver TpuBalancer uses, not a re-implementation;
    # both kernel rows ride along in `kernels`
    from openwhisk_tpu.controller.loadbalancer.kernel_choice import choose
    from openwhisk_tpu.controller.loadbalancer.tpu_balancer import \
        _next_pow2
    default_kernel = choose(_next_pow2(args.fleet), 256, 256).backend
    if default_kernel not in kernels:
        default_kernel = "xla" if "xla" in kernels else "pallas"
    headline = kernels.get(default_kernel) or next(iter(kernels.values()))
    print(f"# device={jax.devices()[0]} backend={jax.default_backend()} "
          f"kernel={default_kernel} "
          f"p50_step={headline['p50_step_ms']:.2f}ms "
          f"cpu_oracle={cpu_rate:.0f}/s parity={parity_ok}", file=sys.stderr)

    # ALWAYS tag the round's backend: bench_compare's advisory
    # backend-mismatch rule needs both sides tagged
    out = {
        "metric": "placements_per_sec",
        "backend": device["platform"],
        "device": device,
        "value": headline["rate_median"],
        "unit": "placements/s",
        "vs_baseline": round(headline["rate_median"] / TARGET, 3),
        "median_of": headline["repeats"],
        "spread_pct": headline["spread_pct"],
        "kernel_selection": {
            "default": default_kernel,
            "policy": "kernel='auto' (loadbalancer/kernel_choice.choose): "
                      "pallas on TPU while the state fits VMEM, else xla "
                      "(large fleets swap to xla on growth)",
            "geometry": {"n_pad": _next_pow2(args.fleet),
                         "action_slots": 256},
            "rationale": "bit-exact parity; which backend is faster is "
                         "not measured on a directly attached chip",
        },
        "kernels": kernels,
        "parity_ok": parity_ok,
        "cpu_oracle_per_sec": round(cpu_rate, 1),
    }
    if balancer is not None:
        out["balancer"] = balancer
    if balancer_host is not None:
        out["balancer_host_path"] = balancer_host
    if recorder_overhead is not None:
        out["flight_recorder_overhead"] = recorder_overhead
    if telemetry_overhead is not None:
        out["telemetry_overhead"] = telemetry_overhead
    if profiling_overhead is not None:
        out["profiling_overhead"] = profiling_overhead
    if anomaly_overhead is not None:
        out["anomaly_overhead"] = anomaly_overhead
    if waterfall_overhead is not None:
        out["waterfall_overhead"] = waterfall_overhead
    if fleet_observatory_overhead is not None:
        out["fleet_observatory_overhead"] = fleet_observatory_overhead
    if placement_quality is not None:
        out["placement_quality"] = placement_quality
    if placement_quality_overhead is not None:
        out["placement_quality_overhead"] = placement_quality_overhead
    if host_profiling_overhead is not None:
        out["host_profiling_overhead"] = host_profiling_overhead
    if host_observatory is not None:
        out["host_observatory"] = host_observatory
    if e2e_open_loop is not None:
        out["e2e_open_loop"] = e2e_open_loop
    if funnel_10k is not None:
        out["funnel_10k"] = funnel_10k
    if bus_coalesce_speedup is not None:
        out["bus_coalesce_speedup"] = bus_coalesce_speedup
    if failover_downtime is not None:
        out["failover_downtime"] = failover_downtime
    if partition_chaos is not None:
        out["partition_chaos"] = partition_chaos
    if repair_vs_scan is not None:
        out["repair_vs_scan"] = repair_vs_scan
    if sharded_fleet_sweep is not None:
        out["sharded_fleet_sweep"] = sharded_fleet_sweep
    if trace_assembly is not None:
        out["trace_assembly"] = trace_assembly
    if trace_plane_overhead is not None:
        out["trace_plane_overhead"] = trace_plane_overhead
    if incident_capture is not None:
        out["incident_capture"] = incident_capture
    if incident_overhead is not None:
        out["incident_overhead"] = incident_overhead
    if multi:
        out["multi_controller"] = multi
    # the `host` block (ISSUE 11 satellite): box identity + load brackets
    # + per-rider wall-time, so BENCH rounds on the noisy box compare
    la_end = None
    import os as _os
    if hasattr(_os, "getloadavg"):
        la_end = round(_os.getloadavg()[0], 2)
    host_info["loadavg_1m_end"] = la_end
    host_info["rider_wall_s"] = rider_wall_s
    out["host"] = host_info
    if failed_riders:
        out["failed_riders"] = failed_riders
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", choices=("xla", "pallas", "both"),
                    default="both")
    ap.add_argument("--fleet", type=int, default=N_INVOKERS,
                    help="invoker count for the kernel stages (the "
                         "north-star config is 65536)")
    ap.add_argument("--quick", action="store_true",
                    help="skip the balancer-level benchmark")
    ap.add_argument("--sweep", action="store_true",
                    help="print an (N x A) xla-vs-pallas table to stderr")
    args = ap.parse_args()

    # preamble (ISSUE 20): reap leaked prior-session service processes
    # BEFORE any round measures — a survivor controller/invoker/loadgen
    # fleet skews every number and can hold the bus ports
    try:
        reaped = _reap_leaked_processes()
    except Exception as e:  # noqa: BLE001 — the reaper must never kill a run
        print(f"# leaked-process reap failed: {e!r}", file=sys.stderr)
        reaped = []

    # ONE parseable JSON line on stdout either way, but a failure is a
    # failure: an exception (no device, a dead stage) or a rider that
    # produced nothing exits non-zero
    try:
        out = _run(args)
    except Exception as e:  # noqa: BLE001 — every failure becomes JSON
        import traceback
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({
            "metric": "placements_per_sec",
            "value": None,
            "unit": "placements/s",
            "error": f"{type(e).__name__}: {e}",
        }))
        raise SystemExit(1)
    if out is not None:
        if reaped:
            out["reaped_leaked_processes"] = reaped
        print(json.dumps(out))
        if out.get("failed_riders"):
            raise SystemExit(1)


if __name__ == "__main__":
    main()
