"""ISSUE 28: the exact warm bit, from the kernels to the counters.

One toy balancer run on the CPU twin with actions of concurrency 1, 3 and
5 whose activations stay in flight across batches, traced, with the
quality plane and the journal on. The program's oracle
(`models/sharding_policy.py` over `utils/semaphores.py`) is driven over
the journal's records of the run and counts the placements that took a
spare slot of a container an invoker already held. Held to that count:
the counter `loadbalancer_tpu_warm_placements`, the `warm` stat of the
`ow_fanout` spans, the flight recorder's rows and, through `cold_start` +
warm = placed, the quality plane's attribution. No assertion is on a time.
"""
from __future__ import annotations

import asyncio

import numpy as np
import pytest

from openwhisk_tpu.controller.loadbalancer import TpuBalancer
from openwhisk_tpu.controller.loadbalancer.base import maybe_batch_publish
from openwhisk_tpu.controller.loadbalancer.journal import (PlacementJournal,
                                                           decode_array)
from openwhisk_tpu.controller.loadbalancer.quality import (QualityConfig,
                                                           QualityPlane)
from openwhisk_tpu.core.entity import (ActionLimits, CodeExec,
                                       ConcurrencyLimit,
                                       ControllerInstanceId, EntityName,
                                       EntityPath, ExecutableWhiskAction,
                                       Identity, MB, MemoryLimit, TimeLimit)
from openwhisk_tpu.core.entity.ids import DocRevision
from openwhisk_tpu.messaging import MemoryMessagingProvider
from openwhisk_tpu.models import sharding_policy as oracle
from openwhisk_tpu.ops.decision_quality import COUNTERS

from tests.test_spans import (N_INVOKERS, _healthy_fleet,  # noqa: E402
                              _host_lines, _idle, _msg)

#: action name -> (memory MB, concurrency); 4 invokers x 2048 MB
ACTIONS = {"solo": (512, 1), "trio": (512, 3), "five": (256, 5)}
WAVES = (9, 12, 7, 10)


def _action(name: str) -> ExecutableWhiskAction:
    mem, conc = ACTIONS[name]
    a = ExecutableWhiskAction(
        EntityPath("guest"), EntityName(name),
        CodeExec(kind="python:3", code="x"),
        limits=ActionLimits(TimeLimit(5000), MemoryLimit(MB(mem)),
                            concurrency=ConcurrencyLimit(conc)))
    a.rev = DocRevision("1-b")
    return a


async def _run(journal_dir: str, trace_dir: str) -> dict:
    import jax

    provider = MemoryMessagingProvider()
    bal = TpuBalancer(
        provider, ControllerInstanceId("0"), managed_fraction=1.0,
        blackbox_fraction=0.0, prewarm=False,
        quality=QualityPlane(QualityConfig(enabled=True, shadow_every_n=0)))
    journal = PlacementJournal(journal_dir)
    bal.attach_journal(journal)
    await bal.start()
    # acks come 0.4 s after delivery: activations of one wave are still in
    # flight when the next is placed, so containers are there to share
    feeds, _ping = await _healthy_fleet(provider, bal, service_s=0.4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        seq0 = bal._journal_seq
        ident = Identity.generate("guest")
        publisher = maybe_batch_publish(bal)
        names = sorted(ACTIONS)
        sent, waits = {}, []
        for k, n in enumerate(WAVES):
            msgs = [(a, _msg(a, ident)) for a in
                    (_action(names[(k + i) % 3]) for i in range(n))]
            for a, m in msgs:
                sent[m.activation_id.asString] = str(a.name)
            waits += await asyncio.gather(
                *[publisher.publish(a, m) for a, m in msgs])
            await asyncio.sleep(0.05)
        await asyncio.gather(*waits)
        await _idle(bal)
        seq1 = bal._journal_seq
    finally:
        jax.profiler.stop_trace()
    counted = bal.metrics.counter_value("loadbalancer_tpu_warm_placements")
    quality = dict(zip(COUNTERS, bal.quality.counts()["counters"].tolist()))
    rows = [bal.flight_recorder.explain(aid) for aid in sent]
    for f in feeds:
        await f.stop()
    # a cold balancer re-derives the journal through the same kernels: its
    # decision words carry the warm bit, the journal's do not
    assert journal.flush()
    cold = TpuBalancer(provider, ControllerInstanceId("1"),
                       managed_fraction=1.0, blackbox_fraction=0.0,
                       prewarm=False)
    replay = cold.replay_journal(PlacementJournal(journal_dir).records(0))
    replay["books"] = bool(
        (np.asarray(cold.state.free_mb)
         == np.asarray(bal.state.free_mb)).all()
        and (np.asarray(cold.state.conc_free)
             == np.asarray(bal.state.conc_free)).all())
    await cold.close()
    await bal.close()
    journal.close()
    records = [r for r in PlacementJournal(journal_dir).records()
               if seq0 < r["seq"] <= seq1]
    return {"records": records, "sent": sent, "counted": counted,
            "quality": quality, "rows": rows, "replay": replay}


def _oracle_over(records: list, sent: dict) -> dict:
    """The oracle's (invoker, forced, warm) per activation id, driven over
    the journal's batch and fold records in order: releases first, then
    the requests, as the fused step does."""
    st = oracle.ShardingPolicyState.build(
        [2048] * N_INVOKERS, managed_fraction=1.0, blackbox_fraction=0.0)
    held, out = {}, {}   # slot -> action name while the oracle holds it

    def fold(rel) -> None:
        for inv, slot, mem, maxc, valid in rel.T:
            if valid:
                oracle.release(st, int(inv), f"guest/{held[int(slot)]}",
                               int(mem), int(maxc))

    for rec in records:
        if rec["t"] == "fold" and "rel" in rec:
            fold(decode_array(rec["rel"]).reshape(5, -1))
        if rec["t"] != "batch":
            continue
        R, H, B = rec["R"], rec["H"], rec["B"]
        buf = decode_array(rec["buf"])
        fold(buf[:5 * R].reshape(5, R))
        req = buf[5 * R + 3 * H:].reshape(rec["rows"], B)
        for col, aid in enumerate(rec["aids"][:rec["b"]]):
            name = sent[aid]
            mem, conc = ACTIONS[name]
            assert (req[4, col], req[6, col]) == (mem, conc)
            held[int(req[5, col])] = name
            before = [i.semaphore.available_permits for i in st.invokers]
            # the balancer hashes the namespace and the qualified name
            inv, forced = oracle.schedule(st, "guest", f"guest/{name}", mem,
                                          conc, forced_rand=int(req[7, col]))
            out[aid] = (inv, forced, inv is not None and before[inv]
                        == st.invokers[inv].semaphore.available_permits)
    return out


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("warm")
    was = ConcurrencyLimit.MAX
    ConcurrencyLimit.MAX = 5
    try:
        out = asyncio.run(_run(str(tmp / "journal"), str(tmp / "trace")))
    finally:
        ConcurrencyLimit.MAX = was
    out["lines"] = _host_lines(str(tmp / "trace"))
    out["oracle"] = _oracle_over(out["records"], out["sent"])
    return out


def test_the_journal_s_decisions_are_the_oracle_s(run):
    acks = {r["for"]: r["out"] for r in run["records"] if r["t"] == "ack"}
    got = {}
    for rec in run["records"]:
        if rec["t"] == "batch":
            for aid, v in zip(rec["aids"], acks[rec["seq"]]):
                # the journal's own layout: no warm bit (journal_words)
                got[aid] = ((v >> 2) - 1, bool(v & 1))
    assert got == {aid: (inv, forced)
                   for aid, (inv, forced, _w) in run["oracle"].items()}
    assert len(got) == sum(WAVES)


def test_the_counter_is_the_oracle_s_count(run):
    warm = sum(w for _i, _f, w in run["oracle"].values())
    # concurrency 3 and 5 share containers; concurrency 1 never does
    assert 0 < warm < sum(WAVES)
    assert not any(w for aid, (_i, _f, w) in run["oracle"].items()
                   if run["sent"][aid] == "solo")
    assert run["counted"] == warm


def test_the_fanout_spans_carry_warm_and_forced(run):
    fanouts = [st for line in run["lines"] for name, _s, _e, st in line
               if name == "ow_fanout"]
    assert len(fanouts) == sum(r["t"] == "batch" for r in run["records"])
    assert sum(st["b"] for st in fanouts) == sum(WAVES)
    assert sum(st["warm"] for st in fanouts) == run["counted"]
    assert sum(st["forced"] for st in fanouts) \
        == sum(f for _i, f, _w in run["oracle"].values())
    by_seq = {r["seq"]: r for r in run["records"] if r["t"] == "batch"}
    for st in fanouts:
        aids = by_seq[st["seq"]]["aids"][:st["b"]]
        assert st["warm"] == sum(run["oracle"][a][2] for a in aids)


def test_cold_start_is_placed_and_not_warm(run):
    q = run["quality"]
    assert q["rows"] == q["placed"] == sum(WAVES)
    assert q["cold_start"] + run["counted"] == q["placed"]
    assert q["cold_start"] == sum(not w for _i, _f, w
                                  in run["oracle"].values())


def test_the_flight_recorder_s_rows_say_warm(run):
    rows = dict(zip(run["sent"], run["rows"]))
    filed = {aid: row["decision"] for aid, row in rows.items()
             if row is not None}
    assert filed
    for aid, d in filed.items():
        assert d["warm"] == run["oracle"][aid][2], (aid, d)
        assert d["invoker_index"] == run["oracle"][aid][0]


def test_replay_rederives_the_journal_s_words(run):
    stats = run["replay"]
    assert stats["batches"] == sum(r["t"] == "batch" for r in run["records"])
    assert stats["parity_mismatches"] == 0 and stats["books"]
