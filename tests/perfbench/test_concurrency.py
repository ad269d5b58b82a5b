"""Concurrent containers in the benchmark's yardstick: the catalogue's
per-action concurrency, the plain reference's second level of books
(NestedSemaphore) against a case worked by hand and against the program's
own oracle, and the slot record. Counts and verdicts only."""
import hashlib
import json
import os
import random

import numpy as np
import pytest

from benchmark import reference, run, traffic

BENCH = os.path.join(run.ROOT, "benchmark")


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# -- the catalogue ------------------------------------------------------------

#: sha256 over (namespace, names, memory, service times in hex) as the
#: parent commit's generator (aa594c3) made them, before concurrency
PARENT_CATALOGUES = {
    ("fleet1k", 7):
        "863db26e3eb82587bd7a5b8bd622fb7dfa16f598f81c1a4ec0e945ed6f1ebadb",
    ("fleet1k", 2**31 + 7):
        "e92ecb6c2a37baedb3f8864256018ee6e20e6875a928d02e33c0169023a38012",
    ("standalone16", 7):
        "539a062c60f0bbe7c7f565bcefac32e441b8883144ea83a69ba7d85cb1a6e2dc",
    ("standalone16", 2**31 + 7):
        "f3325db3a62b612d23b5911bfa2c3ed65f3cc71c8ec7a8d876f7006383b6e2dc",
}


@pytest.mark.parametrize("name,seed", sorted(PARENT_CATALOGUES))
def test_the_two_catalogues_are_the_parent_s_bit_for_bit(name, seed):
    cat = traffic.make_catalog(_config(name), seed)
    blob = json.dumps([cat.namespace, cat.names, cat.memory_mb,
                       [s.hex() for s in cat.service_s]]).encode()
    assert hashlib.sha256(blob).hexdigest() == PARENT_CATALOGUES[name, seed]
    assert cat.concurrency == [1] * len(cat.names)


def test_mixed_concurrency_is_dealt_by_the_catalog_seed_alone():
    cfg = _config("fleet1k")
    plain = traffic.make_catalog(cfg, 3)
    cfg["action_concurrency_max"] = 50
    cfg["actions"]["concurrency"] = {"values": [1, 4, 16, 50],
                                     "weights": [1, 1, 1, 1]}
    a, b = traffic.make_catalog(cfg, 3), traffic.make_catalog(cfg, 4)
    assert a.concurrency == b.concurrency          # never by --seed
    assert sorted(set(a.concurrency)) == [1, 4, 16, 50]
    assert np.bincount(a.concurrency)[[1, 4, 16, 50]].tolist() == [512] * 4
    # a stream of its own: memory and service times stay where they were
    assert (a.memory_mb, a.service_s) == (plain.memory_mb, plain.service_s)
    cfg["catalog_seed"] = 1
    assert traffic.make_catalog(cfg, 3).concurrency != a.concurrency
    cfg["actions"]["concurrency"] = 7
    assert traffic.make_catalog(cfg, 3).concurrency == [7] * 2048
    cfg["action_concurrency_max"] = 6     # over the deployment's ceiling
    with pytest.raises(ValueError):
        traffic.make_catalog(cfg, 3)


# -- the second level of books --------------------------------------------------

def test_three_to_a_container_worked_by_hand():
    fleet = reference.ReferenceFleet(1.0)
    fleet.register(0, 512, True)
    key = reference.action_key("ns/a", 256)

    def place():
        return fleet.schedule("ns", "ns/a", 256, rand=0, maxc=3)

    # three activations on one container's memory
    assert [place() for _ in range(3)] == [(0, False)] * 3
    assert fleet.free_mb() == [256] and fleet.spare() == [{key: 0}]
    # the fourth takes new memory and mints two more spares
    assert place() == (0, False)
    assert fleet.free_mb() == [0] and fleet.spare() == [{key: 2}]
    # another action of the same size finds no memory and is forced; the
    # spare permits of "ns/a" are not its own
    assert fleet.schedule("ns", "ns/b", 256, rand=0, maxc=3) == (0, True)
    assert fleet.free_mb() == [-256]
    assert fleet.spare()[0][reference.action_key("ns/b", 256)] == 2
    # "ns/a" still fits without memory: it has spare permits
    assert [place() for _ in range(2)] == [(0, False)] * 2
    assert fleet.free_mb() == [-256] and fleet.spare()[0][key] == 0
    # a seventh is forced: over-commit, and its container's spares
    assert place() == (0, True)
    assert fleet.free_mb() == [-512] and fleet.spare()[0][key] == 2
    # memory returns only when three permits are back
    for want_free, want_spare in ((-256, 0), (-256, 1), (-256, 2), (0, 0),
                                  (0, 1), (0, 2)):
        fleet.release(0, 256, "ns/a", 3)
        assert fleet.free_mb() == [want_free]
        assert fleet.spare()[0].get(key, 0) == want_spare
    fleet.release(0, 256, "ns/a", 3)
    # the last container idle: its memory back, the key dropped at zero
    assert fleet.free_mb() == [256] and key not in fleet.spare()[0]
    # the forced container of "ns/b" held one activation and two spares
    fleet.release(0, 256, "ns/b", 3)
    assert fleet.free_mb() == [512] and fleet.spare() == [{}]
    # concurrency 1 is the memory alone, whatever the key
    assert fleet.schedule("ns", "ns/c", 256, rand=0) == (0, False)
    assert fleet.free_mb() == [256] and fleet.spare() == [{}]
    fleet.release(0, 256)
    assert fleet.free_mb() == [512]


@pytest.mark.parametrize("maxc", [1, 2, 5, 50])
def test_reference_equals_the_program_s_oracle(maxc):
    """A seeded random sequence of schedules, releases and health flips:
    every decision and every book of the plain reference equals those of
    models/sharding_policy.py over utils/semaphores.py."""
    from openwhisk_tpu.models import sharding_policy as oracle

    n_inv, rng = 24, random.Random(1000 + maxc)
    ref = reference.ReferenceFleet(1.0)
    for i in range(n_inv):
        ref.register(i, 1024, True)
    state = oracle.ShardingPolicyState.build([1024] * n_inv,
                                             managed_fraction=1.0)
    actions = [(f"ns/a{k}", rng.choice([128, 256, 512]),
                maxc if k % 3 else 1) for k in range(40)]
    in_flight, decisions = [], 0
    for _ in range(4000):
        roll = rng.random()
        if roll < 0.55 or not in_flight:
            fqn, mem, c = rng.choice(actions)
            rand = rng.randrange(1 << 20)
            got = ref.schedule("ns", fqn, mem, rand, c)
            want = oracle.schedule(state, "ns", fqn, mem, c,
                                   forced_rand=rand)
            assert got == want
            decisions += 1
            if got[0] is not None:
                in_flight.append((got[0], fqn, mem, c))
        elif roll < 0.97:
            inv, fqn, mem, c = in_flight.pop(rng.randrange(len(in_flight)))
            ref.release(inv, mem, fqn, c)
            oracle.release(state, inv, fqn, mem, c)
        else:
            i, up = rng.randrange(n_inv), rng.random() < 0.5
            ref.set_health(i, up)
            state.set_health(i, up)
        assert ref.free_mb() == [inv.semaphore.available_permits
                                 for inv in state.invokers]
        for mine, theirs in zip(ref.spare(), state.invokers):
            keys = set(mine) | set(theirs.semaphore._action_slots)
            assert all(mine.get(k, 0)
                       == theirs.semaphore.concurrent_slots_available(k)
                       for k in keys)
    assert decisions > 2000 and any(d[3] > 1 for d in in_flight) == (maxc > 1)


# -- the slot record and the permit table -----------------------------------------

def _batch(seq, cols, releases=()):
    """One journaled fused step: `cols` are (aid, mem, slot, maxc, rand),
    `releases` rows of (invoker, slot, MB, maxc)."""
    import base64
    R, H, B = 8, 8, 8
    rel = np.zeros((5, R), np.int32)
    rel[3] = 1
    for j, row in enumerate(releases):
        rel[:4, j] = row
        rel[4, j] = 1
    req = np.zeros((9, B), np.int32)
    req[6] = 1
    for j, (_aid, mem, slot, maxc, rand) in enumerate(cols):
        req[4:8, j] = (mem, slot, maxc, rand)
        req[8, j] = 1
    buf = np.concatenate([rel.ravel(), np.zeros(3 * H, np.int32),
                          req.ravel()])
    return {"t": "batch", "seq": seq, "R": R, "H": H, "B": B, "rows": 9,
            "b": len(cols), "aids": [c[0] for c in cols],
            "buf": base64.b64encode(buf.tobytes()).decode()}


def _replayed(records, sent):
    fleet = reference.ReferenceFleet(1.0)
    fleet.register(0, 512, True)
    return reference.replay(records, sent, fleet, 1)


def test_release_rows_are_translated_through_the_slot_record():
    sent = {"x1": ("ns", "ns/a", 256, 3), "x2": ("ns", "ns/a", 256, 3),
            "y1": ("ns", "ns/b", 128, 1)}
    got = _replayed([
        _batch(1, [("x1", 256, 5, 3, 0), ("x2", 256, 5, 3, 0),
                   ("y1", 128, 6, 1, 0)]),
        _batch(2, [], [(0, 5, 256, 3), (0, 6, 128, 1)]),
    ], sent)
    assert got["input_mismatch"] == 0 and got["slot_conflict"] == 0
    assert got["releases"] == [(0, "ns/a", 256), (0, "ns/b", 128)]
    assert got["free_mb"] == [256]
    assert got["permits"] == {(0, 5): 2} and got["permits_homeless"] == 0
    # the permit table: two spares of the action that last held slot 5
    table = np.zeros((4, 8), np.int32)
    table[0, 5] = 2
    assert reference.permit_cells_differing(table, got) == 0
    table[0, 5] = 1                 # one permit never returned
    table[3, 2] = 1                 # and one in a padding row
    assert reference.permit_cells_differing(table, got) == 2


def test_two_actions_in_one_slot_and_a_wrong_maxc_are_counted():
    sent = {"x1": ("ns", "ns/a", 256, 3), "y1": ("ns", "ns/b", 128, 3)}
    got = _replayed([
        _batch(1, [("x1", 256, 5, 3, 0), ("y1", 128, 5, 3, 0)]),
        # the row of "ns/a" claims concurrency 1; slot 7 holds nothing
        _batch(2, [], [(0, 5, 256, 1), (0, 7, 128, 3)]),
    ], sent)
    assert got["slot_conflict"] == 1
    assert got["input_mismatch"] == 2
    # a slot handed on after its last release is no conflict
    sent = {"x1": ("ns", "ns/a", 256, 1), "y1": ("ns", "ns/b", 256, 1)}
    got = _replayed([_batch(1, [("x1", 256, 5, 1, 0)]),
                     _batch(2, [("y1", 256, 5, 1, 0)], [(0, 5, 256, 1)])],
                    sent)
    assert got["slot_conflict"] == 0 and got["input_mismatch"] == 0
    # a request whose journaled concurrency is not the catalogue's
    got = _replayed([_batch(1, [("x1", 256, 5, 2, 0)])], sent)
    assert got["input_mismatch"] == 1
