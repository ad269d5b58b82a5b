"""Placement engine tests: scheduling-math parity between the CPU oracle
(models.sharding_policy — faithful ShardingContainerPoolBalancer semantics)
and the JAX kernel (ops.placement), single-device and 8-way sharded.

Mirrors the reference's ShardingContainerPoolBalancerTests behaviors
(:86 schedule to home invoker, :244 overload forcing, :369 coprimes,
:386 concurrency slot accounting) plus exact trace parity, which the
reference cannot test (it has only one implementation).
"""
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openwhisk_tpu.models.sharding_policy import (ShardingPolicyState,
                                                  generate_hash,
                                                  pairwise_coprimes, release,
                                                  schedule)
from openwhisk_tpu.ops.placement import (PlacementState, RequestBatch,
                                         init_state, release_batch,
                                         schedule_batch, set_health)


# ---------------------------------------------------------------------------
# CPU oracle behaviors (ref ShardingContainerPoolBalancerTests)
# ---------------------------------------------------------------------------

class TestCpuPolicy:
    def test_coprimes(self):
        assert pairwise_coprimes(7) == [1, 2, 3, 5]
        assert pairwise_coprimes(10) == [1, 3, 7]
        assert pairwise_coprimes(1) == [1]
        for x in (4, 9, 16, 100):
            import math
            for c in pairwise_coprimes(x):
                assert math.gcd(c, x) == 1

    def test_schedule_home_invoker_when_free(self):
        st = ShardingPolicyState.build([512] * 8, managed_fraction=1.0, blackbox_fraction=0.0)
        st.blackbox_fraction = 0.0  # all managed for determinism
        h = generate_hash("ns", "act")
        offset, size = st.partition(False)
        home = h % size
        chosen, forced = schedule(st, "ns", "act", 256)
        assert chosen == home and not forced

    def test_schedule_steps_when_home_full(self):
        st = ShardingPolicyState.build([256] * 4, managed_fraction=1.0, blackbox_fraction=0.0)
        # fill the home invoker
        c1, _ = schedule(st, "ns", "act", 256)
        c2, f2 = schedule(st, "ns", "act", 256)
        assert c2 != c1 and not f2

    def test_overload_forces_random_usable(self):
        st = ShardingPolicyState.build([256] * 2, managed_fraction=1.0, blackbox_fraction=0.0)
        assert schedule(st, "ns", "a", 256)[1] is False
        assert schedule(st, "ns", "a", 256)[1] is False
        chosen, forced = schedule(st, "ns", "a", 256, rng=random.Random(7))
        assert forced and chosen in (0, 1)

    def test_unusable_invokers_skipped(self):
        st = ShardingPolicyState.build([512] * 4, managed_fraction=1.0, blackbox_fraction=0.0)
        h = generate_hash("ns", "act")
        _, size = st.partition(False)
        home = h % size
        st.set_health(home, False)
        chosen, forced = schedule(st, "ns", "act", 256)
        assert chosen != home and not forced

    def test_no_usable_invokers_returns_none(self):
        st = ShardingPolicyState.build([512] * 3, managed_fraction=1.0, blackbox_fraction=0.0)
        for i in range(3):
            st.set_health(i, False)
        assert schedule(st, "ns", "act", 256) == (None, False)

    def test_blackbox_partition(self):
        st = ShardingPolicyState.build([512] * 10, managed_fraction=0.9,
                                       blackbox_fraction=0.1)
        assert st.blackbox_count == 1
        assert st.managed_count == 9
        chosen, _ = schedule(st, "ns", "bb", 256, blackbox=True)
        assert chosen == 9  # only the last invoker serves blackbox

    def test_cluster_share_division(self):
        st = ShardingPolicyState.build([2048] * 2, cluster_size=2,
                                       managed_fraction=1.0, blackbox_fraction=0.0)
        assert st.invokers[0].semaphore.available_permits == 1024
        st.update_cluster(4)
        assert st.invokers[0].semaphore.available_permits == 512
        # share never below one minimal slot
        st2 = ShardingPolicyState.build([256] * 1, cluster_size=8)
        assert st2.invokers[0].semaphore.available_permits == 128

    def test_concurrency_shares_container_slots(self):
        st = ShardingPolicyState.build([256] * 2, managed_fraction=1.0, blackbox_fraction=0.0)
        placements = [schedule(st, "ns", "c", 256, max_concurrent=4)
                      for _ in range(8)]
        # 4 runs share each 256MB container -> two containers on two invokers
        assert all(not f for _, f in placements)
        assert len({c for c, _ in placements}) == 2

    def test_release_restores_capacity(self):
        st = ShardingPolicyState.build([256] * 1, managed_fraction=1.0, blackbox_fraction=0.0)
        c, _ = schedule(st, "ns", "act", 256)
        assert schedule(st, "ns", "act", 256)[1]  # full -> forced
        release(st, c, "act", 256)
        release(st, c, "act", 256)
        c2, forced = schedule(st, "ns", "act", 256)
        assert c2 == c and not forced


# ---------------------------------------------------------------------------
# kernel <-> oracle trace parity
# ---------------------------------------------------------------------------

def _inverse(step: int, m: int) -> int:
    return pow(step, -1, m) if m > 1 else 0


def _batch_from_trace(st: ShardingPolicyState, trace, slot_of):
    """Build a RequestBatch mirroring what the TPU balancer host side does."""
    B = len(trace)
    cols = {k: np.zeros((B,), np.int32) for k in
            ("offset", "size", "home", "step_inv", "need_mb", "conc_slot",
             "max_conc", "rand")}
    valid = np.ones((B,), bool)
    for i, (ns, act, mem, conc, blackbox) in enumerate(trace):
        offset, size = st.partition(blackbox)
        h = generate_hash(ns, act)
        steps = st.step_sizes_blackbox if blackbox else st.step_sizes_managed
        step = steps[h % len(steps)]
        cols["offset"][i] = offset
        cols["size"][i] = size
        cols["home"][i] = h % size
        cols["step_inv"][i] = _inverse(step, size)
        cols["need_mb"][i] = mem
        cols["conc_slot"][i] = slot_of(f"{act}:{mem}")
        cols["max_conc"][i] = conc
        cols["rand"][i] = (h ^ (i * 2654435761)) % max(size, 1)
    return RequestBatch(*(jnp.asarray(cols[k]) for k in
                          ("offset", "size", "home", "step_inv", "need_mb",
                           "conc_slot", "max_conc", "rand")),
                        valid=jnp.asarray(valid))


def _make_slot_allocator():
    slots = {}

    def slot_of(key):
        if key not in slots:
            slots[key] = len(slots)
        return slots[key]
    return slot_of


def _random_trace(n_actions, B, seed, conc_choices=(1,), bb_prob=0.0,
                  mems=(128, 256, 512)):
    rng = random.Random(seed)
    # memory, concurrency and blackbox-ness are properties OF AN ACTION
    # (its limits/exec), constant across its invocations
    action_props = {a: (rng.choice(mems), conc_choices[a % len(conc_choices)],
                        rng.random() < bb_prob) for a in range(n_actions)}
    trace = []
    for _ in range(B):
        a = rng.randrange(n_actions)
        mem, conc, bb = action_props[a]
        trace.append((f"ns{a % 3}", f"action{a}", mem, conc, bb))
    return trace


def _run_oracle(st, trace, warm=None):
    """Run the oracle with the SAME deterministic forced-choice rotation the
    kernel batch carries (host passes identical rand to both paths). A list
    passed as `warm` gets one bool per request: the oracle took a spare
    slot of a container the invoker already held, i.e. placed the request
    and left the chosen invoker's memory as it was."""
    out = []
    for i, (ns, act, mem, conc, bb) in enumerate(trace):
        _, size = st.partition(bb)
        h = generate_hash(ns, act)
        rand = (h ^ (i * 2654435761)) % max(size, 1)
        before = [inv.semaphore.available_permits for inv in st.invokers]
        chosen, forced = schedule(st, ns, act, mem, conc, bb,
                                  forced_rand=rand)
        out.append((chosen if chosen is not None else -1, forced))
        if warm is not None:
            warm.append(chosen is not None and before[chosen]
                        == st.invokers[chosen].semaphore.available_permits)
    return out


@pytest.mark.parametrize("n_invokers,n_actions,conc,bb", [
    (16, 10, (1,), 0.0),
    (16, 4, (1,), 0.0),       # heavy contention -> stepping + forcing
    (40, 12, (1,), 0.25),     # blackbox partition in play
    (16, 6, (4,), 0.0),       # intra-container concurrency
    (64, 30, (1, 4, 8), 0.1), # mixed
])
def test_kernel_matches_oracle_exactly(n_invokers, n_actions, conc, bb):
    """The kernel must make the SAME decision as the reference-semantics
    oracle for every request of a random trace (sequential-equivalence)."""
    from openwhisk_tpu.core.entity import ConcurrencyLimit
    mems = (128, 256) if max(conc) > 1 else (128, 256, 512)
    trace = _random_trace(n_actions, 192, seed=n_invokers * 7 + n_actions,
                          conc_choices=conc, bb_prob=bb, mems=mems)

    st = ShardingPolicyState.build([1024] * n_invokers)
    slot_of = _make_slot_allocator()
    batch = _batch_from_trace(st, trace, slot_of)
    kstate = init_state(n_invokers, [st.invoker_slot_mb(1024)] * n_invokers,
                        action_slots=128)
    kstate, chosen, forced, _warm = schedule_batch(kstate, batch)
    chosen = np.asarray(chosen)
    forced = np.asarray(forced)

    oracle = _run_oracle(st, trace)
    for i, ((oc, of), kc, kf) in enumerate(zip(oracle, chosen, forced)):
        assert of == bool(kf), f"req {i}: forced mismatch {of} vs {kf}"
        assert oc == int(kc), f"req {i}: oracle {oc} vs kernel {int(kc)}"
    # capacity books must agree exactly after the whole batch
    kernel_free = np.asarray(kstate.free_mb)[:n_invokers]
    oracle_free = np.array([inv.semaphore.available_permits
                            for inv in st.invokers])
    np.testing.assert_array_equal(kernel_free, oracle_free)


def _kernel_family(name):
    """One placement-kernel family behind one face: (place, schedule,
    release) over a [N, A] state, whatever layout or mesh it runs on."""
    from openwhisk_tpu.ops.placement import (release_batch_vector,
                                             schedule_batch_repair)
    if name == "xla_scan":
        return (lambda s: s), schedule_batch, release_batch
    if name == "xla_repair":
        return (lambda s: s), schedule_batch_repair, release_batch_vector
    if name.startswith("pallas"):
        from openwhisk_tpu.ops.placement_pallas import (
            schedule_batch_pallas, schedule_batch_repair_pallas,
            to_transposed)
        fn = (schedule_batch_repair_pallas if name == "pallas_repair"
              else schedule_batch_pallas)

        def sched(state, batch):
            ts, *out = fn(to_transposed(state), batch, interpret=True)
            return (to_transposed(ts), *out)

        return (lambda s: s), sched, release_batch
    from openwhisk_tpu.parallel import (make_fleet_release_vector,
                                        make_fleet_repair_schedule,
                                        make_mesh, make_sharded_release,
                                        make_sharded_schedule, shard_state)
    mesh = make_mesh(8)
    if name == "mesh_scan":
        return ((lambda s: shard_state(s, mesh)),
                make_sharded_schedule(mesh), make_sharded_release(mesh))
    return ((lambda s: shard_state(s, mesh)),
            make_fleet_repair_schedule(mesh, axis="inv"),
            make_fleet_release_vector(mesh, axis="inv"))


@pytest.mark.parametrize("maxc", [1, 2, 5, 50])
@pytest.mark.parametrize("kernel", [
    "xla_scan", "xla_repair",
    pytest.param("pallas_scan", marks=pytest.mark.pallas),
    pytest.param("pallas_repair", marks=pytest.mark.pallas),
    pytest.param("mesh_scan", marks=pytest.mark.mesh),
    pytest.param("mesh_repair", marks=pytest.mark.mesh)])
def test_every_kernel_matches_oracle_on_streams(kernel, maxc):
    """The oracle parity above, for every schedule kernel and over a
    STREAM: four batches with random releases between them, on a fleet
    short enough of memory to step and to force. Per row the kernel's
    (chosen, forced) are the oracle's, its `warm` bit is the oracle's
    "took a spare slot", and after every batch and every release fold both
    levels of the books agree."""
    n, n_actions, b = 8, 6, 32
    rng = random.Random(31 * maxc + len(kernel))
    st = ShardingPolicyState.build([256] * n)
    slot_of = _make_slot_allocator()
    place, sched, rel = _kernel_family(kernel)
    kstate = place(init_state(n, [st.invoker_slot_mb(256)] * n,
                              action_slots=8))
    # memory and concurrency are properties of an action, for the stream
    props = [(rng.choice((128, 256)), (maxc, 1, maxc)[a % 3])
             for a in range(n_actions)]
    in_flight, seen_warm, seen_forced, keys = [], 0, 0, set()

    def books_agree():
        np.testing.assert_array_equal(
            np.asarray(kstate.free_mb),
            [inv.semaphore.available_permits for inv in st.invokers])
        conc = np.asarray(kstate.conc_free)
        for i, inv in enumerate(st.invokers):
            for key in keys:
                assert conc[i, slot_of(key)] == \
                    inv.semaphore.concurrent_slots_available(key)

    for step in range(4):
        trace = [(f"ns{a % 3}", f"action{a}", *props[a], False)
                 for a in (rng.randrange(n_actions) for _ in range(b))]
        batch = _batch_from_trace(st, trace, slot_of)
        keys.update(f"{act}:{mem}" for _ns, act, mem, _c, _bb in trace)
        o_warm = []
        oracle = _run_oracle(st, trace, o_warm)
        kstate, chosen, forced, warm = sched(kstate, batch)[:4]
        got = list(zip(np.asarray(chosen).tolist(),
                       np.asarray(forced).tolist()))
        assert got == oracle, (kernel, maxc, step)
        assert np.asarray(warm).tolist() == o_warm, (kernel, maxc, step)
        seen_warm += sum(o_warm)
        seen_forced += sum(f for _c, f in oracle)
        books_agree()
        in_flight += [(c, act, mem, conc) for (c, _f), (_ns, act, mem, conc,
                                                       _bb)
                      in zip(oracle, trace) if c >= 0]
        rng.shuffle(in_flight)
        done, in_flight = in_flight[:len(in_flight) // 2], \
            in_flight[len(in_flight) // 2:]
        cols = np.zeros((5, b * 4), np.int32)
        for j, (inv, act, mem, conc) in enumerate(done):
            release(st, inv, act, mem, conc)
            cols[:, j] = (inv, slot_of(f"{act}:{mem}"), mem, conc, 1)
        kstate = rel(kstate, *(jnp.asarray(c) for c in cols[:4]),
                     jnp.asarray(cols[4].astype(bool)))
        books_agree()
    assert seen_forced > 0 and (seen_warm > 0) == (maxc > 1)


def test_kernel_release_roundtrip():
    """schedule then release returns the state to its initial books."""
    st = ShardingPolicyState.build([512] * 8)
    slot_of = _make_slot_allocator()
    trace = _random_trace(5, 64, seed=3, conc_choices=(1, 4), mems=(128, 256))
    batch = _batch_from_trace(st, trace, slot_of)
    kstate0 = init_state(8, [512] * 8, action_slots=64)
    kstate, chosen, forced, _warm = schedule_batch(kstate0, batch)
    chosen = np.asarray(chosen)
    ok = chosen >= 0
    kstate = release_batch(kstate, jnp.asarray(chosen.clip(0)),
                           batch.conc_slot, batch.need_mb, batch.max_conc,
                           jnp.asarray(ok))
    np.testing.assert_array_equal(np.asarray(kstate.free_mb),
                                  np.asarray(kstate0.free_mb))
    np.testing.assert_array_equal(np.asarray(kstate.conc_free),
                                  np.asarray(kstate0.conc_free))


def test_kernel_health_mask_and_no_capacity():
    kstate = init_state(4, [256] * 4, action_slots=8)
    for i in range(4):
        kstate = set_health(kstate, i, False)
    st = ShardingPolicyState.build([256] * 4)
    batch = _batch_from_trace(st, [("ns", "a", 256, 1, False)],
                              _make_slot_allocator())
    _, chosen, forced, _warm = schedule_batch(kstate, batch)
    assert int(chosen[0]) == -1 and not bool(forced[0])


def test_kernel_padding_rows_never_chosen():
    st = ShardingPolicyState.build([256] * 3)
    batch = _batch_from_trace(
        st, [("ns", f"a{i}", 256, 1, False) for i in range(9)],
        _make_slot_allocator())
    kstate = init_state(3, [256] * 3, n_pad=16, action_slots=8)
    _, chosen, forced, _warm = schedule_batch(kstate, batch)
    assert np.asarray(chosen).max() < 3


def test_forced_overcommit_goes_negative_and_recovers():
    st = ShardingPolicyState.build([256] * 2)
    slot_of = _make_slot_allocator()
    trace = [("ns", "a", 256, 1, False)] * 4
    batch = _batch_from_trace(st, trace, slot_of)
    kstate = init_state(2, [256] * 2, action_slots=8)
    kstate, chosen, forced, _warm = schedule_batch(kstate, batch)
    assert np.asarray(forced)[2:].all()
    assert np.asarray(kstate.free_mb).min() < 0  # ForcibleSemaphore overcommit
    # releases heal the books
    kstate = release_batch(kstate, jnp.asarray(np.asarray(chosen).clip(0)),
                           batch.conc_slot, batch.need_mb, batch.max_conc,
                           jnp.ones((4,), bool))
    assert np.asarray(kstate.free_mb).tolist() == [256, 256]


# ---------------------------------------------------------------------------
# sharded (8-device virtual mesh) parity
# ---------------------------------------------------------------------------

class TestShardedParity:
    @pytest.fixture(scope="class")
    def mesh8(self):
        from openwhisk_tpu.parallel import make_mesh
        return make_mesh(8)

    def test_sharded_matches_single_device(self, mesh8):
        from openwhisk_tpu.parallel import (make_sharded_release,
                                            make_sharded_schedule, shard_state)
        st = ShardingPolicyState.build([1024] * 64)
        slot_of = _make_slot_allocator()
        trace = _random_trace(20, 128, seed=11, conc_choices=(1, 4),
                              mems=(128, 256), bb_prob=0.1)
        batch = _batch_from_trace(st, trace, slot_of)

        single = init_state(64, [1024] * 64, action_slots=64)
        s1, c1, f1, w1 = schedule_batch(single, batch)

        sharded0 = shard_state(init_state(64, [1024] * 64, action_slots=64), mesh8)
        sched = make_sharded_schedule(mesh8)
        s2, c2, f2, w2 = sched(sharded0, batch)
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))

        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
        np.testing.assert_array_equal(np.asarray(s1.free_mb),
                                      np.asarray(s2.free_mb))

        # sharded release parity
        rel = make_sharded_release(mesh8)
        ok = np.asarray(c2) >= 0
        s2r = rel(s2, jnp.asarray(np.asarray(c2).clip(0)), batch.conc_slot,
                  batch.need_mb, batch.max_conc, jnp.asarray(ok))
        s1r = release_batch(s1, jnp.asarray(np.asarray(c1).clip(0)),
                            batch.conc_slot, batch.need_mb, batch.max_conc,
                            jnp.asarray(ok))
        np.testing.assert_array_equal(np.asarray(s1r.free_mb),
                                      np.asarray(s2r.free_mb))


# ---------------------------------------------------------------------------
# north-star scale: 64k invokers (BASELINE.json top configuration)
# ---------------------------------------------------------------------------

class TestNorthStarScale:
    def test_mulmod_no_int32_overflow(self):
        """Probe-rank math must survive size * step_inv products past 2**31
        (naive int32 multiply corrupts ~1/3 of ranks at 64k fleet size)."""
        from openwhisk_tpu.ops.placement import _mulmod
        cases = [(65535, 65534), (65536 - 2, 65533), (131072 - 1, 131070),
                 (46349, 46340), (7, 5)]
        for m, b in cases:
            a = np.arange(-m, m, max(1, m // 501), dtype=np.int64)
            want = (a % m * b) % m
            got = np.asarray(_mulmod(jnp.asarray(a, jnp.int32),
                                     jnp.int32(b), jnp.int32(m)),
                             dtype=np.int64)
            np.testing.assert_array_equal(got, want, err_msg=f"m={m} b={b}")

    def test_kernel_matches_oracle_at_64k(self):
        """Sequential-equivalence at the 64k-invoker configuration, with a
        trace that exercises large step inverses."""
        n = 65536
        st = ShardingPolicyState.build([2048] * n)
        slot_of = _make_slot_allocator()
        trace = _random_trace(24, 48, seed=64, conc_choices=(1, 4),
                              mems=(128, 256))
        batch = _batch_from_trace(st, trace, slot_of)
        assert int(np.asarray(batch.step_inv).max()) * (n - 1) > 2**31, \
            "trace does not exercise the overflow regime"
        kstate = init_state(n, [st.invoker_slot_mb(2048)] * n, action_slots=64)
        kstate, chosen, forced, _warm = schedule_batch(kstate, batch)
        oracle = _run_oracle(st, trace)
        for i, ((oc, of), kc, kf) in enumerate(zip(oracle, np.asarray(chosen),
                                                   np.asarray(forced))):
            assert (oc, of) == (int(kc), bool(kf)), \
                f"req {i}: oracle {(oc, of)} vs kernel {(int(kc), bool(kf))}"
        kernel_free = np.asarray(kstate.free_mb)
        oracle_free = np.array([inv.semaphore.available_permits
                                for inv in st.invokers])
        np.testing.assert_array_equal(kernel_free, oracle_free)

    def test_sharded_8way_matches_single_at_64k(self):
        """The 8-shard mesh kernel must agree with the single-device kernel
        at the target fleet size."""
        from openwhisk_tpu.parallel import (make_mesh, make_sharded_schedule,
                                            shard_state)
        n = 65536
        mesh = make_mesh(8)
        st = ShardingPolicyState.build([2048] * n)
        slot_of = _make_slot_allocator()
        trace = _random_trace(16, 32, seed=65, conc_choices=(1,),
                              mems=(128, 256, 512))
        batch = _batch_from_trace(st, trace, slot_of)

        single = init_state(n, [2048] * n, action_slots=32)
        s1, c1, f1, w1 = schedule_batch(single, batch)
        sharded = shard_state(init_state(n, [2048] * n, action_slots=32), mesh)
        s2, c2, f2, w2 = make_sharded_schedule(mesh)(sharded, batch)
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
        np.testing.assert_array_equal(np.asarray(s1.free_mb),
                                      np.asarray(s2.free_mb))
