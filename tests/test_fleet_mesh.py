"""Sharded-fleet balancer tests (ISSUE 13): the `('fleet',)` mesh kernels
must be BIT-EXACT with the single-device kernels — decisions, forced bits,
books, and repair-round counts — on the 8-way virtual CPU mesh, the
fleet-mesh balancer mode must place identically to the single-device
balancer (off switch = today's path, bit-exact), cluster grow/resize must
classify as expected reshard compiles, the occupancy/admin planes must
aggregate per-shard books host-side, and the calibration cache must key by
per-shard shape."""
import asyncio
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openwhisk_tpu.controller.loadbalancer import HEALTHY, TpuBalancer
from openwhisk_tpu.core.entity import (ActivationId, CodeExec,
                                       ControllerInstanceId, EntityName,
                                       EntityPath, ExecutableWhiskAction,
                                       Identity, InvokerInstanceId, MB,
                                       ActionLimits, MemoryLimit, TimeLimit)
from openwhisk_tpu.core.entity.ids import DocRevision
from openwhisk_tpu.messaging import (ActivationMessage,
                                     MemoryMessagingProvider)
from openwhisk_tpu.ops.placement import (RequestBatch, init_state,
                                         release_batch_vector,
                                         schedule_batch,
                                         schedule_batch_repair)
from openwhisk_tpu.parallel.fleet_mesh import (FLEET_AXIS, fleet_pair,
                                               make_fleet_mesh,
                                               make_fleet_release_vector,
                                               make_fleet_repair_schedule,
                                               mesh_shards, mesh_topology,
                                               shard_state)
from openwhisk_tpu.utils.transaction import TransactionId

pytestmark = pytest.mark.mesh

N_SHARDS = 8


@pytest.fixture(scope="module")
def mesh():
    return make_fleet_mesh(N_SHARDS)


def _rand_batch(rng, n, b, *, need=None, maxc_pool=(1, 1, 1, 4),
                slots=16, invalid_frac=0.1):
    """A random request batch over the whole [0, n) partition — mixed
    memory needs, shared-container actions (max_conc > 1), some invalid
    rows, randomized forced-placement rotations."""
    return RequestBatch(
        offset=jnp.zeros(b, jnp.int32),
        size=jnp.full(b, n, jnp.int32),
        home=jnp.asarray(rng.randint(0, n, b), jnp.int32),
        step_inv=jnp.ones(b, jnp.int32),
        need_mb=jnp.asarray(need if need is not None
                            else rng.choice([128, 256, 512], b), jnp.int32),
        conc_slot=jnp.asarray(rng.randint(0, slots, b), jnp.int32),
        max_conc=jnp.asarray(rng.choice(maxc_pool, b), jnp.int32),
        rand=jnp.asarray(rng.randint(0, n, b), jnp.int32),
        valid=jnp.asarray(rng.rand(b) > invalid_frac))


def _dirty_state(rng, n, slots=16, slot_mb=2048):
    """A partially-occupied state: random memory holds, random open
    containers with spare permits, a few unhealthy rows."""
    free = jnp.asarray(
        slot_mb - rng.choice([0, 128, 256, 1024], n), jnp.int32)
    conc = np.zeros((n, slots), np.int32)
    for _ in range(n // 2):
        conc[rng.randint(0, n), rng.randint(0, slots)] = rng.randint(1, 4)
    health = jnp.asarray(rng.rand(n) > 0.1)
    return init_state(n, [slot_mb] * n, n_pad=n, action_slots=slots
                      )._replace(free_mb=free, conc_free=jnp.asarray(conc),
                                 health=health)


def _same(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def _states_equal(s1, s2):
    return (_same(s1.free_mb, s2.free_mb)
            and _same(s1.conc_free, s2.conc_free)
            and _same(s1.health, s2.health))


class TestFleetKernelParity:
    """The tentpole acceptance: sharded decisions, books AND round counts
    bit-identical to the single-device repair kernel on the 8-way virtual
    mesh — mixed traffic, forced overload, container-open permits,
    invalid rows, releases, chained steps."""

    def test_repair_parity_fuzz(self, mesh):
        sched = make_fleet_repair_schedule(mesh)
        rng = np.random.RandomState(7)
        for n, b in [(16, 8), (32, 32), (64, 64), (128, 96)]:
            for trial in range(3):
                st = _dirty_state(rng, n)
                batch = _rand_batch(rng, n, b)
                s1, c1, f1, w1, r1 = schedule_batch_repair(st, batch)
                s2, c2, f2, w2, r2 = sched(shard_state(st, mesh), batch)
                assert _same(c1, c2), (n, b, trial)
                assert _same(f1, f2), (n, b, trial)
                assert _same(w1, w2), (n, b, trial)
                assert _states_equal(s1, s2), (n, b, trial)
                assert int(r1) == int(r2), (n, b, trial)

    def test_forced_overload_parity(self, mesh):
        """Needs far beyond capacity: every placement forces (over-commit
        books go negative) — the forced-candidate election must match the
        single-device argmin exactly."""
        sched = make_fleet_repair_schedule(mesh)
        rng = np.random.RandomState(11)
        n, b = 32, 48
        st = _dirty_state(rng, n)
        batch = _rand_batch(rng, n, b, need=np.full(b, 1900, np.int32),
                            maxc_pool=(1,))
        s1, c1, f1, w1, r1 = schedule_batch_repair(st, batch)
        s2, c2, f2, w2, r2 = sched(shard_state(st, mesh), batch)
        assert bool(np.asarray(f1).any()), "protocol must actually force"
        assert _same(c1, c2) and _same(f1, f2) and _same(w1, w2)
        assert _states_equal(s1, s2) and int(r1) == int(r2)

    def test_container_open_burst_parity(self, mesh):
        """Same-action bursts opening shared containers (max_conc > 1):
        the permit-grant cascade is the hardest conflict class — permits
        minted by an earlier commit can flip a later request's choice."""
        sched = make_fleet_repair_schedule(mesh)
        rng = np.random.RandomState(13)
        n, b = 32, 64
        st = init_state(n, [2048] * n, n_pad=n, action_slots=16)
        batch = _rand_batch(rng, n, b, maxc_pool=(4,), slots=4,
                            invalid_frac=0.0)
        s1, c1, f1, w1, r1 = schedule_batch_repair(st, batch)
        s2, c2, f2, w2, r2 = sched(shard_state(st, mesh), batch)
        assert _same(c1, c2) and _same(f1, f2) and _same(w1, w2)
        assert _states_equal(s1, s2) and int(r1) == int(r2)

    def test_release_vector_parity_incl_conflation(self, mesh):
        """The owner-masked vector release, including the heterogeneous
        slot-conflation residue (two actions sharing one hashed slot with
        different need/max_conc replay sequentially)."""
        rel = make_fleet_release_vector(mesh)
        rng = np.random.RandomState(17)
        n, r = 32, 48
        st = _dirty_state(rng, n)
        inv = jnp.asarray(rng.randint(0, n, r), jnp.int32)
        slot = jnp.asarray(rng.randint(0, 4, r), jnp.int32)
        need = jnp.asarray(rng.choice([128, 256], r), jnp.int32)
        maxc = jnp.asarray(rng.choice([1, 4, 6], r), jnp.int32)
        valid = jnp.asarray(rng.rand(r) > 0.15)
        s1 = release_batch_vector(st, inv, slot, need, maxc, valid)
        s2 = rel(shard_state(st, mesh), inv, slot, need, maxc, valid)
        assert _states_equal(s1, s2)

    def test_chained_steps_with_releases_parity(self, mesh):
        """Several fused-style rounds: schedule, then release what placed,
        then schedule again on the dirtied books — covers the production
        steady state where both kernels run back to back."""
        sched = make_fleet_repair_schedule(mesh)
        rel = make_fleet_release_vector(mesh)
        rng = np.random.RandomState(19)
        n, b = 64, 48
        st1 = init_state(n, [2048] * n, n_pad=n, action_slots=16)
        st2 = shard_state(st1, mesh)
        for step in range(4):
            batch = _rand_batch(rng, n, b)
            st1, c1, f1, w1, r1 = schedule_batch_repair(st1, batch)
            st2, c2, f2, w2, r2 = sched(st2, batch)
            assert _same(c1, c2) and _same(w1, w2), step
            assert int(r1) == int(r2), step
            inv = jnp.asarray(np.clip(np.asarray(c1), 0, None), jnp.int32)
            ok = jnp.asarray(np.asarray(c1) >= 0)
            st1 = release_batch_vector(st1, inv, batch.conc_slot,
                                       batch.need_mb, batch.max_conc, ok)
            st2 = rel(st2, inv, batch.conc_slot, batch.need_mb,
                      batch.max_conc, ok)
            assert _states_equal(st1, st2), step

    def test_scan_pair_parity(self, mesh):
        """fleet_pair('scan') keeps the prototype sharded scan — parity
        with the single-device scan (the legacy mesh path, still exact)."""
        sched, rel, resolved = fleet_pair(mesh, "scan")
        assert resolved == "scan"
        rng = np.random.RandomState(23)
        n, b = 32, 24
        st = _dirty_state(rng, n)
        batch = _rand_batch(rng, n, b)
        s1, c1, f1, w1 = schedule_batch(st, batch)
        out = sched(shard_state(st, mesh), batch)
        s2, c2, f2, w2 = out
        assert _same(c1, c2) and _same(f1, f2) and _same(w1, w2)
        assert _states_equal(s1, s2)

    def test_auto_pair_is_per_bucket_hybrid(self, mesh):
        """kernel_choice's mesh leg under placement_kernel="auto" routes
        by static batch width like every backend: scan below
        REPAIR_MIN_BATCH (rounds absent/0), repair at and above it
        (rounds >= 1) — both bit-exact with the oracle."""
        from openwhisk_tpu.controller.loadbalancer.kernel_choice import \
            choose
        plan = choose(32, 64, 64, placement_kernel="auto", mesh=mesh)
        sched, rel, resolved = plan.schedule, plan.release, plan.algorithm
        assert plan.backend == "sharded" and resolved == "repair"
        assert getattr(sched, "_placement_hybrid", False)
        rng = np.random.RandomState(29)
        n = 32
        st = _dirty_state(rng, n)
        small = _rand_batch(rng, n, 8)
        big = _rand_batch(rng, n, 64)
        out_small = sched(shard_state(st, mesh), small)
        assert len(out_small) == 4  # the scan pair: no rounds element
        s1, c1, _f1, _w1 = schedule_batch(st, small)
        assert _same(c1, out_small[1])
        out_big = sched(shard_state(st, mesh), big)
        s2, c2, _f2, _w2, r2 = schedule_batch_repair(st, big)
        assert _same(c2, out_big[1]) and int(out_big[4]) == int(r2)

    def test_grow_reshard_continues_bit_exact(self, mesh):
        """Fleet growth = reshard: re-pad the invoker axis (holds
        preserved), reshard onto the same mesh, and keep placing — books
        and decisions must track the single-device kernel through the
        resize."""
        sched = make_fleet_repair_schedule(mesh)
        rng = np.random.RandomState(31)
        n1, n2, b = 32, 64, 24
        st1 = _dirty_state(rng, n1)
        st2 = shard_state(st1, mesh)
        batch = _rand_batch(rng, n1, b)
        st1, c1, _f, _w, _r = schedule_batch_repair(st1, batch)
        st2, c2, _f2, _w2, _r2 = sched(st2, batch)
        assert _same(c1, c2)

        def grow(st, pad):
            free = np.zeros((pad,), np.int32)
            free[:n1] = np.asarray(st.free_mb)
            conc = np.zeros((pad, st.conc_free.shape[1]), np.int32)
            conc[:n1] = np.asarray(st.conc_free)
            health = np.zeros((pad,), bool)
            health[:n1] = np.asarray(st.health)
            # the new rows come up healthy at full capacity (registration)
            free[n1:] = 2048
            health[n1:] = True
            from openwhisk_tpu.ops.placement import PlacementState
            return PlacementState(jnp.asarray(free), jnp.asarray(conc),
                                  jnp.asarray(health))

        st1 = grow(st1, n2)
        st2 = shard_state(grow(st2, n2), mesh)
        batch2 = _rand_batch(rng, n2, b)
        st1, c1, _f, _w, r1 = schedule_batch_repair(st1, batch2)
        st2, c2, _f2, _w2, r2 = sched(st2, batch2)
        assert _same(c1, c2) and int(r1) == int(r2)
        assert _states_equal(st1, st2)


# -- balancer level ---------------------------------------------------------

def _make_action(name="act", memory=256):
    a = ExecutableWhiskAction(EntityPath("guest"), EntityName(name),
                              CodeExec(kind="python:3", code="x"),
                              limits=ActionLimits(TimeLimit(5000),
                                                  MemoryLimit(MB(memory))))
    a.rev = DocRevision("1-b")
    return a


def _make_msg(action, ident):
    return ActivationMessage(TransactionId(), action.fully_qualified_name,
                             action.rev.rev, ident, ActivationId.generate(),
                             ControllerInstanceId("0"), False, {})


def _mk_balancer(provider, **kw):
    kw.setdefault("managed_fraction", 1.0)
    kw.setdefault("blackbox_fraction", 0.0)
    kw.setdefault("prewarm", False)
    kw.setdefault("initial_pad", 16)
    kw.setdefault("max_batch", 32)
    return TpuBalancer(provider, ControllerInstanceId("0"), **kw)


async def _drive(bal, n_invokers=12, waves=3, per_wave=40):
    """Register a fleet directly, publish identical traffic, and return
    the placement decisions in PUBLISH order plus the final books."""
    placed = {}

    async def fake_send(msg, invoker):
        placed[msg.activation_id.asString] = invoker.instance

    bal.send_activation_to_invoker = fake_send
    for i in range(n_invokers):
        bal._status_change(InvokerInstanceId(i, user_memory=MB(2048)),
                           HEALTHY)
    ident = Identity.generate("guest")
    actions = [_make_action(f"fm{i}", memory=[128, 256, 512][i % 3])
               for i in range(10)]
    ordered = []
    for _ in range(waves):
        msgs = [_make_msg(actions[i % 10], ident) for i in range(per_wave)]
        ordered += [m.activation_id.asString for m in msgs]
        await asyncio.gather(*[bal.publish(actions[i % 10], m)
                               for i, m in enumerate(msgs)])
    books = np.asarray(bal.state.free_mb).tolist()
    return [placed[a] for a in ordered], books


class TestFleetBalancer:
    def test_fleet_mode_places_like_single_device(self):
        """The production acceptance: identical publish traffic through
        the fleet-mesh balancer and the single-device balancer yields
        identical placements and identical books (the off switch IS the
        single-device path, so this is also the off-switch bit-exactness
        proof)."""
        async def go(fleet_mesh):
            bal = _mk_balancer(MemoryMessagingProvider(),
                               fleet_mesh=fleet_mesh,
                               fleet_shards=N_SHARDS)
            if fleet_mesh:
                assert bal.kernel_resolved == "sharded"
                assert bal.n_shards == N_SHARDS
                assert bal.fleet_axis == FLEET_AXIS
            else:
                assert bal.mesh is None and bal.n_shards == 1
            try:
                return await _drive(bal)
            finally:
                await bal.close()

        d_off, b_off = asyncio.run(go(False))
        d_on, b_on = asyncio.run(go(True))
        assert d_on == d_off, "fleet-mesh placements must be bit-exact"
        assert b_on == b_off, "fleet-mesh books must be bit-exact"

    def test_env_knob_builds_the_mesh(self, monkeypatch):
        monkeypatch.setenv("CONFIG_whisk_loadBalancer_fleetMesh", "true")
        monkeypatch.setenv("CONFIG_whisk_loadBalancer_fleetShards",
                           str(N_SHARDS))
        bal = _mk_balancer(MemoryMessagingProvider())
        assert bal.n_shards == N_SHARDS
        assert bal.fleet_axis == FLEET_AXIS
        asyncio.run(bal.close())

    def test_growth_resharding_classifies_expected(self):
        """Cluster grow = reshard event: registrations past the pad force
        a re-pad + reshard mid-traffic; every compile must classify
        expected (the PR 3 watchdog contract) and placement must keep
        working across the reshard."""
        async def go():
            os.environ["CONFIG_whisk_profiling_enabled"] = "true"
            try:
                bal = _mk_balancer(MemoryMessagingProvider(),
                                   fleet_mesh=True,
                                   fleet_shards=N_SHARDS)
            finally:
                os.environ.pop("CONFIG_whisk_profiling_enabled", None)
            placed = {}

            async def fake_send(msg, invoker):
                placed[msg.activation_id.asString] = invoker.instance

            bal.send_activation_to_invoker = fake_send
            for i in range(12):
                bal._status_change(
                    InvokerInstanceId(i, user_memory=MB(2048)), HEALTHY)
            ident = Identity.generate("guest")
            a = _make_action("grow", memory=128)
            await asyncio.gather(*[bal.publish(a, _make_msg(a, ident))
                                   for _ in range(12)])
            # grow past initial_pad=16 -> _grow_padding -> reshard
            for i in range(12, 20):
                bal._status_change(
                    InvokerInstanceId(i, user_memory=MB(2048)), HEALTHY)
            assert bal._n_pad == 32
            assert bal._n_pad % bal.n_shards == 0
            await asyncio.gather(*[bal.publish(a, _make_msg(a, ident))
                                   for _ in range(12)])
            prof = bal.kernel_profile()
            await bal.close()
            return prof, len(placed)

        prof, n_placed = asyncio.run(go())
        assert n_placed == 24
        assert prof["compiles"]["unexpected"] == 0
        assert any(c["reason"] == "reshard"
                   for c in prof["compiles"]["log"]), \
            "the re-pad compiles must classify under the reshard window"
        assert prof["mesh"] == {"n_shards": N_SHARDS, "axis": FLEET_AXIS}

    def test_occupancy_shards_block_and_gauges(self):
        """The admin/occupancy planes aggregate per-shard books from the
        HOST cache (never a device sync): the shard rows must sum to the
        fleet totals, and the supervision-tick gauges must export the
        shard count and per-shard ratios."""
        async def go():
            bal = _mk_balancer(MemoryMessagingProvider(), fleet_mesh=True,
                               fleet_shards=N_SHARDS)
            try:
                await _drive(bal, waves=1)
                occ = bal.occupancy()
                assert occ["mesh"] == {"n_shards": N_SHARDS,
                                       "axis": FLEET_AXIS}
                shards = occ["shards"]
                assert len(shards) == N_SHARDS
                assert sum(s["capacity_mb"] for s in shards) == \
                    occ["fleet"]["capacity_mb"]
                assert sum(s["used_mb"] for s in shards) == \
                    occ["fleet"]["used_mb"]
                assert sum(s["invokers"] for s in shards) == 12
                # API-path contract: serving occupancy never syncs device
                assert bal.OCCUPANCY_SYNCS_DEVICE is False
                bal._telemetry_tick()
                assert bal.metrics.gauge_value(
                    "loadbalancer_fleet_shards") == N_SHARDS
                for s in range(N_SHARDS):
                    assert bal.metrics.gauge_value(
                        "loadbalancer_shard_occupancy_ratio",
                        tags={"shard": str(s)}) is not None
            finally:
                await bal.close()

        asyncio.run(go())

    def test_snapshot_reshards_across_topologies(self):
        """Snapshots carry GLOBAL books: a single-device snapshot restores
        onto the mesh (deterministic reshard) and a mesh snapshot restores
        onto a single device — books preserved both ways, `fleet_shards`
        recorded."""
        async def go():
            single = _mk_balancer(MemoryMessagingProvider())
            await _drive(single, waves=1)
            snap1 = single.snapshot()
            assert snap1["fleet_shards"] == 1
            books1 = np.asarray(single.state.free_mb)[:12]
            await single.close()

            meshy = _mk_balancer(MemoryMessagingProvider(),
                                 fleet_mesh=True, fleet_shards=N_SHARDS)
            meshy.restore(snap1)
            assert _same(np.asarray(meshy.state.free_mb)[:12], books1)
            await _drive(meshy, waves=1)
            snap2 = meshy.snapshot()
            assert snap2["fleet_shards"] == N_SHARDS
            books2 = np.asarray(meshy.state.free_mb)[:12]
            await meshy.close()

            back = _mk_balancer(MemoryMessagingProvider())
            back.restore(snap2)
            assert _same(np.asarray(back.state.free_mb)[:12], books2)
            await back.close()

        asyncio.run(go())


class TestMeshTopologyHelpers:
    def test_mesh_topology_record(self, mesh):
        topo = mesh_topology(mesh)
        assert topo["n_shards"] == N_SHARDS
        assert topo["axis"] == FLEET_AXIS
        assert mesh_topology(None) == {"n_shards": 1, "axis": None}

    def test_make_fleet_mesh_rejects_non_pow2(self):
        with pytest.raises(ValueError):
            make_fleet_mesh(6)

    def test_make_fleet_mesh_default_pow2_floors(self, mesh):
        assert mesh_shards(make_fleet_mesh(None)) in (8, 4, 2, 1)
        # 0 is the knob's documented "all devices" value — same floor,
        # never the explicit-count validation path
        assert mesh_shards(make_fleet_mesh(0)) == \
            mesh_shards(make_fleet_mesh(None))


class TestFleetSweepRider:
    def test_sweep_row_parity_census_and_heal(self):
        """Satellite: the bench rider's in-process body on the virtual
        mesh — parity column true, MULTICHIP heal check folded in, zero
        unexpected recompiles, n_devices/mesh_axis recorded."""
        import bench
        out = bench._sharded_fleet_sweep_measure(
            fleet_sizes=(64,), n_devices=N_SHARDS, batch_size=32,
            iters=2, repeats=1)
        assert out["n_devices"] == N_SHARDS
        assert out["mesh_axis"] == FLEET_AXIS
        assert out["parity_all"] is True
        assert out["recompiles_unexpected"] == 0
        row = out["rows"][0]
        assert row["shard_rows"] == 64 // N_SHARDS
        assert row["books_heal"] is True
        assert row["rate_median"] > 0
