"""Device token-bucket admission tests (ops.throttle)."""
import jax.numpy as jnp
import numpy as np

from openwhisk_tpu.ops.throttle import admit_batch, init_buckets


def test_burst_then_throttle_then_refill():
    st = init_buckets(4, rate_per_minute=60)  # 1 token/s, burst 60
    ns = jnp.zeros((64,), jnp.int32)
    valid = jnp.ones((64,), bool)
    st, admitted = admit_batch(st, jnp.float32(0.0), ns, valid)
    assert int(np.asarray(admitted).sum()) == 60  # burst drained
    st, admitted = admit_batch(st, jnp.float32(0.5), ns, valid)
    assert int(np.asarray(admitted).sum()) == 0   # no refill yet
    st, admitted = admit_batch(st, jnp.float32(10.5), ns, valid)
    assert int(np.asarray(admitted).sum()) == 10  # 10 s -> 10 tokens


def test_namespaces_isolated():
    st = init_buckets(2, rate_per_minute=120)
    ns = jnp.asarray([0] * 8 + [1] * 8, jnp.int32)
    st, admitted = admit_batch(st, jnp.float32(0.0), ns, jnp.ones((16,), bool))
    assert np.asarray(admitted).all()
    tokens = np.asarray(st.tokens)
    assert tokens[0] == tokens[1] == 120 - 8


def test_intra_batch_contention():
    st = init_buckets(1, rate_per_minute=60)
    # drain to 3 tokens
    st = st._replace(tokens=jnp.asarray([3.0], jnp.float32))
    ns = jnp.zeros((8,), jnp.int32)
    st, admitted = admit_batch(st, jnp.float32(0.0), ns, jnp.ones((8,), bool))
    a = np.asarray(admitted)
    assert a[:3].all() and not a[3:].any()  # first 3 in batch order win


def test_invalid_rows_ignored():
    st = init_buckets(1, rate_per_minute=60)
    ns = jnp.zeros((4,), jnp.int32)
    valid = jnp.asarray([True, False, True, False])
    st, admitted = admit_batch(st, jnp.float32(0.0), ns, valid)
    assert np.asarray(admitted).tolist() == [True, False, True, False]
    assert float(np.asarray(st.tokens)[0]) == 58.0


class TestDeviceAdmissionInBalancer:
    """r5: admit_batch fused into the TpuBalancer placement step
    (--balancer-rate-limit). Parity vs the entitlement RateThrottler's
    behavior: a burst up to the limit admits, the next request rejects
    with a throttle (429-mapped) error, and no capacity leaks."""

    def test_over_rate_publishes_throttled_and_leak_free(self):
        import asyncio

        import numpy as np

        from openwhisk_tpu.controller.loadbalancer import (
            LoadBalancerThrottleException, TpuBalancer)
        from openwhisk_tpu.core.entity import ControllerInstanceId, Identity
        from openwhisk_tpu.messaging import MemoryMessagingProvider
        from tests.test_balancers import (_fleet, _ping_all, make_action,
                                          make_msg)

        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              batch_window=0.002, max_batch=16,
                              rate_limit_per_minute=5)
            await bal.start()
            invokers, producer = await _fleet(provider, 2)
            await _ping_all(invokers, producer)
            free0 = np.asarray(bal.state.free_mb).copy()
            ident = Identity.generate("guest")
            action = make_action("ratelimited", memory=128)

            async def one():
                try:
                    p = await bal.publish(action,
                                          make_msg(action, ident, True))
                    await p
                    return "ok"
                except LoadBalancerThrottleException:
                    return "throttled"

            # a 12-deep burst against a 5/min bucket: exactly 5 admitted
            results = await asyncio.gather(*[one() for _ in range(12)])
            # drain releases so the books settle
            for _ in range(100):
                await asyncio.sleep(0.01)
                if (sum(bal._slots.refcount.values()) == 0
                        and (np.asarray(bal.state.free_mb) == free0).all()):
                    break
            leaked = sum(bal._slots.refcount.values())
            free_ok = (np.asarray(bal.state.free_mb) == free0).all()
            throttle_count = bal.metrics.counter_value(
                "loadbalancer_device_throttled")
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return results, leaked, free_ok, throttle_count

        results, leaked, free_ok, throttle_count = asyncio.run(go())
        assert results.count("ok") == 5
        assert results.count("throttled") == 7
        assert throttle_count == 7
        assert leaked == 0 and free_ok

    def test_overflow_namespaces_stay_in_shared_subrange(self):
        """Regression (ISSUE 1 satellite): once the dedicated rate buckets
        fill, overflow namespaces must hash into the RESERVED shared tail
        sub-range — never onto a dedicated tenant's bucket, where their
        traffic would drain that tenant's tokens."""
        from openwhisk_tpu.controller.loadbalancer import TpuBalancer
        from openwhisk_tpu.core.entity import ControllerInstanceId
        from openwhisk_tpu.messaging import MemoryMessagingProvider

        bal = TpuBalancer(MemoryMessagingProvider(),
                          ControllerInstanceId("0"),
                          rate_limit_per_minute=60)
        dedicated = bal.RATE_NS_BUCKETS - bal.RATE_NS_SHARED_BUCKETS
        for i in range(dedicated):
            assert bal._ns_slot(f"tenant{i}") == i  # dedicated, memoized
        # every overflow namespace lands in [dedicated, RATE_NS_BUCKETS)
        overflow_slots = {bal._ns_slot(f"overflow{i}") for i in range(500)}
        assert all(dedicated <= s < bal.RATE_NS_BUCKETS
                   for s in overflow_slots)
        # dedicated tenants keep their original buckets
        assert bal._ns_slot("tenant0") == 0
        assert bal._ns_slot(f"tenant{dedicated - 1}") == dedicated - 1

    def test_bucket_state_survives_rebuilds(self):
        """Regression (ISSUE 1 satellite): _build_packed_fns must CARRY the
        live token-bucket state through kernel swaps / growth rebuilds —
        re-initializing would grant a fresh full burst mid-minute."""
        import numpy as np

        from openwhisk_tpu.controller.loadbalancer import TpuBalancer
        from openwhisk_tpu.core.entity import ControllerInstanceId
        from openwhisk_tpu.messaging import MemoryMessagingProvider

        bal = TpuBalancer(MemoryMessagingProvider(),
                          ControllerInstanceId("0"),
                          rate_limit_per_minute=60)
        st = bal._bucket_state
        assert st is not None
        # drain the buckets, then force the rebuild paths
        bal._bucket_state = st._replace(tokens=st.tokens * 0.0)
        bal.update_cluster(2)            # _init_device_state -> rebuild
        assert float(np.asarray(bal._bucket_state.tokens).max()) == 0.0
        bal._adopt_plan(bal._choose_plan(), rebuild=True)  # as a swap does
        assert float(np.asarray(bal._bucket_state.tokens).max()) == 0.0

    def test_refill_readmits_like_rate_window(self):
        """After the window passes, the budget returns (RateThrottler's
        rolling-minute behavior; the bucket refills continuously at
        limit/60 per second)."""
        import jax.numpy as jnp

        from openwhisk_tpu.ops.throttle import admit_batch, init_buckets

        st = init_buckets(4, rate_per_minute=6)  # 0.1 tokens/s
        ns = jnp.zeros((6,), jnp.int32)
        valid = jnp.ones((6,), bool)
        st, admitted = admit_batch(st, jnp.float32(0.0), ns, valid)
        assert admitted.all()  # burst == limit admits, like the window
        st, admitted = admit_batch(st, jnp.float32(1.0), ns, valid)
        assert not admitted.any()  # immediately after: rejected
        st, admitted = admit_batch(st, jnp.float32(61.0), ns, valid)
        assert admitted.all()  # a minute later the full budget is back
