"""Warm-hit parity: the TPU kernel vs the reference scheduling policy.

BASELINE.json's quality bar is >= 95% warm-hit parity with
ShardingContainerPoolBalancer. This tool measures it directly: a simulated
workload (zipf-ish action popularity, schedule/release churn) runs through
BOTH the device kernel (ops.placement) and the CPU oracle
(models.sharding_policy — the reference algorithm), with identical forced-
placement randomness. For each path we track which (invoker, action) pairs
are warm (a prior placement of the action on that invoker still resident)
and report the warm-hit rate plus the fraction of identical decisions.

Because the kernel reproduces the oracle's probe order bit-for-bit
(tests/test_placement_kernel.py asserts exact trace parity), decision parity
is expected to be 1.0 — i.e. warm-hit parity is 100%, not just >= 95%.

    python tests/performance/warmhit.py --invokers 64 --rounds 20 --batch 128
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402


def simulate(n_invokers: int, rounds: int, batch: int, n_actions: int = 32,
             seed: int = 11) -> dict:
    import jax.numpy as jnp

    from openwhisk_tpu.models.sharding_policy import (ShardingPolicyState,
                                                      generate_hash, release,
                                                      schedule)
    from openwhisk_tpu.ops.placement import (RequestBatch, init_state,
                                             release_batch, schedule_batch)

    rng = random.Random(seed)
    mems = [128, 256, 512]
    actions = [(f"ns{a % 4}", f"action{a}", mems[a % 3])
               for a in range(n_actions)]
    # zipf-ish popularity: low action ids dominate, like production mixes
    weights = [1.0 / (a + 1) for a in range(n_actions)]

    st = ShardingPolicyState.build([2048] * n_invokers)
    kstate = init_state(n_invokers, [st.invoker_slot_mb(2048)] * n_invokers,
                        action_slots=max(64, n_actions))

    warm_oracle: set = set()
    warm_kernel: set = set()
    hits_o = hits_k = agree = total = 0
    in_flight: list = []  # (a, oracle_chosen, kernel_chosen)

    for rnd in range(rounds):
        picks = rng.choices(range(n_actions), weights=weights, k=batch)
        cols = {k: np.zeros((batch,), np.int32) for k in
                ("offset", "size", "home", "step_inv", "need_mb", "conc_slot",
                 "max_conc", "rand")}
        oracle_out = []
        for i, a in enumerate(picks):
            ns, act, mem = actions[a]
            offset, size = st.partition(False)
            h = generate_hash(ns, act)
            step = st.step_sizes_managed[h % len(st.step_sizes_managed)]
            frand = (h ^ ((rnd * batch + i) * 2654435761)) % max(size, 1)
            cols["offset"][i] = offset
            cols["size"][i] = size
            cols["home"][i] = h % size
            cols["step_inv"][i] = pow(step, -1, size) if size > 1 else 0
            cols["need_mb"][i] = mem
            cols["conc_slot"][i] = a
            cols["max_conc"][i] = 1
            cols["rand"][i] = frand
            oc, _ = schedule(st, ns, act, mem, forced_rand=frand)
            oracle_out.append(oc if oc is not None else -1)

        rb = RequestBatch(*(jnp.asarray(cols[k]) for k in
                            ("offset", "size", "home", "step_inv", "need_mb",
                             "conc_slot", "max_conc", "rand")),
                          valid=jnp.ones((batch,), bool))
        kstate, chosen, _forced, _warm = schedule_batch(kstate, rb)
        kernel_out = [int(c) for c in np.asarray(chosen)]

        for a, oc, kc in zip(picks, oracle_out, kernel_out):
            total += 1
            agree += (oc == kc)
            if oc >= 0:
                hits_o += ((oc, a) in warm_oracle)
                warm_oracle.add((oc, a))
            if kc >= 0:
                hits_k += ((kc, a) in warm_kernel)
                warm_kernel.add((kc, a))
            if oc >= 0 or kc >= 0:
                in_flight.append((a, oc, kc))

        # churn: release a random half of the in-flight placements on both
        # paths (warm sets keep the affinity — the container stays warm)
        rng.shuffle(in_flight)
        n_rel = len(in_flight) // 2
        rel, in_flight = in_flight[:n_rel], in_flight[n_rel:]
        if rel:
            for a, oc, kc in rel:
                if oc is not None and oc >= 0:
                    ns, act, mem = actions[a]
                    release(st, oc, act, mem)
            inv = jnp.asarray([kc for a, _, kc in rel], jnp.int32)
            slot = jnp.asarray([a for a, _, _ in rel], jnp.int32)
            mem = jnp.asarray([actions[a][2] for a, _, _ in rel], jnp.int32)
            maxc = jnp.ones((len(rel),), jnp.int32)
            valid = jnp.asarray([kc >= 0 for _, _, kc in rel], bool)
            kstate = release_batch(kstate, jnp.clip(inv, 0), slot, mem, maxc,
                                   valid)

    return {
        "metric": "warm_hit_parity",
        "requests": total,
        "oracle_warm_rate": round(hits_o / max(total, 1), 4),
        "kernel_warm_rate": round(hits_k / max(total, 1), 4),
        "decision_parity": round(agree / max(total, 1), 4),
        "target_parity": 0.95,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--invokers", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--actions", type=int, default=32)
    args = ap.parse_args()
    print(json.dumps(simulate(args.invokers, args.rounds, args.batch,
                              args.actions)))


if __name__ == "__main__":
    main()
