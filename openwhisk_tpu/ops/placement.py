"""Batched invoker placement on device.

The TPU-native reformulation of the reference's scheduling inner loop
(ShardingContainerPoolBalancer.scala:398-436). The reference probes invokers
one-by-one per activation (home + k*step mod n, step coprime to n). Key
observation: because gcd(step, n) = 1, the probe ORDER is a permutation with
closed-form rank

    rank(i) = (i - home) * step^{-1}  (mod n)

so "first invoker with capacity along the probe sequence" becomes
"argmin(rank) over eligible invokers" — one vectorized reduction over the
fleet instead of a sequential walk. A micro-batch of B activations is then a
`lax.scan` of B such reductions with the capacity state carried through,
which preserves the reference's sequential read-modify-write semantics
exactly (intra-batch contention resolves identically to processing the
requests one at a time).

Two batch algorithms implement those semantics:

  `schedule_batch`        — the reference scan: sequential depth B.
  `schedule_batch_repair` — speculate-and-repair: round 1 probes ALL B
                            requests against the pre-batch state at once,
                            a prefix-conflict detector commits the
                            conflict-free prefix-closure in one shot, and a
                            `lax.while_loop` re-runs only the conflicting
                            residue. Bit-exact with the scan (the fuzz
                            suite asserts it); expected sequential depth
                            collapses from B to the conflict count, which
                            is small when fleet ≫ batch. See the conflict
                            rules on `schedule_batch_repair`.

State (static shapes; fleets grow into padding, SURVEY §7 risk list):
  free_mb   int32[N]     free memory permits per invoker (this controller's
                         shard; may go negative under forced placement, the
                         ForcibleSemaphore over-commit semantics)
  conc_free int32[N, A]  spare intra-container concurrency permits per
                         (invoker, action-slot) — the NestedSemaphore inner
                         level. Slot ids are assigned host-side (collision-
                         free up to A live actions).
  health    bool[N]      usable mask (Healthy; flips fold in from the
                         supervision feed)

Request batch (int32[B] each): partition offset/size (managed vs blackbox
fleet slice), home, step_inv (modular inverse of the coprime step), need_mb,
conc_slot, max_conc, rand (forced-placement choice), valid.

Returns (new_state, chosen int32[B] — global invoker index or -1, forced
bool[B], warm bool[B] — placed on a spare permit of a container the invoker
already holds, the capacity update's own `use_conc`). Overload forces a
random usable invoker (over-commit); no usable invokers -> -1.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp


def _mulmod(a, b, m):
    """(a % m) * b % m without int32 overflow, for b < m <= 2**17.

    The naive product overflows int32 once partition sizes pass ~46k (e.g.
    the 64k-invoker configuration with a large step inverse), corrupting
    probe ranks. Splitting b = hi*512 + lo keeps every intermediate under
    2**26: a' < 2**17, hi < 2**8, lo < 2**9.
    """
    a = jnp.mod(a, m)
    hi = b // 512
    lo = b - hi * 512
    t = jnp.mod(a * hi, m)
    t = jnp.mod(t * 512, m)
    return jnp.mod(t + a * lo, m)


class PlacementState(NamedTuple):
    free_mb: jax.Array    # int32[N]
    conc_free: jax.Array  # int32[N, A]
    health: jax.Array     # bool[N]


class RequestBatch(NamedTuple):
    offset: jax.Array     # int32[B] partition start
    size: jax.Array       # int32[B] partition length
    home: jax.Array       # int32[B] hash % size
    step_inv: jax.Array   # int32[B] inverse of step mod size
    need_mb: jax.Array    # int32[B]
    conc_slot: jax.Array  # int32[B]
    max_conc: jax.Array   # int32[B]
    rand: jax.Array       # int32[B] randomness for forced placement
    valid: jax.Array      # bool[B]


def init_state(n_invokers: int, slot_mb, n_pad: int = 0, action_slots: int = 512
               ) -> PlacementState:
    """Build device state; `slot_mb` is scalar or per-invoker list. Padding
    rows are unhealthy with zero capacity."""
    n_pad = n_pad or n_invokers
    assert n_pad >= n_invokers
    free = jnp.zeros((n_pad,), jnp.int32)
    slot_arr = jnp.broadcast_to(jnp.asarray(slot_mb, jnp.int32), (n_invokers,))
    free = free.at[:n_invokers].set(slot_arr)
    health = jnp.zeros((n_pad,), bool).at[:n_invokers].set(True)
    conc = jnp.zeros((n_pad, action_slots), jnp.int32)
    return PlacementState(free, conc, health)


def set_health(state: PlacementState, idx, usable) -> PlacementState:
    return state._replace(health=state.health.at[jnp.asarray(idx)].set(
        jnp.asarray(usable)))


def _schedule_one(state: PlacementState, req, penalty=None
                  ) -> Tuple[PlacementState, Tuple]:
    """One activation: vectorized probe + capacity update (scan body).

    `penalty` (optional int32[N], small non-negative levels) demotes an
    invoker by one full lap of the probe ring per level: the augmented key
    `rank + penalty * size` keeps the original probe order within a level
    but probes every level-p invoker after all of level p-1. The sentinel
    must then exceed any augmented key, so the penalized path swaps
    `n + 2` for 2^30 (`rank < 2^17` and `penalty` is clipped small by the
    caller, so no int32 overflow). `penalty=None` leaves the trace
    bit-identical to the pre-penalty kernel.
    """
    offset, size, home, step_inv, need, slot, max_conc, rand, valid = req
    n = state.free_mb.shape[0]
    big = jnp.int32(n + 2)

    idx = jnp.arange(n, dtype=jnp.int32)
    local = idx - offset
    in_part = (local >= 0) & (local < size)
    size_safe = jnp.maximum(size, 1)
    # probe-order rank via modular inverse of the coprime step
    rank = _mulmod(local - home, step_inv, size_safe)
    if penalty is not None:
        big = jnp.int32(1 << 30)
        rank = rank + penalty * size_safe

    conc_col = jax.lax.dynamic_index_in_dim(state.conc_free, slot, axis=1,
                                            keepdims=False)
    has_conc = conc_col > 0
    has_mem = state.free_mb >= need
    eligible = in_part & state.health & (has_conc | has_mem)
    key = jnp.where(eligible, rank, big)
    choice = jnp.argmin(key)
    found = key[choice] < big

    # overload: force a usable invoker chosen by a random rotation
    usable = in_part & state.health
    fkey = jnp.where(usable, jnp.mod(local - rand, size_safe), big)
    fchoice = jnp.argmin(fkey)
    have_usable = fkey[fchoice] < big

    sel = jnp.where(found, choice, fchoice)
    placed = valid & (found | have_usable)
    forced = valid & ~found & have_usable

    # capacity update (NestedSemaphore.tryAcquireConcurrent semantics)
    use_conc = placed & (conc_col[sel] > 0)
    take_mem = placed & ~use_conc
    free_mb = state.free_mb.at[sel].add(
        jnp.where(take_mem, -need, 0).astype(jnp.int32))
    conc_delta = jnp.where(use_conc, -1,
                           jnp.where(take_mem & (max_conc > 1), max_conc - 1, 0))
    conc_free = state.conc_free.at[sel, slot].add(conc_delta.astype(jnp.int32))

    out_choice = jnp.where(placed, sel, -1)
    return (PlacementState(free_mb, conc_free, state.health),
            (out_choice, forced, use_conc))


@jax.jit
def schedule_batch(state: PlacementState, batch: RequestBatch, penalty=None
                   ) -> Tuple[PlacementState, jax.Array, jax.Array, jax.Array]:
    """Place a micro-batch sequentially (lax.scan) with vectorized probes.
    `penalty=None` (the production default) traces identically to the
    penalty-free kernel; see `_schedule_one` for the augmented geometry."""
    reqs = (batch.offset, batch.size, batch.home, batch.step_inv,
            batch.need_mb, batch.conc_slot, batch.max_conc, batch.rand,
            batch.valid)
    new_state, (chosen, forced, warm) = jax.lax.scan(
        lambda s, r: _schedule_one(s, r, penalty), state, reqs)
    return new_state, chosen, forced, warm


class RepairPrims(NamedTuple):
    """Index primitives the repair conflict rules are written against.

    The RULES (`repair_commit_masks`) exist exactly once; only these five
    order-sensitive reductions have backend-specific implementations:

      `flat_prims`     — scatter/sort formulations over int32[B] vectors,
                         O(B + key_size) per call: what `schedule_batch_repair`
                         (the XLA kernel) uses.
      `pairwise_prims` — [B, B] mask + reduction formulations over
                         COLUMN-oriented int32[B, 1] vectors: no argsort, no
                         scatter, no gather, no concatenate — the only shapes
                         Mosaic (the Pallas TPU compiler) can lower. O(B^2),
                         which at the balancer's B <= 256 is noise next to the
                         [B, N] probe work.

    Both must agree bit-for-bit (fuzz-asserted by
    tests/test_placement_repair_pallas.py): a drift here is a drift between
    the production kernels.

      bidx                    request's own batch index (same orientation as
                              the vectors the prims consume)
      first_index_where(f, k, size)
                              per request i: does any FLAGGED request j < i
                              share my key?
      any_same_key(f, k, size)
                              per request i: does ANY flagged request (self
                              included) share my key?
      segment_exclusive_sum(v, k)
                              per request i: sum of v[j] over j < i with
                              k[j] == k[i]
      exclusive_cumsum(v)     per request i: sum of v[j] over j < i
      exclusive_cummax(v)     per request i: max of v[j] over j < i (0 when
                              empty; callers pass non-negative values)
      min_index_where(f)      smallest flagged batch index (B when none) —
                              scalar-shaped for broadcasting against bidx
    """
    bidx: jax.Array
    first_index_where: Callable
    any_same_key: Callable
    segment_exclusive_sum: Callable
    exclusive_cumsum: Callable
    exclusive_cummax: Callable
    min_index_where: Callable


def flat_prims(b: int) -> RepairPrims:
    """Scatter/sort prims over flat int32[B] vectors (the XLA repair
    kernel's implementations, unchanged from PR 5)."""
    bidx = jnp.arange(b, dtype=jnp.int32)
    sentinel = jnp.int32(b)

    def first_index_where(flag, key, size):
        # scatter-min of flagged indices onto the key axis, then gather —
        # O(B + size) where the pairwise [B, B] formulation is O(B^2)
        firsts = jnp.full((size,), sentinel).at[key].min(
            jnp.where(flag, bidx, sentinel))
        return firsts[key] < bidx

    def any_same_key(flag, key, size):
        return jnp.zeros((size,), bool).at[key].max(flag)[key]

    def segment_exclusive_sum(values, key):
        # stable sort by key keeps batch order inside each segment; a
        # cummax of the segment-start prefix turns the global cumsum into
        # per-segment exclusive sums
        order = jnp.argsort(key, stable=True)
        v_s = values[order]
        k_s = key[order]
        c = jnp.cumsum(v_s)
        seg_start = jnp.concatenate(
            [jnp.ones((1,), bool), k_s[1:] != k_s[:-1]])
        base = jax.lax.cummax(jnp.where(seg_start, c - v_s, 0))
        return jnp.zeros_like(c).at[order].set(c - v_s - base)

    def exclusive_cumsum(values):
        return jnp.cumsum(values) - values

    def exclusive_cummax(values):
        m = jax.lax.cummax(values)
        return jnp.concatenate([jnp.zeros((1,), m.dtype), m[:-1]])

    def min_index_where(flag):
        return jnp.min(jnp.where(flag, bidx, sentinel))

    return RepairPrims(bidx, first_index_where, any_same_key,
                       segment_exclusive_sum, exclusive_cumsum,
                       exclusive_cummax, min_index_where)


def pairwise_prims(b: int) -> RepairPrims:
    """Sort/scatter-free prims over COLUMN-oriented int32[B, 1] vectors
    (self index on the sublane axis) — every helper is a [B, B] mask plus a
    lane reduction, lowerable by Mosaic inside a Pallas kernel. The [1, B]
    "other request" orientation is derived without a transpose op: mask the
    [B, B] diagonal and reduce the sublane axis."""
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)  # self
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)  # other
    eye = iota_s == iota_l
    before = iota_l < iota_s  # other strictly earlier in batch order
    bidx = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)

    def _row(col):
        # [B, 1] -> [1, B] transpose via diagonal mask + sublane reduction
        return jnp.sum(jnp.where(eye, col.astype(jnp.int32), 0), axis=0,
                       keepdims=True)

    def first_index_where(flag, key, size):
        m = (_row(flag) > 0) & (_row(key) == key) & before
        return jnp.any(m, axis=1, keepdims=True)

    def any_same_key(flag, key, size):
        m = (_row(flag) > 0) & (_row(key) == key)
        return jnp.any(m, axis=1, keepdims=True)

    def segment_exclusive_sum(values, key):
        m = (_row(key) == key) & before
        return jnp.sum(jnp.where(m, _row(values), 0), axis=1, keepdims=True)

    def exclusive_cumsum(values):
        return jnp.sum(jnp.where(before, _row(values), 0), axis=1,
                       keepdims=True)

    def exclusive_cummax(values):
        return jnp.max(jnp.where(before, _row(values), 0), axis=1,
                       keepdims=True)

    def min_index_where(flag):
        return jnp.min(jnp.where(flag, bidx, jnp.int32(b)))

    return RepairPrims(bidx, first_index_where, any_same_key,
                       segment_exclusive_sum, exclusive_cumsum,
                       exclusive_cummax, min_index_where)


def repair_commit_masks(prims: RepairPrims, *, pending, placed, forced, sel,
                        take_mem, use_conc, simple, need_mb, conc_slot,
                        free_at_sel, col_conc, n: int, a_slots: int,
                        slot_ok=None):
    """THE speculate-and-repair conflict rules — the one copy both the XLA
    (`schedule_batch_repair`) and Pallas (`schedule_batch_repair_pallas`)
    kernels execute per round, so the two implementations cannot drift.

    Inputs are this round's speculation results (same orientation as
    `prims.bidx`); returns `(safe, commit)` — the rows whose outcome is
    settled this round and the subset that writes capacity. See
    `schedule_batch_repair`'s docstring for the full exactness argument;
    mechanically:

      * `hard_conflict`: an earlier pending non-cascade writer shares my
        chosen invoker, or an earlier container-opener shares my conc
        column (its permit grant can flip my choice — or un-force me);
      * `mem_conflict`: I take memory (non-forced) at an invoker whose
        free space, after the committed cascade prefix's demand, no longer
        covers my need;
      * everything before the first conflict commits, plus outcome-
        invariant rows (valid-but-unplaceable) and the provably
        order-independent out-of-order commits (`ooo`): past the first
        conflict, i may commit while earlier requests stay unresolved iff
        every such straggler is a pure-memory request, a pessimistic
        budget at sel_i covers all of them plus i, and i's conc write (if
        any) touches no column a straggler probes.

    `slot_ok` (None on the XLA path) marks requests whose conc_slot was in
    range BEFORE clamping: the XLA scatters drop out-of-range keys while
    gathers clamp them, and a caller that pre-clamps (the Pallas kernel,
    whose `pl.ds` reads need in-range starts) passes the mask so the
    slot-keyed writer flags reproduce exactly that drop-write/clamp-read
    behavior."""
    def _w(flag):
        # writer-side validity for slot-keyed helpers (see slot_ok above)
        return flag if slot_ok is None else flag & slot_ok

    writer = pending & placed
    # memory-cascade writers: touch only free_mb[sel], no conc cell
    cascade = writer & take_mem & simple
    hard = writer & ~cascade
    grow = writer & take_mem & ~simple

    hard_conflict = (prims.first_index_where(hard, sel, n)
                     | prims.first_index_where(_w(grow), conc_slot, a_slots))
    prior_mem = prims.segment_exclusive_sum(
        jnp.where(cascade, need_mb, 0), sel).astype(jnp.int32)
    mem_conflict = (take_mem & ~forced
                    & (free_at_sel - prior_mem < need_mb))
    conflict = pending & (hard_conflict | mem_conflict)
    first_bad = prims.min_index_where(conflict)

    # out-of-order commits past the first conflict (see docstring)
    straggler = pending & placed & (prims.bidx >= first_bad)
    grow_potential = prims.any_same_key(_w(pending & ~simple), conc_slot,
                                        a_slots)
    pure = simple & ~col_conc & ~grow_potential
    bad_w = straggler & ~pure
    impure_before = prims.exclusive_cumsum(bad_w.astype(jnp.int32)) > 0
    s_demand = jnp.where(straggler, need_mb, 0)
    demand_before = prims.exclusive_cumsum(s_demand).astype(jnp.int32)
    # the budget must keep sel_i's eligibility bit STABLE for every
    # earlier straggler too (they run before i sequentially, so their
    # re-probe must not observe i's commit flipping has_mem at sel_i):
    # reserve the largest earlier-straggler need on top of their total
    # demand
    max_need_before = prims.exclusive_cummax(s_demand).astype(jnp.int32)
    budget_ok = (~take_mem |
                 (free_at_sel - prior_mem - demand_before
                  - max_need_before >= need_mb))
    conc_write = use_conc | (take_mem & ~simple)
    slot_probed_before = prims.first_index_where(_w(straggler), conc_slot,
                                                 a_slots)
    ooo = (pending & placed & ~forced & ~hard_conflict & ~impure_before
           & budget_ok & ~(conc_write & slot_probed_before))

    # prefix-closure: everything before the first conflict, plus rows
    # whose outcome no commit can change (valid-but-unplaceable; the
    # invalid rows never enter `pending`), plus the proven
    # order-independent commits
    safe = pending & ((prims.bidx < first_bad) | ~placed | ooo)
    return safe, safe & placed


def _probe_geometry(n: int, batch: RequestBatch, penalty=None):
    """The state-INDEPENDENT part of the batch probe, hoisted out of the
    repair loop: partition masks, probe ranks and the forced-placement
    choice (health never changes inside a batch — the fold runs before the
    schedule — so the whole forced path is loop-invariant too... except
    health, which the caller folds in). Returns [B, N] rank/in_part and the
    per-request forced rotation key.

    `penalty` (optional int32[N]) augments the rank by one probe-ring lap
    per penalty level — the loop-invariant seam every repair-family kernel
    (XLA, Pallas, sharded) shares, so threading it here penalizes them all
    identically. The penalized sentinel grows to 2^30 because an augmented
    rank can exceed n + 2; forced-rotation keys stay < size, so the larger
    sentinel is equally correct for them."""
    big = jnp.int32(n + 2)
    idx = jnp.arange(n, dtype=jnp.int32)
    local = idx[None, :] - batch.offset[:, None]          # [B, N]
    size_col = batch.size[:, None]
    in_part = (local >= 0) & (local < size_col)
    size_safe = jnp.maximum(size_col, 1)
    rank = _mulmod(local - batch.home[:, None], batch.step_inv[:, None],
                   size_safe)
    if penalty is not None:
        big = jnp.int32(1 << 30)
        rank = rank + penalty[None, :] * size_safe
    fkey_rot = jnp.mod(local - batch.rand[:, None], size_safe)
    return big, in_part, rank, fkey_rot


@jax.jit
def schedule_batch_repair(state: PlacementState, batch: RequestBatch,
                          penalty=None
                          ) -> Tuple[PlacementState, jax.Array, jax.Array,
                                     jax.Array, jax.Array]:
    """Speculate-and-repair: bit-exact `schedule_batch` semantics with the
    B-length sequential dependency chain collapsed to the conflict count.

    Each round speculates every still-pending request against the current
    state and commits the conflict-free prefix-closure in one scatter. A
    pending request i (speculating invoker `sel`, probing conc column
    `slot`) CONFLICTS — meaning an earlier pending request's commit could
    change its decision — iff one of:

      * an earlier pending NON-cascade writer chose the same invoker
        (its commit touches sel's memory books or i's conc cell), or
      * an earlier pending writer opens a shared container on i's conc
        slot (`take_mem & max_conc > 1` adds permits anywhere in the
        column, which can create a better-ranked eligible invoker — and
        can even flip a would-be-forced request back to a normal
        placement), or
      * i takes memory (non-forced) at an invoker whose free space, after
        the cumulative demand of earlier same-invoker memory-cascade
        writers this round, no longer covers its need ("capacity made
        insufficient by a committed prefix").

    The memory cascade is the exactness refinement that keeps same-action
    bursts parallel: `max_conc <= 1` memory writers touch ONLY
    `free_mb[sel]`, so a run of them on one invoker commits together via
    one accumulated scatter-add as long as the prefix demand still fits —
    exactly the sequential outcome.

    The commit set must respect sequential order: a conflicted request
    re-speculates next round and may then write anywhere, so nothing after
    it may blindly commit. Three classes are provably order-independent
    and commit regardless of position:

      * invalid rows and rows with no usable invoker (outcome invariant
        under any writes), and
      * non-forced placements i past the first conflict for which EVERY
        earlier unresolved request j is a "pure memory" request
        (`max_conc <= 1`, no consumable permit on its column, and no
        pending container-opener on its column that could create one) AND
        a pessimistic budget holds: `free_mb[sel_i]` covers the committing
        cascade demand, the TOTAL demand of those unresolved requests
        (wherever they eventually land — including all of them landing on
        `sel_i`), and `need_i`. Under that budget no memory write in
        either direction can flip an eligibility bit anyone reads, so
        commits commute with the stragglers' later re-runs. Requests that
        write a conc cell additionally require that no unresolved earlier
        request probes the same column (conc writes never commute with
        order-inverted column reads).

    Everything else commits as a strict prefix up to the first conflict.
    The head of the pending order never conflicts, so every round commits
    at least one request and the loop terminates in at most B rounds
    (rare; typically 1 + the depth of the worst per-invoker overflow
    chain).

    Returns (state, chosen, forced, warm, rounds) — `warm` is each row's
    `use_conc` of the round that settled it; `rounds` is the repair-loop
    trip count, exported by the balancer as the loadbalancer_repair_rounds
    summary family.
    """
    b = batch.valid.shape[0]
    prims = flat_prims(b)

    # loop-invariant geometry: ranks, partitions, and the whole forced
    # path (health is fixed inside a batch, and forced placement ignores
    # capacity — `usable` never moves between repair rounds)
    n = state.free_mb.shape[0]
    a_slots = state.conc_free.shape[1]
    big, in_part, rank, fkey_rot = _probe_geometry(n, batch, penalty)
    usable = in_part & state.health[None, :]
    fkey = jnp.where(usable, fkey_rot, big)
    fchoice = jnp.argmin(fkey, axis=1).astype(jnp.int32)
    have_usable = jnp.take_along_axis(fkey, fchoice[:, None], 1)[:, 0] < big
    simple = batch.max_conc <= 1

    def cond(carry):
        _, pending, _, _, _, rounds = carry
        return jnp.any(pending) & (rounds <= b)

    def body(carry):
        state, pending, chosen, forced_acc, warm_acc, rounds = carry
        # per-round speculation: only the capacity-dependent half of the
        # probe re-runs (conc column gather + memory eligibility)
        conc_bn = state.conc_free[:, batch.conc_slot].T   # [B, N]
        has_conc = conc_bn > 0
        eligible = usable & (has_conc
                             | (state.free_mb[None, :]
                                >= batch.need_mb[:, None]))
        key = jnp.where(eligible, rank, big)
        choice = jnp.argmin(key, axis=1).astype(jnp.int32)
        found = jnp.take_along_axis(key, choice[:, None], 1)[:, 0] < big
        sel = jnp.where(found, choice, fchoice)
        placed = batch.valid & (found | have_usable)
        forced = batch.valid & ~found & have_usable
        conc_at_sel = jnp.take_along_axis(conc_bn, sel[:, None], 1)[:, 0]
        use_conc = placed & (conc_at_sel > 0)
        take_mem = placed & ~use_conc
        # any consumable permit on my column inside my partition? (feeds
        # the "pure memory request" predicate)
        col_conc = jnp.any(usable & has_conc, axis=1)
        free_at_sel = state.free_mb[sel]

        # the conflict rules proper live in repair_commit_masks — ONE copy
        # shared with the Pallas repair kernel. Conservative by
        # construction: over-counting demand or purity only defers a
        # commit to a later round, never mis-commits.
        safe, commit = repair_commit_masks(
            prims, pending=pending, placed=placed, forced=forced, sel=sel,
            take_mem=take_mem, use_conc=use_conc, simple=simple,
            need_mb=batch.need_mb, conc_slot=batch.conc_slot,
            free_at_sel=free_at_sel, col_conc=col_conc,
            n=n, a_slots=a_slots)
        dmem = jnp.where(commit & take_mem, batch.need_mb, 0)
        free_mb = state.free_mb.at[sel].add(-dmem.astype(jnp.int32))
        conc_delta = jnp.where(
            commit & use_conc, -1,
            jnp.where(commit & take_mem & ~simple,
                      batch.max_conc - 1, 0))
        conc_free = state.conc_free.at[sel, batch.conc_slot].add(
            conc_delta.astype(jnp.int32))
        chosen = jnp.where(safe, jnp.where(placed, sel, jnp.int32(-1)),
                           chosen)
        forced_acc = forced_acc | (safe & forced)
        warm_acc = warm_acc | (safe & use_conc)
        return (PlacementState(free_mb, conc_free, state.health),
                pending & ~safe, chosen, forced_acc, warm_acc, rounds + 1)

    state, _, chosen, forced, warm, rounds = jax.lax.while_loop(
        cond, body, (state, batch.valid,
                     jnp.full((b,), -1, jnp.int32),
                     jnp.zeros((b,), bool), jnp.zeros((b,), bool),
                     jnp.int32(0)))
    return state, chosen, forced, warm, rounds


def _release_one(state: PlacementState, rel) -> Tuple[PlacementState, Tuple]:
    inv, slot, need, max_conc, valid = rel
    simple = valid & (max_conc <= 1)
    conc_val = state.conc_free[inv, slot] + 1
    reduced = valid & (max_conc > 1) & (conc_val >= max_conc)
    # concurrency release: +1 permit; a full container's worth free ->
    # reduce by max_conc and return the container's memory
    conc_delta = jnp.where(valid & (max_conc > 1),
                           jnp.where(reduced, 1 - max_conc, 1), 0)
    free_delta = jnp.where(simple | reduced, need, 0)
    return PlacementState(
        state.free_mb.at[inv].add(free_delta.astype(jnp.int32)),
        state.conc_free.at[inv, slot].add(conc_delta.astype(jnp.int32)),
        state.health), ()


@jax.jit
def release_batch(state: PlacementState, inv, slot, need_mb, max_conc, valid
                  ) -> PlacementState:
    """Fold a batch of completion releases into the state (ref
    releaseInvoker / NestedSemaphore.releaseConcurrent)."""
    new_state, _ = jax.lax.scan(
        lambda s, r: _release_one(s, r),
        state, (inv, slot, need_mb, max_conc, valid))
    return new_state


@jax.jit
def release_batch_vector(state: PlacementState, inv, slot, need_mb, max_conc,
                         valid) -> PlacementState:
    """Bit-exact `release_batch` with the R-length scan vectorized away —
    the release-side twin of the repair schedule (together they take the
    fused step's sequential depth from 2B to ~the conflict count).

    Exactness argument, by row class:
      * simple rows (`max_conc <= 1`) add memory unconditionally and read
        nothing — one masked scatter-add commutes with everything;
      * concurrency rows group by (invoker, slot). A HOMOGENEOUS group
        (all rows share need/max_conc — the invariant the slot allocator
        maintains, since a slot maps to one action:mem key) evolves the
        permit cell by +1 per release with a wrap of -max_conc whenever it
        reaches max_conc, returning the container's memory. k releases
        from cell value c0 wrap exactly r = clip(floor((c0 + k) /
        max_conc), 0, k) times (the cell+wraps invariant c_t = c0 + t -
        max_conc * r_t makes the wrap count a pure division), so the whole
        group is two scatter-adds;
      * HETEROGENEOUS groups — possible only under slot-overflow
        conflation, where two actions share a hashed slot — replay ALL
        their rows sequentially in batch order under a `lax.while_loop`
        whose trip count is the row count of conflated groups: zero in
        steady state, so the loop body never executes.
    Groups touch disjoint permit cells and memory adds commute, so the
    three classes compose exactly.
    """
    r_len = inv.shape[0]
    bidx = jnp.arange(r_len, dtype=jnp.int32)
    simple = valid & (max_conc <= 1)
    free = state.free_mb.at[inv].add(
        jnp.where(simple, need_mb, 0).astype(jnp.int32))

    conc_row = valid & (max_conc > 1)
    # lexicographic (inv, slot) sort via two stable passes; non-conc rows
    # key to a (-1, -1) sentinel segment that contributes nothing
    ki = jnp.where(conc_row, inv, -1)
    ks = jnp.where(conc_row, slot, -1)
    o1 = jnp.argsort(ks, stable=True)
    o = o1[jnp.argsort(ki[o1], stable=True)]
    ki_s, ks_s = ki[o], ks[o]
    start = jnp.concatenate(
        [jnp.ones((1,), bool),
         (ki_s[1:] != ki_s[:-1]) | (ks_s[1:] != ks_s[:-1])])
    gid = jnp.cumsum(start.astype(jnp.int32)) - 1
    conc_s, need_s, maxc_s = conc_row[o], need_mb[o], max_conc[o]
    k_g = jnp.zeros((r_len,), jnp.int32).at[gid].add(
        conc_s.astype(jnp.int32))
    # the group leader (lowest batch index: stable sorts preserve batch
    # order within a key) defines the group's expected need/max_conc
    fneed = jnp.zeros((r_len,), jnp.int32).at[gid].add(
        jnp.where(start, need_s, 0))
    fmaxc = jnp.zeros((r_len,), jnp.int32).at[gid].add(
        jnp.where(start, maxc_s, 0))
    het_row = conc_s & ((need_s != fneed[gid]) | (maxc_s != fmaxc[gid]))
    het_g = jnp.zeros((r_len,), bool).at[gid].max(het_row)

    inv_s, slot_s = inv[o], slot[o]
    apply_leader = start & conc_s & ~het_g[gid]
    c0 = state.conc_free[inv_s, slot_s]
    k = k_g[gid]
    mx = jnp.maximum(maxc_s, 1)  # sentinel rows: avoid div by <= 0
    wraps = jnp.clip((c0 + k) // mx, 0, k)
    free = free.at[inv_s].add(
        jnp.where(apply_leader, need_s * wraps, 0).astype(jnp.int32))
    conc = state.conc_free.at[inv_s, slot_s].add(
        jnp.where(apply_leader, k - mx * wraps, 0).astype(jnp.int32))

    # heterogeneous residue: EVERY conc row of a conflated group (the
    # leader-matching ones included — the bulk apply skipped the whole
    # group) replays sequentially in batch order; trip count == rows in
    # conflated groups (normally zero)
    het_b = jnp.zeros((r_len,), bool).at[o].set(conc_s & het_g[gid])

    def cond(carry):
        return jnp.any(carry[2])

    def body(carry):
        free, conc, pending = carry
        i = jnp.argmin(jnp.where(pending, bidx, r_len))
        iv, sl = inv[i], slot[i]
        nd, mc = need_mb[i], max_conc[i]
        conc_val = conc[iv, sl] + 1
        reduced = conc_val >= mc
        free = free.at[iv].add(jnp.where(reduced, nd, 0).astype(jnp.int32))
        conc = conc.at[iv, sl].add(
            jnp.where(reduced, 1 - mc, 1).astype(jnp.int32))
        return free, conc, pending.at[i].set(False)

    free, conc, _ = jax.lax.while_loop(cond, body, (free, conc, het_b))
    return PlacementState(free, conc, state.health)


def make_fused_step(release_fn=None, schedule_fn=None):
    """One jitted device program for the balancer's whole step:
    fold releases -> fold health flips -> schedule the micro-batch.

    The three phases as separate calls cost three dispatches per batch
    (dominant at small fleet sizes, where each kernel is ~microseconds);
    fused, XLA compiles them into a single program. Works over any
    (release_fn, schedule_fn) pair — the XLA kernels (default scan or the
    repair kernel), the shard_map'd variants, or the pallas schedule.

    Returns (state, chosen, forced, warm, rounds): schedule kernels
    without a repair loop (scan / pallas / sharded) report rounds == 0.
    """
    release_fn = release_fn or release_batch
    schedule_fn = schedule_fn or schedule_batch

    @jax.jit
    def fused(state: PlacementState, rel_inv, rel_slot, rel_mem, rel_maxc,
              rel_valid, health_idx, health_val, health_valid,
              batch: RequestBatch):
        state = release_fn(state, rel_inv, rel_slot, rel_mem, rel_maxc,
                           rel_valid)
        # masked health fold: padded rows keep their current value
        cur = state.health[health_idx]
        state = state._replace(health=state.health.at[health_idx].set(
            jnp.where(health_valid, health_val, cur)))
        out = schedule_fn(state, batch)
        rounds = out[4] if len(out) > 4 else jnp.int32(0)
        return out[0], out[1], out[2], out[3], rounds

    return fused


def make_release_packed(release_fn=None, donate: bool = False):
    """Release-only fold over the packed int32[5,R] matrix (inv, slot, mem,
    maxc, valid) — the idle-drain counterpart of make_fused_step_packed.
    Returns (state, books): `books` is the post-fold `free_mb` in a buffer
    of its own, which a later donating call cannot consume (what the
    step's output vector carries, see make_fused_step_packed).
    `donate=True` donates the state."""
    release_fn = release_fn or release_batch

    @partial(jax.jit, donate_argnums=((0,) if donate else ()))
    def packed(state: PlacementState, rel):
        state = release_fn(state, rel[0], rel[1], rel[2], rel[3],
                           rel[4].astype(bool))
        return state, jnp.copy(state.free_mb)

    return packed


def make_fused_step_packed(release_fn=None, schedule_fn=None,
                           donate: bool = False):
    """Transfer-packed variant of make_fused_step for the balancer's host
    path. The unpacked signature costs 16 host->device transfers per step
    (8 request columns + 5 release arrays + 3 health arrays) and 2 reads
    back; with a slow device round trip every transfer pays it, so the
    TRANSFER COUNT — not the kernel — dominates the step. Packing collapses
    the inputs to ONE flat int32 buffer (rel [5*R] ++ health [3*H] ++ req
    [9*B] here, [10*B] in the admit variant; split by static shape inside
    the program) and the outputs to ONE int32 vector (`pack_step_output`;
    callers decode with `unpack_step_output(out, B)`): B decision words
    (`pack_decisions`: ((chosen+1)<<3) | warm<<2 | throttled<<1 | forced,
    throttled always 0 here), ONE element carrying the repair-round count
    (0 for schedule kernels without a repair loop), then the POST-step
    `free_mb`, n_pad words. The books ride the step's own output so that a
    step is one program launched and one vector read back: whoever wants
    the post-step books across a later (donating) dispatch — occupancy's
    cache, the flight recorder — slices them off the host copy of this
    vector and never touches `state.free_mb`. On a fleet mesh the append
    is the gather of the row-sharded books.
    R/H/B are static per compile; the balancer's power-of-two bucketing
    bounds the cache-key count.

    `donate=True` donates the state (XLA reuses its buffers for the
    output): the [N, A] concurrency matrix stops round-tripping through
    fresh HBM allocations every step. The caller's input reference is
    INVALIDATED by the call — anything holding the pre-call state (snapshot
    threads, occupancy readers) must copy it first (see TpuBalancer's
    materialize boundaries).
    """
    fused = make_fused_step(release_fn, schedule_fn)

    @partial(jax.jit, static_argnums=(2, 3, 4),
             donate_argnums=((0,) if donate else ()))
    def packed(state: PlacementState, buf, R: int, H: int, B: int):
        # buf int32[5R+3H+9B]:
        #   rel    [5,R]: inv, slot, mem, maxc, valid
        #   health [3,H]: idx, val, mask
        #   req    [9,B]: offset, size, home, step_inv, need_mb,
        #                 conc_slot, max_conc, rand, valid
        rel = buf[:5 * R].reshape(5, R)
        health = buf[5 * R:5 * R + 3 * H].reshape(3, H)
        req = buf[5 * R + 3 * H:].reshape(9, B)
        batch = RequestBatch(req[0], req[1], req[2], req[3], req[4], req[5],
                             req[6], req[7], req[8].astype(bool))
        state, chosen, forced, warm, rounds = fused(
            state, rel[0], rel[1], rel[2], rel[3], rel[4].astype(bool),
            health[0], health[1].astype(bool), health[2].astype(bool), batch)
        return state, pack_step_output(
            pack_decisions(chosen, forced, warm), rounds, state)

    return packed


def make_fused_admit_step_packed(release_fn=None, schedule_fn=None,
                                 donate: bool = False):
    """make_fused_step_packed + device token-bucket admission (ops.throttle):
    the fused program folds releases and health, ADMITS the batch against
    per-namespace buckets (Entitlement.scala:86-153 / RateThrottler.scala as
    a vectorized segmented count — see ops/throttle.py), then schedules only
    the admitted requests. Over-rate requests come back flagged in the
    throttled bit of the decision word and never consume placement capacity.

    req grows a 10th row: ns_slot (the balancer's namespace->bucket index).
    `donate=True` donates the whole (state, buckets) carry.
    """
    from .throttle import admit_batch

    fused = make_fused_step(release_fn, schedule_fn)

    @partial(jax.jit, static_argnums=(3, 4, 5),
             donate_argnums=((0,) if donate else ()))
    def packed(carry, buf, now, R: int, H: int, B: int):
        state, buckets = carry
        rel = buf[:5 * R].reshape(5, R)
        health = buf[5 * R:5 * R + 3 * H].reshape(3, H)
        req = buf[5 * R + 3 * H:].reshape(10, B)
        valid = req[8].astype(bool)
        buckets, admitted = admit_batch(buckets, now, req[9], valid)
        throttled = valid & ~admitted
        batch = RequestBatch(req[0], req[1], req[2], req[3], req[4], req[5],
                             req[6], req[7], admitted)
        state, chosen, forced, warm, rounds = fused(
            state, rel[0], rel[1], rel[2], rel[3], rel[4].astype(bool),
            health[0], health[1].astype(bool), health[2].astype(bool), batch)
        return (state, buckets), pack_step_output(
            pack_decisions(chosen, forced, warm, throttled), rounds, state)

    return packed


def make_shadow_step_packed(release_fn=None, schedule_fn=None):
    """Decision-only counterfactual twin of make_fused_step_packed: same
    packed buffer, same release/health folds, but the schedule runs with an
    augmented probe geometry (`penalty` int32[N]) and NOTHING it computes
    is written back — the caller keeps its live state, this program returns
    only the packed decision vector (`pack_decisions` words, no repair-round
    tail). Never donates: the production step consumes (and may donate) the
    very same state buffers after the shadow has enqueued, so the shadow
    must leave them untouched.

    `schedule_fn(state, batch, penalty)` defaults to the scan kernel;
    callers pass the penalty-aware variant matching their production kernel
    so divergence measures the PENALTY, not a kernel family change.
    """
    release_fn = release_fn or release_batch
    schedule_fn = schedule_fn or schedule_batch

    @partial(jax.jit, static_argnums=(3, 4, 5))
    def shadow(state: PlacementState, buf, penalty, R: int, H: int, B: int):
        rel = buf[:5 * R].reshape(5, R)
        health = buf[5 * R:5 * R + 3 * H].reshape(3, H)
        req = buf[5 * R + 3 * H:].reshape(9, B)
        state = release_fn(state, rel[0], rel[1], rel[2], rel[3],
                           rel[4].astype(bool))
        cur = state.health[health[0]]
        state = state._replace(health=state.health.at[health[0]].set(
            jnp.where(health[2].astype(bool), health[1].astype(bool), cur)))
        batch = RequestBatch(req[0], req[1], req[2], req[3], req[4], req[5],
                             req[6], req[7], req[8].astype(bool))
        out = schedule_fn(state, batch, penalty)
        return pack_decisions(out[1], out[2], out[3])

    return shadow


def make_shadow_admit_step_packed(release_fn=None, schedule_fn=None):
    """Shadow twin of make_fused_admit_step_packed (rate limiting on): the
    admission fold re-runs against the SAME bucket state and `now` as the
    production step — admit_batch is a pure function, so the admitted set
    is identical — but neither the buckets nor the placement state are
    returned. Output encodes throttled like the production step.
    """
    from .throttle import admit_batch

    release_fn = release_fn or release_batch
    schedule_fn = schedule_fn or schedule_batch

    @partial(jax.jit, static_argnums=(4, 5, 6))
    def shadow(carry, buf, penalty, now, R: int, H: int, B: int):
        state, buckets = carry
        rel = buf[:5 * R].reshape(5, R)
        health = buf[5 * R:5 * R + 3 * H].reshape(3, H)
        req = buf[5 * R + 3 * H:].reshape(10, B)
        valid = req[8].astype(bool)
        _, admitted = admit_batch(buckets, now, req[9], valid)
        throttled = valid & ~admitted
        state = release_fn(state, rel[0], rel[1], rel[2], rel[3],
                           rel[4].astype(bool))
        cur = state.health[health[0]]
        state = state._replace(health=state.health.at[health[0]].set(
            jnp.where(health[2].astype(bool), health[1].astype(bool), cur)))
        batch = RequestBatch(req[0], req[1], req[2], req[3], req[4], req[5],
                             req[6], req[7], admitted)
        out = schedule_fn(state, batch, penalty)
        return pack_decisions(out[1], out[2], out[3], throttled)

    return shadow


def pack_decisions(chosen, forced, warm, throttled=None):
    """The step's decision word, one int32 per request (device jnp):
    ((chosen+1)<<3) | warm<<2 | throttled<<1 | forced. `chosen` < 2**17
    (`_mulmod`'s bound) leaves the word far inside int32."""
    out = (((chosen + 1) << 3) | (warm.astype(jnp.int32) << 2)
           | forced.astype(jnp.int32))
    if throttled is not None:
        out = out | (throttled.astype(jnp.int32) << 1)
    return out


def pack_step_output(words, rounds, state: PlacementState):
    """The packed step's ONE output vector (device jnp): B decision words,
    the repair-round count, the post-step books. `unpack_step_output` is
    its decoder; nobody else knows the layout."""
    return jnp.concatenate([words, rounds.reshape(1), state.free_mb])


def unpack_chosen(out):
    """Decode decision words (host numpy or device jnp) -> (chosen int32,
    forced bool, throttled bool). Throttled requests carry chosen == -1
    (they were never scheduled). NOTE: the packed step's output vector
    carries more than its B words: decode it with `unpack_step_output`."""
    return (out >> 3) - 1, (out & 1).astype(bool), ((out >> 1) & 1).astype(bool)


def unpack_warm(out):
    """The decision words' warm bit (host numpy or device jnp) -> bool:
    the request was placed on a spare permit of a container its invoker
    already held, and took no memory."""
    return ((out >> 2) & 1).astype(bool)


def journal_words(out):
    """Decision words as the journal's `ack` records persist them:
    ((chosen+1)<<2) | throttled<<1 | forced, the layout every journal on
    disk and every reader of one has (replay, the time-travel debugger,
    the benchmark's reference). The warm bit is not persisted: replay
    re-derives it with the books, through the same kernels."""
    return ((out >> 3) << 2) | (out & 3)


class StepOutput(NamedTuple):
    """One decoded packed step output (`unpack_step_output`)."""
    chosen: object      # int32[B], -1 = not scheduled
    forced: object      # bool[B]
    throttled: object   # bool[B]
    rounds: object      # repair rounds (an int off a host vector)
    warm: object        # bool[B], `unpack_warm`
    books: object       # int32[n_pad], the post-step free_mb


def unpack_step_output(out, B: int) -> StepOutput:
    """Decode a packed step's whole output vector (`pack_step_output`; host
    numpy or device jnp), `B` being the static request bucket the step ran
    with: words [0, B), the repair-round count at B, the post-step books
    after it. Off a host vector `rounds` is an int and `books` a view of
    `out`; off a device vector everything stays on the device and nothing
    syncs (the balancer's compensation path decodes `chosen` that way)."""
    words = out[:B]
    chosen, forced, throttled = unpack_chosen(words)
    rounds = out[B] if isinstance(out, jax.Array) else int(out[B])
    return StepOutput(chosen, forced, throttled, rounds, unpack_warm(words),
                      out[B + 1:])
