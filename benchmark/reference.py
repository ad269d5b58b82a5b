"""The plain reference of the placement policy, and the comparison that
decides `correct`.

The policy is upstream's ShardingContainerPoolBalancer in plain Python (a
copy of the semantics of `openwhisk_tpu/models/sharding_policy.py` and
`utils/semaphores.py`, kept here so that no later PR can change the
yardstick; it imports nothing of the program): home invoker = hash %
fleet, probe in a step coprime to the fleet size, first invoker with free
memory wins, total overload forces a rotation-picked usable invoker and
over-commits it. An invoker's books have upstream's two levels
(NestedSemaphore): its free memory, and per ACTION ("<fqn>:<MB>", never the
program's slot) the spare permits of the containers it holds of an action
whose concurrency C is over 1. Such an action fits where it has a spare
permit or the memory for a new container; a new container takes the memory
and mints C - 1 spares; a release returns one permit, and when C are spare
again one container's memory goes back. C = 1 is the memory alone.

From the program's run the comparison takes only ORDER, RANDOMNESS and
SLOTS: the order of step inputs (which activation ids were scheduled in
which step, which release rows and health flips were folded before it), the
random number the program drew for a forced placement, and the slot (the
column of its dense permit table) each scheduled activation carries. A
release row names (invoker, slot, MB, maxc); the reference keeps its own
record of which action holds which slot while activations of it are in
flight and translates the row through it. Names, memory and concurrency of
each activation come from the benchmark's own catalogue, and the journaled
health flips are held to the fleet's own truth: the benchmark's invokers
are all up and pinging, so `unusable` counts any of them the books hold
unusable at a step of the window.
"""
from __future__ import annotations

import base64
import math
import zlib
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

MIN_SLOT_MB = 128


def generate_hash(namespace: str, fqn: str) -> int:
    return zlib.crc32(f"{namespace}/{fqn}".encode()) & 0x7FFFFFFF


def pairwise_coprimes(x: int) -> List[int]:
    out: List[int] = []
    for cur in range(1, x + 1):
        if math.gcd(cur, x) == 1 and all(math.gcd(cur, p) == 1 for p in out):
            out.append(cur)
    return out or [1]


def action_key(fqn: str, mem: int) -> str:
    """What a pool of concurrent containers is keyed by."""
    return f"{fqn}:{mem}"


class _Invoker:
    """NestedSemaphore's two levels of books for one invoker."""
    __slots__ = ("free_mb", "usable", "spare")

    def __init__(self, free_mb: int, usable: bool):
        self.free_mb = free_mb
        self.usable = usable
        #: action key -> spare permits of the containers held of it
        self.spare: Dict[str, int] = {}

    def acquire(self, key: str, mem: int, maxc: int) -> bool:
        """Take a spare permit (and say so), or else the memory (forced or
        not: the caller has decided) and, for C > 1, mint C - 1 spares."""
        if maxc > 1:
            have = self.spare.get(key, 0)
            if have > 0:
                self.spare[key] = have - 1
                return True
            self.spare[key] = maxc - 1
        self.free_mb -= mem
        return False

    def release(self, key: str, mem: int, maxc: int) -> None:
        if maxc > 1:
            have = self.spare.get(key, 0) + 1
            if have < maxc:
                self.spare[key] = have
                return
            # a whole container is idle again (ResizableSemaphore's
            # reduction): its permits go, its memory comes back
            have -= maxc
            if have:
                self.spare[key] = have
            else:
                self.spare.pop(key, None)
        self.free_mb += mem


class ReferenceFleet:
    """One controller's view of the fleet (cluster size 1)."""

    def __init__(self, managed_fraction: float = 1.0,
                 late_release: bool = False):
        self.invokers: List[_Invoker] = []
        self.managed_fraction = managed_fraction
        #: the CONTROL's broken guarantee: fold a step's releases after
        #: its requests instead of before (stale books)
        self.late_release = late_release
        #: placements that took a spare permit of a container already held
        self.shared = 0
        #: managed fleet size -> its probe steps, worked out when a placement
        #: first needs them: a fleet that registers row by row passes through
        #: every size on its way up, and one list costs O(size^2 / log size)
        self._steps_of: Dict[int, List[int]] = {}

    def register(self, idx: int, user_memory_mb: int, usable: bool) -> None:
        """An invoker's ping, as upstream registers it: the fleet grows up
        to its index, each row whose invoker has not pinged yet standing in
        with this invoker's memory, offline (InvokerPool.registerInvoker's
        padToIndexed, :191-207), and the books take slots for new rows
        alone (ShardingContainerPoolBalancerState.updateInvokers,
        :512-551): a row that is there keeps its books and its health,
        whoever pings for it."""
        mb = max(user_memory_mb, MIN_SLOT_MB)
        while idx > len(self.invokers):
            self.invokers.append(_Invoker(mb, False))
        if idx == len(self.invokers):
            self.invokers.append(_Invoker(mb, usable))

    @property
    def managed_count(self) -> int:
        n = len(self.invokers)
        return max(int(self.managed_fraction * n), 1) if n else 0

    def set_health(self, idx: int, usable: bool) -> None:
        if 0 <= idx < len(self.invokers):
            self.invokers[idx].usable = usable

    def schedule(self, namespace: str, fqn: str, mem: int, rand: int,
                 maxc: int = 1) -> Tuple[Optional[int], bool]:
        size = self.managed_count
        if size == 0:
            return None, False
        key = action_key(fqn, mem)
        pooled = maxc > 1
        h = generate_hash(namespace, fqn)
        steps = self._steps_of.get(size)
        if steps is None:
            steps = self._steps_of[size] = pairwise_coprimes(size)
        step = steps[h % len(steps)]
        idx = h % size
        for _ in range(size):
            inv = self.invokers[idx]
            # it fits where there is the memory for a container or, of a
            # container already held, a spare permit
            if inv.usable and (inv.free_mb >= mem or (
                    pooled and inv.spare.get(key, 0) > 0)):
                self.shared += inv.acquire(key, mem, maxc)
                return idx, False
            idx = (idx + step) % size
        best = None
        for i in range(size):
            if self.invokers[i].usable:
                r = (i - rand) % size
                if best is None or r < best[0]:
                    best = (r, i)
        if best is None:
            return None, False
        self.invokers[best[1]].acquire(key, mem, maxc)
        return best[1], True

    def release(self, idx: int, mem: int, fqn: str = "",
                maxc: int = 1) -> None:
        if 0 <= idx < len(self.invokers):
            self.invokers[idx].release(action_key(fqn, mem), mem, maxc)

    def free_mb(self) -> List[int]:
        return [inv.free_mb for inv in self.invokers]

    def spare(self) -> List[Dict[str, int]]:
        """Per invoker, the spare permits it holds by action key."""
        return [dict(inv.spare) for inv in self.invokers]

    def unusable(self, fleet_size: int) -> int:
        """How many of the `fleet_size` invokers the deployment runs are
        not registered or not usable in these books."""
        return fleet_size - sum(inv.usable
                                for inv in self.invokers[:fleet_size])


def _decode_i32(s: str) -> np.ndarray:
    return np.frombuffer(base64.b64decode(s), np.int32)


def _parse_mb(size_json) -> int:
    if isinstance(size_json, (int, float)):
        return int(size_json) // (1 << 20)
    num, unit = str(size_json).split()
    scale = {"B": 1, "KB": 1 << 10, "MB": 1 << 20, "GB": 1 << 30}[unit]
    return int(num) * scale // (1 << 20)


class _Slots:
    """The reference's own record of which action holds which of the
    program's slots while activations of it are in flight."""

    def __init__(self):
        #: slot -> {(fqn, MB): [concurrency, activations in flight]}
        self.holding: Dict[int, Dict[Tuple[str, int], list]] = {}
        #: action key -> the slot it held last; slot -> its last action key
        self.slot_of: Dict[str, int] = {}
        self.key_of: Dict[int, str] = {}
        self.conflicts = 0

    def take(self, slot: int, fqn: str, mem: int, maxc: int) -> None:
        held = self.holding.setdefault(slot, {})
        if (fqn, mem) in held:
            held[fqn, mem][1] += 1
        else:
            # two actions in one slot at once: their pools are conflated
            self.conflicts += bool(held)
            held[fqn, mem] = [maxc, 1]
        key = action_key(fqn, mem)
        self.slot_of[key] = slot
        self.key_of[slot] = key

    def give(self, slot: int, mem: int) -> Optional[Tuple[str, int]]:
        """The (fqn, concurrency) of the action a release row of `mem` MB
        in `slot` belongs to; None where nothing of that size holds it."""
        held = self.holding.get(slot) or {}
        for (fqn, mb), entry in held.items():
            if mb == mem:
                entry[1] -= 1
                if entry[1] <= 0:
                    del held[fqn, mb]
                return fqn, entry[0]
        return None

    def permit_table(self, spare: List[Dict[str, int]]) -> Tuple[dict, int]:
        """The program's dense permit table as these books would fill it:
        {(invoker, slot): spare permits} of the action that last held each
        slot, and how many spare pools have no cell to stand in (their
        action's last slot has gone to another since)."""
        cells, homeless = {}, 0
        for i, pools in enumerate(spare):
            for key, n in pools.items():
                slot = self.slot_of.get(key)
                if not n:
                    continue
                if slot is not None and self.key_of[slot] == key:
                    cells[i, slot] = n
                else:
                    homeless += 1
        return cells, homeless


def replay(records: Iterable[dict], sent: Dict[str, tuple],
           fleet: ReferenceFleet, fleet_size: int) -> dict:
    """Drive `fleet` through the program's journal in mutation order.

    `sent[aid] = (namespace, fqn, memory_mb, concurrency)` is the
    benchmark's own record of what it published, `fleet_size` the invokers
    its fleet runs. Returns per activation the program's decision and the
    reference's, every release row as (invoker, fqn, MB), and one entry per
    dispatch of the placement program (`fused`: a step that scheduled
    requests; else a release-only fold)."""
    records = list(records)
    acks = {int(r["for"]): r["out"] for r in records if r.get("t") == "ack"}
    program: Dict[str, Tuple[int, bool]] = {}
    reference: Dict[str, Tuple[Optional[int], bool]] = {}
    unknown_aids = unacked = input_mismatch = 0
    releases: List[Tuple[int, str, int]] = []
    steps: List[dict] = []
    slots = _Slots()

    def fold_releases(rel: np.ndarray) -> int:
        inv, slot, mem, maxc, valid = rel
        wrong = 0
        for j in np.flatnonzero(valid):
            i, mb = int(inv[j]), int(mem[j])
            fqn, conc = slots.give(int(slot[j]), mb) or ("", 1)
            # a row no action in flight accounts for gives its memory back
            # as the program says, and is counted
            wrong += (not fqn) or int(maxc[j]) != conc
            fleet.release(i, mb, fqn, conc)
            releases.append((i, fqn, mb))
        return wrong

    for rec in records:
        t = rec.get("t")
        if t == "reg":
            # a new row starts unusable ON THE DEVICE whatever the host's
            # flag says: only a folded health flip makes it usable
            for j in rec["reg"]:
                fleet.register(int(j["instance"]), _parse_mb(j["userMemory"]),
                               False)
        elif t == "fold":
            if "rel" in rec:
                rel = _decode_i32(rec["rel"]).reshape(5, -1)
                input_mismatch += fold_releases(rel)
                steps.append({"seq": int(rec["seq"]), "fused": False, "b": 0,
                              "B": 0, "R": rel.shape[1], "distinct": 0,
                              "aids": [], "unusable": None})
            for i, v in rec.get("health") or ():
                fleet.set_health(int(i), bool(v))
        elif t == "batch":
            R, H, B = int(rec["R"]), int(rec["H"]), int(rec["B"])
            rows, b = int(rec["rows"]), int(rec["b"])
            buf = _decode_i32(rec["buf"])
            if not fleet.late_release:
                input_mismatch += fold_releases(buf[:5 * R].reshape(5, R))
            hidx, hval, hvalid = buf[5 * R:5 * R + 3 * H].reshape(3, H)
            for j in np.flatnonzero(hvalid):
                fleet.set_health(int(hidx[j]), bool(hval[j]))
            req = buf[5 * R + 3 * H:].reshape(rows, B)
            out = acks.get(int(rec["seq"]))
            if out is None:
                unacked += b
            distinct = set()
            for col, aid in enumerate(rec["aids"][:b]):
                meta = sent.get(aid)
                if meta is None:
                    unknown_aids += 1
                    continue
                ns, fqn, mem, conc = meta
                distinct.add(fqn)
                if int(req[4, col]) != mem or int(req[6, col]) != conc:
                    input_mismatch += 1
                placed = fleet.schedule(ns, fqn, mem, int(req[7, col]), conc)
                reference[aid] = placed
                if placed[0] is not None:
                    slots.take(int(req[5, col]), fqn, mem, conc)
                if out is not None:
                    v = int(out[col])
                    program[aid] = ((v >> 2) - 1, bool(v & 1))
            if fleet.late_release:
                fold_releases(buf[:5 * R].reshape(5, R))
            steps.append({"seq": int(rec["seq"]), "fused": True, "b": b,
                          "B": B, "R": R, "distinct": len(distinct),
                          "aids": rec["aids"][:b],
                          "unusable": fleet.unusable(fleet_size)})
    permits, homeless = slots.permit_table(fleet.spare())
    return {"program": program, "reference": reference, "steps": steps,
            "releases": releases, "unknown_aids": unknown_aids,
            "unacked": unacked, "input_mismatch": input_mismatch,
            "slot_conflict": slots.conflicts, "shared": fleet.shared,
            "free_mb": fleet.free_mb(), "permits": permits,
            "permits_homeless": homeless}


#: every number compared, with its limit; all are exact comparisons
LIMITS = {
    "lost": 0,             # published, promise never resolved (drain + 60 s)
    "not_once": 0,         # not delivered to exactly one invoker exactly once
    "misdelivered": 0,     # delivered to another invoker than the one chosen
    "unjournaled": 0,      # published but in no journaled step (or unacked),
                           # or a journaled input differs from the catalogue
    "unusable": 0,         # most invokers held unusable at a window's step
    "decision_mismatch": 0,  # (invoker, forced) differs from the reference
    "release_mismatch": 0,   # release rows != completions, per invoker,
                             # action and MB
    "books_mismatch": 0,     # invokers whose final free MB differ, and cells
                             # of the permit table that differ
    "slot_conflict": 0,      # two actions in flight in one slot at once
}


def permit_cells_differing(program_conc_free, replayed: dict) -> int:
    """Cells of the program's permit table int32[N, A] that differ from the
    reference's spare permits of the action that last held that slot."""
    table = np.asarray(program_conc_free)
    want = np.zeros_like(table)
    outside = replayed["permits_homeless"]
    for (i, slot), n in replayed["permits"].items():
        if i < want.shape[0] and slot < want.shape[1]:
            want[i, slot] = n
        else:
            outside += 1
    return int(np.count_nonzero(table != want)) + outside


def compare(replayed: dict, *, sent: Dict[str, tuple],
            resolved: Dict[str, bool], deliveries: Dict[str, List[int]],
            completions: List[Tuple[int, str, int]],
            program_free_mb: List[int], program_conc_free,
            window_aids: set,
            decisions: Optional[Dict[str, Tuple[int, bool]]] = None) -> dict:
    """The numbers that decide `correct`. `window_aids` are the
    activations of the timed window; `completions` the fleet's acks as
    (invoker, fqn, MB); `decisions` replaces the program's journaled
    decisions (the control hands in its own)."""
    program = decisions if decisions is not None else replayed["program"]
    reference = replayed["reference"]
    lost = sum(1 for aid in sent if not resolved.get(aid))
    not_once = sum(1 for aid in sent if len(deliveries.get(aid, ())) != 1)
    not_once += sum(1 for aid in deliveries if aid not in sent)
    misdelivered = sum(
        1 for aid, where in deliveries.items()
        if aid in program and where and where[0] != program[aid][0])
    unjournaled = (sum(1 for aid in sent if aid not in program)
                   + replayed["unknown_aids"] + replayed["input_mismatch"])
    unusable = max((s["unusable"] for s in replayed["steps"] if s["fused"]
                    and not window_aids.isdisjoint(s["aids"])), default=0)
    mismatch = sum(1 for aid, dec in program.items()
                   if aid in reference and tuple(reference[aid]) != tuple(dec))
    rel, done = {}, {}
    for k in replayed["releases"]:
        rel[k] = rel.get(k, 0) + 1
    for k in completions:
        done[k] = done.get(k, 0) + 1
    release_mismatch = sum(abs(rel.get(k, 0) - done.get(k, 0))
                           for k in set(rel) | set(done))
    ref_free = replayed["free_mb"]
    n = len(ref_free)
    books = sum(1 for i in range(n)
                if i >= len(program_free_mb)
                or int(program_free_mb[i]) != ref_free[i])
    books += permit_cells_differing(program_conc_free, replayed)
    numbers = {"lost": lost, "not_once": not_once,
               "misdelivered": misdelivered, "unjournaled": unjournaled,
               "unusable": unusable, "decision_mismatch": mismatch,
               "release_mismatch": release_mismatch, "books_mismatch": books,
               "slot_conflict": replayed["slot_conflict"]}
    return {"numbers": numbers,
            "correct": all(numbers[k] <= LIMITS[k] for k in LIMITS),
            "compared": len(program)}


def checked_line(numbers: dict) -> dict:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
