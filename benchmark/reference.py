"""The plain reference of the placement policy, and the comparison that
decides `correct`.

The policy is upstream's ShardingContainerPoolBalancer in plain Python (a
copy of the semantics of `openwhisk_tpu/models/sharding_policy.py` and
`utils/semaphores.py`, kept here so that no later PR can change the
yardstick; it imports nothing of the program): home invoker = hash %
fleet, probe in a step coprime to the fleet size, first invoker with free
memory wins, total overload forces a rotation-picked usable invoker and
over-commits it. Per-action concurrency is 1 in every cell, so an
invoker's books are its free memory alone; a cell that brings concurrent
containers brings their books here too.

From the program's run the comparison takes only ORDER and RANDOMNESS: the
order of step inputs (which activation ids were scheduled in which step,
which release rows and health flips were folded before it) and the random
number the program drew for a forced placement. Names and memory of each
activation come from the benchmark's own catalogue, and the journaled
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


class _Invoker:
    __slots__ = ("free_mb", "usable")

    def __init__(self, free_mb: int, usable: bool):
        self.free_mb = free_mb
        self.usable = usable


class ReferenceFleet:
    """One controller's view of the fleet (cluster size 1)."""

    def __init__(self, managed_fraction: float = 1.0,
                 late_release: bool = False):
        self.invokers: List[_Invoker] = []
        self.managed_fraction = managed_fraction
        #: the CONTROL's broken guarantee: fold a step's releases after
        #: its requests instead of before (stale books)
        self.late_release = late_release
        self._steps: List[int] = [1]

    def register(self, idx: int, user_memory_mb: int, usable: bool) -> None:
        while idx >= len(self.invokers):
            self.invokers.append(_Invoker(0, False))
        self.invokers[idx] = _Invoker(max(user_memory_mb, MIN_SLOT_MB), usable)
        self._steps = pairwise_coprimes(self.managed_count)

    @property
    def managed_count(self) -> int:
        n = len(self.invokers)
        return max(int(self.managed_fraction * n), 1) if n else 0

    def set_health(self, idx: int, usable: bool) -> None:
        if 0 <= idx < len(self.invokers):
            self.invokers[idx].usable = usable

    def schedule(self, namespace: str, fqn: str, mem: int,
                 rand: int) -> Tuple[Optional[int], bool]:
        size = self.managed_count
        if size == 0:
            return None, False
        h = generate_hash(namespace, fqn)
        step = self._steps[h % len(self._steps)]
        idx = h % size
        for _ in range(size):
            inv = self.invokers[idx]
            if inv.usable and inv.free_mb >= mem:
                inv.free_mb -= mem
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
        self.invokers[best[1]].free_mb -= mem
        return best[1], True

    def release(self, idx: int, mem: int) -> None:
        if 0 <= idx < len(self.invokers):
            self.invokers[idx].free_mb += mem

    def free_mb(self) -> List[int]:
        return [inv.free_mb for inv in self.invokers]

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


def replay(records: Iterable[dict], sent: Dict[str, tuple],
           fleet: ReferenceFleet, fleet_size: int) -> dict:
    """Drive `fleet` through the program's journal in mutation order.

    `sent[aid] = (namespace, fqn, memory_mb)` is the benchmark's own record
    of what it published, `fleet_size` the invokers its fleet runs.
    Returns per activation the program's decision and the reference's,
    every release row, and one entry per dispatch of the placement program
    (`fused`: a step that scheduled requests; else a release-only fold)."""
    records = list(records)
    acks = {int(r["for"]): r["out"] for r in records if r.get("t") == "ack"}
    program: Dict[str, Tuple[int, bool]] = {}
    reference: Dict[str, Tuple[Optional[int], bool]] = {}
    unknown_aids = unacked = input_mismatch = 0
    releases: List[Tuple[int, int]] = []
    steps: List[dict] = []

    def fold_releases(rel: np.ndarray) -> int:
        inv, _slot, mem, maxc, valid = rel
        rows = np.flatnonzero(valid)
        for j in rows:
            fleet.release(int(inv[j]), int(mem[j]))
            releases.append((int(inv[j]), int(mem[j])))
        return int(np.count_nonzero(maxc[rows] != 1))

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
                ns, fqn, mem = meta
                distinct.add(fqn)
                if int(req[4, col]) != mem or int(req[6, col]) != 1:
                    input_mismatch += 1
                reference[aid] = fleet.schedule(ns, fqn, mem,
                                                int(req[7, col]))
                if out is not None:
                    v = int(out[col])
                    program[aid] = ((v >> 2) - 1, bool(v & 1))
            if fleet.late_release:
                fold_releases(buf[:5 * R].reshape(5, R))
            steps.append({"seq": int(rec["seq"]), "fused": True, "b": b,
                          "B": B, "R": R, "distinct": len(distinct),
                          "aids": rec["aids"][:b],
                          "unusable": fleet.unusable(fleet_size)})
    return {"program": program, "reference": reference, "steps": steps,
            "releases": releases, "unknown_aids": unknown_aids,
            "unacked": unacked, "input_mismatch": input_mismatch,
            "free_mb": fleet.free_mb()}


#: every number compared, with its limit; all are exact comparisons
LIMITS = {
    "lost": 0,             # published, promise never resolved (drain + 60 s)
    "not_once": 0,         # not delivered to exactly one invoker exactly once
    "misdelivered": 0,     # delivered to another invoker than the one chosen
    "unjournaled": 0,      # published but in no journaled step (or unacked)
    "unusable": 0,         # most invokers held unusable at a window's step
    "decision_mismatch": 0,  # (invoker, forced) differs from the reference
    "release_mismatch": 0,   # release rows != completions, per invoker and MB
    "books_mismatch": 0,     # invokers whose final free MB differ
}


def compare(replayed: dict, *, sent: Dict[str, tuple],
            resolved: Dict[str, bool], deliveries: Dict[str, List[int]],
            completions: List[Tuple[int, int]],
            program_free_mb: List[int], window_aids: set,
            decisions: Optional[Dict[str, Tuple[int, bool]]] = None) -> dict:
    """The numbers that decide `correct`. `window_aids` are the
    activations of the timed window; `decisions` replaces the program's
    journaled decisions (the control hands in its own)."""
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
    numbers = {"lost": lost, "not_once": not_once,
               "misdelivered": misdelivered, "unjournaled": unjournaled,
               "unusable": unusable, "decision_mismatch": mismatch,
               "release_mismatch": release_mismatch, "books_mismatch": books}
    return {"numbers": numbers,
            "correct": all(numbers[k] <= LIMITS[k] for k in LIMITS),
            "compared": len(program)}


def checked_line(numbers: dict) -> dict:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
