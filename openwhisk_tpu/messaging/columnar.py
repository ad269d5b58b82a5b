"""The batch wire: one struct-packed frame for the two hot messages.

The `ActivationMessage` (controller -> invoker) and the three
`AcknowledgementMessage` kinds (invoker -> controller) cross the bus in
ONE binary frame per (topic, family) and flush, at every group size
from 1 up. A lone message is a 1-row frame: behind the coalescing
producer these two families have no per-message JSON form. The funnel's
`fun1` / `funA` records (ISSUE 20) are still JSON and live at the end
of this module.

The frame, byte by byte (little-endian, no padding; docs/spi.md has the
same table):

    offset  size     field
    0       3        magic `\\xff O W`: 0xFF starts no UTF-8 text, so no
                     JSON payload can begin with it
    3       1        version (2)
    4       1        family: 1 = activation, 2 = ack
    5       4        n, the row count (uint32)
    9       2+2+2    the sizes of the three dedup tables (uint16 each):
                     activation = users, actions, controllers;
                     ack = invokers, 0, 0
    15      4        s, the byte length of the sparse section (uint32)
    19      4*T      the byte length of each table blob (uint32; T = the
                     three sizes' sum), in table order
            8*n      transaction `start_wallclock` (float64, bit-exact)
            1*n      row flags (below)
            2*n      byte length of the transaction id's UTF-8 (uint16)
            4*n      byte length of the row's opaque body (uint32; 0 = None)
            2*n*k    row -> table indices (uint16), one column after the
                     other: activation k = 3 (user, action, controller),
                     ack k = 1 (invoker; 0xFFFF = none)
            ...      the table blobs, back to back
            s        the sparse section: one compact JSON object
                     {column: {row: value}} of the rare fields; s = 0
                     when no row carries one
            16*n     activation ids (the 32 hex characters as 16 bytes)
            ...      per row: transaction id bytes, then the opaque body

    activation flags: bit 0 blocking
    ack flags: bits 0-1 the kind (0 completion, 1 result, 2 combined);
      bit 2 isSystemError; bits 4-5 the response's `statusCode` (0..3;
      0 where the row carries no record). Version 1 had no status bits,
      so its frames are refused, never read as successes
    both: bit 3 the id is not 32 lowercase hex and sits in the sparse
      `ids` column (its 16 bytes are zero)

A table blob is the sub-object's existing compact JSON: a user is
`Identity.to_json()`, an action `[fqn, revision]`, a controller its
name, an invoker `InvokerInstanceId.to_json()`: every field the serial
wire carries, so nothing of an identity's limits or rights is lost.
Blobs are interned on both sides. The ENCODER keeps a blob beside the
object it came from (`_blob_beside`: keyed by object identity, dropped
with the object; identities and instance ids are values nobody
mutates), so a sub-object is encoded once in its life and not once a
message. The DECODER keeps bounded tables from blob bytes to the parsed
object (`_InternTable`: `INTERN_BOUND` entries, reset whole when full):
a blob seen before costs one dict lookup, and the parsed object is
SHARED by every message that names it (read-only on the consume side,
like the reference's case classes); a blob that differs in any byte (a
namespace whose limits changed, a new revision) is parsed anew.

The opaque body is the user's own data as JSON bytes: an activation's
`content` (`{}` is two bytes and no `dumps`), an ack's response record.
The response is parsed only when somebody reads it
(`LazyWhiskActivation`, which carries the row's status code beside the
bytes); the completion loop needs the columns alone, and a blocking
answer the bytes and the status.
Sparse columns: `cause`, `trace`, `init` (non-empty `initArgs`),
`fence` / `fences`, `fpart` / `fparts` on activations, `trace` on acks,
`ids` on both.

Consumers route with a sniff, not a parse: `is_batch_payload` is true
for a frame and for the funnel's JSON records, never for a plain
message; `parse_batch` returns `(kind, messages)`. A truncated or
garbled frame raises ValueError / IndexError / KeyError / TypeError,
which the feeds' handlers log as a corrupt frame before any of its rows
is applied.

Off switch: `CONFIG_whisk_bus_coalesce_batchWire=false` restores one
independently encoded JSON payload per message: the serial wire format,
byte-exact. The plain parsers (`ActivationMessage.parse`, `parse_ack`)
stay for it and for a peer that still sends it.
"""
from __future__ import annotations

import json
import logging
import struct
import weakref
from functools import lru_cache
from typing import Callable, Dict, List, Optional, Tuple

from ..core.entity import (ActivationId, ControllerInstanceId, Identity,
                           InvokerInstanceId)
from ..core.entity.names import FullyQualifiedEntityName
from ..utils.transaction import TransactionId
from .message import AcknowledgementMessage, ActivationMessage, Message

#: a frame's first three bytes
WIRE_MAGIC = b"\xffOW"
WIRE_VERSION = 2
#: the funnel's JSON records lead with this key (json.dumps preserves
#: insertion order, so it is a stable byte prefix)
BATCH_MAGIC = b'{"whiskBatch":'

KIND_ACTIVATION = "act2"
KIND_ACK = "ack2"
#: the admission-funnel frame (ISSUE 20): an activation batch plus a
#: (origin, seq, epoch) routing header — one front-end process's whole
#: admission wave shipped to the device-owning balancer as one record.
KIND_FUNNEL = "fun1"
#: the funnel's per-row outcome frame back to the origin: placement /
#: refusal / completion records, columnar like the ack batch.
KIND_FUNNEL_ACK = "funA"

#: serde hop labels by batch kind (mirrors connector._SERDE_HOPS so the
#: host observatory's per-hop accounting survives the batch wire)
_BATCH_HOPS = {KIND_ACTIVATION: "activation", KIND_ACK: "completion_ack",
               KIND_FUNNEL: "activation",
               KIND_FUNNEL_ACK: "completion_ack"}

#: the deferred result parse books its cost under its OWN hop, so the
#: "consumer never reads the result" case is visible as a ZERO row here
#: while the frame decode stays under completion_ack
LAZY_RESULT_HOP = "ack_result"

#: magic, version, family, rows, three table sizes, sparse length
_HEADER = struct.Struct("<3sBBIHHHI")
_NO_INDEX = 0xFFFF
_ZERO_ID = bytes(16)
#: `json.dumps(obj, separators=(",", ":"))` without the encoder object it
#: builds per call
_dumps = json.JSONEncoder(separators=(",", ":")).encode
#: row flags: bit 3 means the same in both families
_BLOCKING = 1
_ACK_KIND_MASK = 3
_ACK_SYSTEM_ERROR = 4
_ID_SPARSE = 8
#: ack flags bits 4-5: the response's statusCode
_ACK_STATUS_SHIFT = 4
_ACK_KINDS = ("completion", "result", "combined")
_ACK_CODES = {kind: code for code, kind in enumerate(_ACK_KINDS)}

#: a decoder table holds at most this many blobs and is reset whole when
#: full (a process sees thousands of identities and actions, not
#: millions; a reset costs one parse per blob still in use)
INTERN_BOUND = 8192

#: how often the interning engaged, process-wide: the decoder's lookups
#: and those that had to parse (gauges `bus_wire_intern_*`,
#: messaging/coalesce.py; hits = lookups - misses), and the blobs the
#: encoder reused without an encode (`ow_produce`'s `interned`)
WIRE_STATS = {"intern_lookups": 0, "intern_misses": 0, "blob_hits": 0}


def intern_hits() -> int:
    """Decoder lookups answered from a table, process-wide so far."""
    return WIRE_STATS["intern_lookups"] - WIRE_STATS["intern_misses"]


def is_batch_payload(raw) -> bool:
    """True when `raw` is a batch wire record: a struct-packed frame or
    one of the funnel's JSON records (prefix sniff; no parse). Accepts
    bytes/bytearray/str; a str is never a frame."""
    if isinstance(raw, str):
        return raw.startswith('{"whiskBatch":')
    head = bytes(raw[:len(BATCH_MAGIC)])
    return head.startswith(WIRE_MAGIC) or head == BATCH_MAGIC


def batch_hop_of(kind: str) -> str:
    return _BATCH_HOPS.get(kind, "other")


def batchable_family(msg) -> Optional[str]:
    """The batch family a message coalesces into, or None for messages
    that stay per-frame (pings, events: background chatter whose framing
    is not on the hot path)."""
    if isinstance(msg, ActivationMessage):
        return KIND_ACTIVATION
    if isinstance(msg, AcknowledgementMessage):
        return KIND_ACK
    return None


# -- interning --------------------------------------------------------------

#: the encoder's side: id(object) -> (weak reference, blob)
_BLOBS_BESIDE: Dict[int, tuple] = {}
#: (path, name, revision) -> blob. A message builds its action's name
#: anew each time, so this one is keyed by value; bounded like the
#: decoder's tables
_ACTION_BLOBS: Dict[tuple, bytes] = {}


def _blob_beside(obj, to_json: Callable) -> bytes:
    """The compact JSON of `to_json(obj)`, encoded once in `obj`'s life."""
    key = id(obj)
    kept = _BLOBS_BESIDE.get(key)
    if kept is not None and kept[0]() is obj:
        WIRE_STATS["blob_hits"] += 1
        return kept[1]
    blob = _dumps(to_json(obj)).encode()
    try:
        _BLOBS_BESIDE[key] = (
            weakref.ref(obj,
                        lambda _r, key=key: _BLOBS_BESIDE.pop(key, None)),
            blob)
    except TypeError:
        pass    # an object that takes no weak reference is encoded each time
    return blob


def _action_blob(action: FullyQualifiedEntityName,
                 revision: Optional[str]) -> bytes:
    key = (action.path.path, action.name.name, revision)
    blob = _ACTION_BLOBS.get(key)
    if blob is None:
        if len(_ACTION_BLOBS) >= INTERN_BOUND:
            _ACTION_BLOBS.clear()
        blob = _ACTION_BLOBS[key] = _dumps([str(action), revision]).encode()
    else:
        WIRE_STATS["blob_hits"] += 1
    return blob


class _InternTable:
    """The decoder's side: blob bytes -> the object parsed from them."""

    __slots__ = ("parse", "objects")

    def __init__(self, parse: Callable):
        self.parse = parse
        self.objects: Dict[bytes, object] = {}

    def get(self, blob: bytes):
        obj = self.objects.get(blob)
        if obj is None:
            obj = self.parse(json.loads(blob))
            if len(self.objects) >= INTERN_BOUND:
                self.objects.clear()
            self.objects[blob] = obj
            WIRE_STATS["intern_misses"] += 1
        return obj


_USERS = _InternTable(Identity.from_json)
_ACTIONS = _InternTable(
    lambda j: (FullyQualifiedEntityName.parse(j[0]), j[1]))
_CONTROLLERS = _InternTable(ControllerInstanceId)
_INVOKERS = _InternTable(InvokerInstanceId.from_json)


# -- the frame --------------------------------------------------------------

@lru_cache(maxsize=1024)
def _columns_struct(blobs: int, n: int, index_columns: int) -> struct.Struct:
    """The fixed-width columns of a frame of `n` rows and `blobs` table
    blobs (module doc), compiled once per shape."""
    return struct.Struct(
        f"<{blobs}I{n}d{n}B{n}H{n}I{index_columns * n}H")


def _id_bytes(aid: str) -> Optional[bytes]:
    """The id's 16 bytes, or None where its string is not exactly their
    32 lowercase hex characters (no ActivationId is such, but the wire
    round-trips whatever it is given)."""
    if len(aid) == 32:
        try:
            raw = bytes.fromhex(aid)
        except ValueError:
            return None
        if len(raw) == 16 and raw.hex() == aid:
            return raw
    return None


def _pack_frame(code: int, n: int, sizes: tuple, blobs: list, sparse: dict,
                walls: list, flags: list, tid_lens: list, body_lens: list,
                indices: list, ids: list, heap: list) -> bytes:
    if max(sizes) >= _NO_INDEX:
        raise ValueError(f"a frame's table holds under {_NO_INDEX} blobs")
    sparse_raw = _dumps(sparse).encode() if sparse else b""
    return b"".join([
        _HEADER.pack(WIRE_MAGIC, WIRE_VERSION, code, n, *sizes,
                     len(sparse_raw)),
        _columns_struct(len(blobs), n, len(indices) // n if n else 0).pack(
            *map(len, blobs), *walls, *flags, *tid_lens, *body_lens,
            *indices),
        *blobs, sparse_raw, *ids, *heap])


def _open_frame(raw: bytes, header: tuple, index_columns: int) -> tuple:
    """Unpack a frame's fixed-width columns, slice its table blobs and
    parse its sparse section. Returns (walls, flags, tid lengths, body
    lengths, indices, blobs, sparse, the ids' hex, offset of the first
    row's bytes); a frame whose length is not what its own columns add
    up to is refused here, before a row is built."""
    _magic, _version, _family, n, t0, t1, t2, sparse_len = header
    nt = t0 + t1 + t2
    columns = _columns_struct(nt, n, index_columns)
    vals = columns.unpack_from(raw, _HEADER.size)
    a = nt + n
    tid_lens, body_lens = vals[a + n:a + 2 * n], vals[a + 2 * n:a + 3 * n]
    off = _HEADER.size + columns.size
    blobs = []
    for blob_len in vals[:nt]:
        blobs.append(raw[off:off + blob_len])
        off += blob_len
    rows_at = off + sparse_len + 16 * n
    if rows_at + sum(tid_lens) + sum(body_lens) != len(raw):
        raise ValueError(f"wire frame of {len(raw)} bytes, its columns add "
                         f"up to {rows_at + sum(tid_lens) + sum(body_lens)}")
    WIRE_STATS["intern_lookups"] += nt
    sparse = json.loads(raw[off:off + sparse_len]) if sparse_len else {}
    return (vals[nt:a], vals[a:a + n], tid_lens, body_lens, vals[a + 3 * n:],
            blobs, sparse, raw[rows_at - 16 * n:rows_at].hex(), rows_at)


def _sparse_activation_columns(msgs: List[ActivationMessage]) -> dict:
    """The rarely-present fields as {column: {row: value}}; shared by the
    frame's sparse section and the funnel's JSON record. `fence` is the
    batch-level HA epoch (one controller's flush shares one epoch; a
    rare mixed-epoch flush falls back to the per-row `fences`), `fpart`
    / `fparts` likewise for the active/active partition ids."""
    cause: Dict[str, str] = {}
    trace: Dict[str, dict] = {}
    init: Dict[str, dict] = {}
    fences: Dict[str, int] = {}
    fparts: Dict[str, int] = {}
    for row, m in enumerate(msgs):
        if m.cause is not None:
            cause[str(row)] = m.cause.to_json()
        if m.trace_context is not None:
            trace[str(row)] = m.trace_context
        if m.init_args:
            init[str(row)] = m.init_args
        if m.fence_epoch is not None:
            fences[str(row)] = m.fence_epoch
        if m.fence_part is not None:
            fparts[str(row)] = m.fence_part
    out: dict = {}
    if cause:
        out["cause"] = cause
    if trace:
        out["trace"] = trace
    if init:
        out["init"] = init
    for scalar, per_row, col in (("fence", "fences", fences),
                                 ("fpart", "fparts", fparts)):
        if col:
            vals = set(col.values())
            if len(vals) == 1 and len(col) == len(msgs):
                out[scalar] = vals.pop()
            else:
                out[per_row] = col
    return out


class _SparseActivation:
    """Reader of `_sparse_activation_columns`' record."""

    __slots__ = ("cause", "trace", "init", "fence", "fences", "fpart",
                 "fparts")

    def __init__(self, j: dict):
        self.cause = j.get("cause") or {}
        self.trace = j.get("trace") or {}
        self.init = j.get("init") or {}
        self.fence = j.get("fence")
        self.fences = j.get("fences") or {}
        self.fpart = j.get("fpart")
        self.fparts = j.get("fparts") or {}

    def of(self, row: int) -> tuple:
        """(init_args, cause, trace_context, fence_epoch, fence_part)"""
        key = str(row)
        cause = self.cause.get(key)
        return (self.init.get(key) or {},
                ActivationId(cause) if cause else None,
                self.trace.get(key),
                self.fence if self.fence is not None
                else self.fences.get(key),
                self.fpart if self.fpart is not None
                else self.fparts.get(key))


class WireFrame(Message):
    """N same-family messages as one frame (see module doc); what
    `make_batch` hands the producer."""

    family = ""
    #: the header's family byte
    code = 0

    def __init__(self, msgs: list):
        self.msgs = msgs

    #: the waterfall produce edge stamps per activation: connector
    #: stamp_produce reads this instead of .activation_id
    @property
    def activation_ids(self) -> List[str]:
        return [m.activation_id.asString for m in self.msgs]


def _controller_name(ctrl: ControllerInstanceId) -> str:
    return ctrl.name


class ActivationFrame(WireFrame):
    family = KIND_ACTIVATION
    code = 1

    def serialize(self) -> bytes:
        msgs = self.msgs
        users: Dict[bytes, int] = {}
        actions: Dict[bytes, int] = {}
        ctrls: Dict[bytes, int] = {}
        walls, flags, tid_lens, body_lens = [], [], [], []
        u_col, a_col, c_col = [], [], []
        ids, heap = [], []
        sparse_ids: Dict[str, str] = {}
        rare = False
        for row, m in enumerate(msgs):
            u_col.append(users.setdefault(
                _blob_beside(m.user, Identity.to_json), len(users)))
            a_col.append(actions.setdefault(
                _action_blob(m.action, m.revision), len(actions)))
            c_col.append(ctrls.setdefault(
                _blob_beside(m.root_controller_index, _controller_name),
                len(ctrls)))
            tid = str(m.transid.id).encode()
            walls.append(m.transid.start_wallclock)
            tid_lens.append(len(tid))
            heap.append(tid)
            content = m.content
            if content is not None:
                # `{}` is what a parameterless invoke carries
                body = b"{}" if type(content) is dict and not content \
                    else _dumps(content).encode()
                heap.append(body)
                body_lens.append(len(body))
            else:
                body_lens.append(0)
            flag = _BLOCKING if m.blocking else 0
            aid = _id_bytes(m.activation_id.asString)
            if aid is None:
                flag |= _ID_SPARSE
                sparse_ids[str(row)] = m.activation_id.asString
                aid = _ZERO_ID
            ids.append(aid)
            flags.append(flag)
            rare = rare or m.cause is not None \
                or m.trace_context is not None or bool(m.init_args) \
                or m.fence_epoch is not None or m.fence_part is not None
        sparse = _sparse_activation_columns(msgs) if rare else {}
        if sparse_ids:
            sparse["ids"] = sparse_ids
        return _pack_frame(
            self.code, len(msgs), (len(users), len(actions), len(ctrls)),
            [*users, *actions, *ctrls], sparse, walls, flags, tid_lens,
            body_lens, u_col + a_col + c_col, ids, heap)

    @staticmethod
    def decode(raw: bytes, header: tuple) -> List[ActivationMessage]:
        """Each distinct identity / action / controller of the frame is
        looked up once and the objects are SHARED by its messages."""
        (walls, flags, tid_lens, body_lens, idx, blobs, sparse, ids,
         off) = _open_frame(raw, header, 3)
        n, n_users, n_actions = header[3], header[4], header[4] + header[5]
        users = [_USERS.get(b) for b in blobs[:n_users]]
        actions = [_ACTIONS.get(b) for b in blobs[n_users:n_actions]]
        ctrls = [_CONTROLLERS.get(b) for b in blobs[n_actions:]]
        rare = _SparseActivation(sparse) if sparse else None
        out: List[ActivationMessage] = []
        for row in range(n):
            end = off + tid_lens[row]
            transid = TransactionId(str(raw[off:end], "utf-8"),
                                    start_wallclock=walls[row])
            off = end + body_lens[row]
            body = raw[end:off]
            content = {} if body == b"{}" \
                else json.loads(body) if body else None
            flag = flags[row]
            aid = ActivationId(sparse["ids"][str(row)]) \
                if flag & _ID_SPARSE \
                else ActivationId.of_hex(ids[32 * row:32 * row + 32])
            fqn, revision = actions[idx[n + row]]
            out.append(ActivationMessage(
                transid, fqn, revision, users[idx[row]], aid,
                ctrls[idx[2 * n + row]], bool(flag & _BLOCKING), content,
                *(rare.of(row) if rare is not None else ())))
        return out


class LazyWhiskActivation:
    """A WhiskActivation that stays raw bytes until somebody reads it.

    The ack frame ships each activation's response record as an opaque
    body; the completion hot loop (`process_acknowledgements`) only needs
    the ack COLUMNS (id, invoker, system-error bit) — the response is
    dead weight there. This proxy
    carries the raw payload through the promise plumbing and parses it on
    the first attribute access, which for a blocking invoke happens on
    the API handler's own turn and for a fire-and-forget ack happens
    never. The deferred parse books its bytes + wall time under the
    `ack_result` serde hop, so skipped parses are a measurable zero.
    `status_code` is the response's, from the row's flags: what a
    blocking answer needs to choose 200 or 502 without the parse."""

    __slots__ = ("raw", "status_code", "_obj")

    def __init__(self, raw: bytes, status_code: int):
        self.raw = raw
        self.status_code = status_code
        self._obj = None

    @property
    def materialized(self) -> bool:
        return self._obj is not None

    def _materialize(self):
        obj = self._obj
        if obj is None:
            from ..core.entity import WhiskActivation
            from ..utils.hostprof import GLOBAL_HOST_OBSERVATORY
            obs = GLOBAL_HOST_OBSERVATORY
            try:
                if obs.serde_active:
                    import time as _time
                    t0 = _time.perf_counter_ns()
                    obj = WhiskActivation.from_json(json.loads(self.raw))
                    obs.serde_observe(LAZY_RESULT_HOP, "deserialize",
                                      len(self.raw),
                                      _time.perf_counter_ns() - t0)
                else:
                    obj = WhiskActivation.from_json(json.loads(self.raw))
            except Exception as e:
                # a corrupt body behind a CONSISTENT lazy frame (header +
                # lengths fine, payload garbled) is by design undetectable
                # until this first read — the eager wire's decode-time
                # "corrupt completion ack" drop can't apply. Surface a
                # well-defined, logged error here instead of letting a
                # JSONDecodeError/KeyError escape deep inside whatever
                # consumer touched the first attribute.
                logging.warning("corrupt lazy ack result (%dB): %r",
                                len(self.raw), e)
                raise ValueError(
                    f"corrupt lazy ack result: {e!r}") from e
            self._obj = obj
        return obj

    def __getattr__(self, name):
        # only reached for names not in __slots__/class dict: every real
        # WhiskActivation attribute (activation_id, response, to_json...)
        # lands here and forces the parse
        return getattr(self._materialize(), name)

    def __repr__(self) -> str:  # no parse for logging
        state = "parsed" if self._obj is not None else f"{len(self.raw)}B raw"
        return f"LazyWhiskActivation({state})"


class AckFrame(WireFrame):
    """N invoker->controller acks. The heavy per-row payload, the
    WhiskActivation record, IS the data and stays per row, as opaque
    bytes: the decode side never parses a response the consumer does not
    read (a blocking invoke parses it on the API handler's turn, a
    fire-and-forget ack never)."""

    family = KIND_ACK
    code = 2

    @staticmethod
    def _record(m: AcknowledgementMessage) -> Tuple[bytes, int]:
        """One row's opaque response record and its status code. A
        still-raw relay (a LazyWhiskActivation nobody parsed) passes its
        bytes and its status through untouched: re-encoding an unread
        payload would be the very cost the opaque column exists to
        skip."""
        act = m.activation
        if act is None:
            return b"", 0
        if isinstance(act, LazyWhiskActivation) and not act.materialized:
            return act.raw, act.status_code
        return _dumps(act.to_json()).encode(), act.response.status_code

    def serialize(self) -> bytes:
        msgs = self.msgs
        invokers: Dict[bytes, int] = {}
        walls, flags, tid_lens, body_lens, iv_col = [], [], [], [], []
        ids, heap = [], []
        trace: Dict[str, dict] = {}
        sparse_ids: Dict[str, str] = {}
        for row, m in enumerate(msgs):
            iv_col.append(
                _NO_INDEX if m.invoker is None else invokers.setdefault(
                    _blob_beside(m.invoker, InvokerInstanceId.to_json),
                    len(invokers)))
            tid = str(m.transid.id).encode()
            body, status = self._record(m)
            walls.append(m.transid.start_wallclock)
            tid_lens.append(len(tid))
            body_lens.append(len(body))
            heap.append(tid)
            heap.append(body)
            flag = _ACK_CODES.get(m.kind, 2) | status << _ACK_STATUS_SHIFT
            if m.is_system_error:
                flag |= _ACK_SYSTEM_ERROR
            aid = _id_bytes(m.activation_id.asString)
            if aid is None:
                flag |= _ID_SPARSE
                sparse_ids[str(row)] = m.activation_id.asString
                aid = _ZERO_ID
            ids.append(aid)
            flags.append(flag)
            if m.trace_context is not None:
                trace[str(row)] = m.trace_context
        sparse: dict = {}
        if trace:
            sparse["trace"] = trace
        if sparse_ids:
            sparse["ids"] = sparse_ids
        return _pack_frame(self.code, len(msgs), (len(invokers), 0, 0),
                           list(invokers), sparse, walls, flags, tid_lens,
                           body_lens, iv_col, ids, heap)

    @staticmethod
    def decode(raw: bytes, header: tuple) -> List[AcknowledgementMessage]:
        """Decode WITHOUT touching a response byte beyond slicing: every
        ack field comes from the columns (the system-error bit and the
        status code were computed at encode time from the same response
        the serial parse would re-derive them from), and each present
        response becomes a LazyWhiskActivation over its slice. Building
        the base AcknowledgementMessage directly, not the kind
        subclasses, matters: ResultMessage reads activation_id off the
        activation and CombinedCompletionAndResultMessage reads
        response.is_whisk_error, either of which would force the parse
        this frame exists to defer."""
        (walls, flags, tid_lens, body_lens, iv_col, blobs, sparse, ids,
         off) = _open_frame(raw, header, 1)
        invokers = [_INVOKERS.get(b) for b in blobs]
        trace = sparse.get("trace") or {}
        out: List[AcknowledgementMessage] = []
        for row in range(header[3]):
            end = off + tid_lens[row]
            transid = TransactionId(str(raw[off:end], "utf-8"),
                                    start_wallclock=walls[row])
            off = end + body_lens[row]
            flag, iv = flags[row], iv_col[row]
            aid = ActivationId(sparse["ids"][str(row)]) \
                if flag & _ID_SPARSE \
                else ActivationId.of_hex(ids[32 * row:32 * row + 32])
            ack = AcknowledgementMessage(
                transid, aid, invokers[iv] if iv != _NO_INDEX else None,
                bool(flag & _ACK_SYSTEM_ERROR),
                LazyWhiskActivation(raw[end:off],
                                    flag >> _ACK_STATUS_SHIFT & 3)
                if off > end else None)
            ack.kind = _ACK_KINDS[flag & _ACK_KIND_MASK]
            if trace:
                ack.trace_context = trace.get(str(row))
            out.append(ack)
        return out


_FRAMES = {frame.family: frame for frame in (ActivationFrame, AckFrame)}
_FRAME_OF_CODE = {frame.code: frame for frame in _FRAMES.values()}


# -- the funnel's JSON records ----------------------------------------------

class _Dedup:
    """Insertion-ordered dedup table: intern() returns the index of the
    (hashable) key, appending `value` on first sight."""

    __slots__ = ("index", "values")

    def __init__(self):
        self.index: Dict[object, int] = {}
        self.values: List[object] = []

    def intern(self, key, value) -> int:
        i = self.index.get(key)
        if i is None:
            i = len(self.values)
            self.index[key] = i
            self.values.append(value)
        return i


class ActivationBatchMessage:
    """N ActivationMessages as one struct-of-arrays JSON record: the body
    the funnel's `fun1` embeds (`FunnelBatchMessage`), and nothing else
    any more: on the bus an activation batch is an `ActivationFrame`.

    `users`/`actions`/`ctrls` are per-batch dedup tables (each unique
    identity / (fqn, revision) / controller encoded ONCE); `ids`, `u`,
    `a`, `c`, `tx`, `bl`, `args` are length-N columns; the sparse
    columns are `_sparse_activation_columns`'."""

    @staticmethod
    def columns(msgs: List[ActivationMessage]) -> dict:
        users, actions, ctrls = _Dedup(), _Dedup(), _Dedup()
        ids: List[str] = []
        u_col: List[int] = []
        a_col: List[int] = []
        c_col: List[int] = []
        tx_col: List[object] = []
        bl_col: List[int] = []
        args_col: List[Optional[dict]] = []
        for m in msgs:
            ids.append(m.activation_id.asString)
            # identity dedup keys on the subject+namespace-uuid pair (the
            # stable identity key); the action table keys on (fqn, rev)
            ident = m.user
            u_col.append(users.intern(
                (ident.subject, ident.namespace.uuid.asString),
                ident.to_json()))
            a_col.append(actions.intern((str(m.action), m.revision),
                                        [str(m.action), m.revision]))
            c_col.append(ctrls.intern(m.root_controller_index.name,
                                      m.root_controller_index.name))
            tx_col.append(m.transid.to_json())
            bl_col.append(1 if m.blocking else 0)
            args_col.append(m.content)
        out = {
            "users": users.values,
            "actions": actions.values,
            "ctrls": ctrls.values,
            "ids": ids,
            "u": u_col, "a": a_col, "c": c_col,
            "tx": tx_col, "bl": bl_col,
            "args": args_col,
        }
        out.update(_sparse_activation_columns(msgs))
        return out

    @staticmethod
    def from_json(j: dict) -> List[ActivationMessage]:
        """Each unique identity/action/controller in the batch is parsed
        exactly once and the rebuilt objects are SHARED across the
        batch's messages (read-only on the consume side, like the
        reference's case classes)."""
        users = [Identity.from_json(u) for u in j["users"]]
        actions = [(FullyQualifiedEntityName.parse(a), rev)
                   for a, rev in j["actions"]]
        ctrls = [ControllerInstanceId(c) for c in j["ctrls"]]
        rare = _SparseActivation(j)
        out: List[ActivationMessage] = []
        for row, (aid, u, a, c, tx, bl, args) in enumerate(zip(
                j["ids"], j["u"], j["a"], j["c"], j["tx"], j["bl"],
                j["args"])):
            fqn, rev = actions[a]
            out.append(ActivationMessage(
                TransactionId.from_json(tx), fqn, rev, users[u],
                ActivationId(aid), ctrls[c], bool(bl), args,
                *rare.of(row)))
        return out


class FunnelFrame:
    """Decoded `fun1` frame: the rebuilt ActivationMessages plus the
    routing header the receiver fences/dedupes on."""

    __slots__ = ("origin", "seq", "epoch", "msgs")

    def __init__(self, origin: int, seq: int, epoch: int,
                 msgs: List[ActivationMessage]):
        self.origin = origin
        self.seq = seq
        self.epoch = epoch
        self.msgs = msgs


class FunnelBatchMessage(Message):
    """ISSUE 20: one front-end admission wave as ONE wire record:
    `ActivationBatchMessage`'s struct-of-arrays columns (dedup tables +
    packed per-row columns) plus three routing scalars:

      * `origin` — the front-end controller instance the per-row outcome
        frames route back to (topic `ctrlfunnelack<origin>`);
      * `seq` — the sender's frame counter. Application-level retry
        re-ships the SAME seq, and the receiver dedupes PER ROW (the
        `pubN` discipline one layer up): a replayed frame only places
        rows whose first delivery was lost;
      * `epoch` — the placement-leadership epoch the sender believes
        current. 0 = unfenced (bootstrap; the balancer's own standby /
        partition fences still apply row-by-row); nonzero must equal the
        receiving balancer's live epoch or the whole frame is refused —
        covering both the zombie sender and the demoted (stale-epoch)
        balancer."""

    def __init__(self, msgs: List[ActivationMessage], origin: int,
                 seq: int, epoch: int = 0):
        self.msgs = msgs
        self.origin = int(origin)
        self.seq = int(seq)
        self.epoch = int(epoch)

    @property
    def activation_ids(self) -> List[str]:
        return [m.activation_id.asString for m in self.msgs]

    def to_json(self) -> dict:
        # `whiskBatch` in first position (dict order): the prefix sniff
        out = {"whiskBatch": KIND_FUNNEL}
        out.update(ActivationBatchMessage.columns(self.msgs))
        out["origin"] = self.origin
        out["seq"] = self.seq
        out["epoch"] = self.epoch
        return out

    @staticmethod
    def from_json(j: dict) -> FunnelFrame:
        msgs = ActivationBatchMessage.from_json(j)
        return FunnelFrame(int(j["origin"]), int(j["seq"]),
                           int(j.get("epoch", 0)), msgs)


#: funnel outcome codes (one char per row in the `k` column):
#:   p = placed (the row has a completion promise at the balancer)
#:   r = refused (sparse `exc` row carries [kind-code, exact text])
#:   c = completed (sparse `resp` row carries the activation JSON for
#:       blocking rows; non-blocking completions ship slim)
#:   f = forced completion timeout (the serial path's ActiveAckTimeout)
FUNNEL_PLACED = "p"
FUNNEL_REFUSED = "r"
FUNNEL_COMPLETED = "c"
FUNNEL_FORCED = "f"

#: refusal kind-codes: "T" rebuilds LoadBalancerThrottleException (429
#: at the front door), anything else a plain LoadBalancerException (503)
FUNNEL_EXC_THROTTLE = "T"
FUNNEL_EXC_ERROR = "L"


class FunnelOutcome:
    """One decoded `funA` row."""

    __slots__ = ("code", "aid", "err", "exc", "resp")

    def __init__(self, code: str, aid: str, err: bool = False,
                 exc: Optional[Tuple[str, str]] = None,
                 resp: Optional[dict] = None):
        self.code = code
        self.aid = aid
        self.err = err
        self.exc = exc
        self.resp = resp


class FunnelAckFrame:
    __slots__ = ("origin", "epoch", "rows")

    def __init__(self, origin: int, epoch: int, rows: List[FunnelOutcome]):
        self.origin = origin
        self.epoch = epoch
        self.rows = rows


class FunnelAckMessage(Message):
    """N funnel outcome records as one columnar record. `epoch` is the
    balancer's CURRENT placement epoch — senders adopt it, so a
    bootstrap (epoch-0) sender converges to fenced frames after its
    first outcome wave."""

    def __init__(self, origin: int, epoch: int,
                 rows: List[FunnelOutcome]):
        self.origin = int(origin)
        self.epoch = int(epoch)
        self.rows = rows

    def to_json(self) -> dict:
        exc: Dict[str, list] = {}
        resp: Dict[str, dict] = {}
        for i, r in enumerate(self.rows):
            if r.exc is not None:
                exc[str(i)] = [r.exc[0], r.exc[1]]
            if r.resp is not None:
                resp[str(i)] = r.resp
        out = {
            "whiskBatch": KIND_FUNNEL_ACK,
            "origin": self.origin,
            "epoch": self.epoch,
            "ids": [r.aid for r in self.rows],
            "k": "".join(r.code for r in self.rows),
            "err": [1 if r.err else 0 for r in self.rows],
        }
        if exc:
            out["exc"] = exc
        if resp:
            out["resp"] = resp
        return out

    @staticmethod
    def from_json(j: dict) -> FunnelAckFrame:
        exc = j.get("exc") or {}
        resp = j.get("resp") or {}
        rows = []
        for i, (aid, code, err) in enumerate(zip(j["ids"], j["k"],
                                                 j["err"])):
            key = str(i)
            e = exc.get(key)
            rows.append(FunnelOutcome(
                code, aid, bool(err),
                (e[0], e[1]) if e is not None else None,
                resp.get(key)))
        return FunnelAckFrame(int(j["origin"]), int(j.get("epoch", 0)),
                              rows)


def make_batch(family: str, msgs: list) -> WireFrame:
    """Wrap same-family messages into their frame (the `serialize_many`
    entry point the coalescing producer uses), from one message up."""
    frame = _FRAMES.get(family)
    if frame is None:
        raise ValueError(f"not a batchable family: {family!r}")
    return frame(msgs)


def parse_batch(raw) -> Tuple[str, list]:
    """Decode one batch payload -> (kind, [messages]). The caller sniffs
    with is_batch_payload first. A frame that is truncated, garbled or
    of an unknown family, and a JSON record of an unknown kind, raise
    ValueError (the feed's corrupt-message posture)."""
    if isinstance(raw, str):
        raw = raw.encode()
    raw = bytes(raw)
    if raw.startswith(WIRE_MAGIC):
        try:
            header = _HEADER.unpack_from(raw, 0)
            frame = _FRAME_OF_CODE.get(header[2])
            if header[1] != WIRE_VERSION or frame is None:
                raise ValueError(f"wire frame of version {header[1]}, "
                                 f"family {header[2]}")
            return frame.family, frame.decode(raw, header)
        except struct.error as e:
            raise ValueError(f"wire frame truncated: {e}") from e
    j = json.loads(raw)
    kind = j.get("whiskBatch")
    if kind == KIND_FUNNEL:
        # the funnel frame decodes to ONE header-carrying object, not a
        # message list — only the funnel receiver consumes this kind
        return kind, FunnelBatchMessage.from_json(j)
    if kind == KIND_FUNNEL_ACK:
        return kind, FunnelAckMessage.from_json(j)
    raise ValueError(f"unknown batch kind {kind!r}")
