"""ISSUE 12: columnar hot path — batch wire records, batch-shaped
completion pipeline, sharded front end. ISSUE 35: the records are ONE
struct-packed frame for activations and acks, from one row up.

Covers the acceptance contracts:
  * wire parity: a frame decodes to messages equal, field for field, to
    what the serial parsers make of the same messages' JSON (1, 2, 8 and
    64 rows, every optional column), every batch payload sniffs as one
    while plain payloads never do, and a frame is never taken for JSON;
  * the record's status: ack flag bits 4-5 carry the response's
    `statusCode`, a relay passes it on unparsed, and a blocking answer
    is the record's framed bytes with the status the bits give; every
    other record is answered from the entity as before;
  * the frame's edges: a truncated or garbled frame raises what the
    feeds' handlers catch and nothing of it is applied (a frame of
    another version too); the decoder's
    tables are bounded and a blob that differs in one byte is parsed
    anew; the encoder keeps a blob exactly as long as its object; the
    gauges and the `interned` stat count what they say;
  * encode-exactly-once: a message riding a frame is serialized once, at
    flush, with the serde byte counters seeing exactly the frame's bytes;
  * off-switches: batchWire=false ships byte-identical serial payloads;
    batchedAck=false replays a decoded frame through the serial per-ack
    path with identical state transitions;
  * out-of-order / partial batch acks: a completion frame spanning two
    dispatch batches, and a frame holding an ack for an evicted entry,
    must not desync the waterfall stamps or the inflight gauge;
  * sharded front end: shards=1 builds nothing (bit-exact default);
    shards>=2 decides per-namespace sequences exactly like the serial
    path (parity fuzz) and propagates the serial exceptions.
"""
from __future__ import annotations

import asyncio
import json
import random
import time

import pytest

from aiohttp import web

from openwhisk_tpu.controller.api import _record_answer
from openwhisk_tpu.controller.entitlement import (ACTIVATE,
                                                  LocalEntitlementProvider,
                                                  ThrottleRejectRequest)
from openwhisk_tpu.controller.frontend import (FrontendConfig,
                                               FrontendShardPlane,
                                               maybe_shard_frontend)
from openwhisk_tpu.core.entity import (ActivationId, ActivationResponse,
                                       ControllerInstanceId, EntityPath,
                                       Identity, InvokerInstanceId, MB,
                                       WhiskActivation)
from openwhisk_tpu.core.entity.names import FullyQualifiedEntityName
from openwhisk_tpu.messaging import MemoryMessagingProvider
from openwhisk_tpu.messaging.coalesce import CoalescingProducer
from openwhisk_tpu.messaging import columnar
from openwhisk_tpu.messaging.columnar import (KIND_ACK, KIND_ACTIVATION,
                                              LazyWhiskActivation,
                                              batchable_family,
                                              is_batch_payload, make_batch,
                                              parse_batch)
from openwhisk_tpu.messaging.connector import decode_batch, encode_batch
from openwhisk_tpu.messaging.message import (ActivationMessage,
                                             CombinedCompletionAndResultMessage,
                                             CompletionMessage, PingMessage,
                                             ResultMessage, parse_ack)
from openwhisk_tpu.utils.transaction import TransactionId
from openwhisk_tpu.utils.waterfall import (ActivationWaterfall,
                                           STAGE_COMPLETION_ACK,
                                           STAGE_PUBLISH_ENQUEUE,
                                           WaterfallConfig)


def _ident(ns="guest"):
    return Identity.generate(ns)


def _act_msg(ident, name="act0", i=0, **kw):
    return ActivationMessage(
        TransactionId(), FullyQualifiedEntityName.parse(f"guest/{name}"),
        "1-b", ident, ActivationId.generate(), ControllerInstanceId("0"),
        bool(i % 2), {"x": i}, **kw)


def _activation(ident, msg):
    now = time.time()
    return WhiskActivation(
        EntityPath("guest"), msg.action.name, ident.subject,
        msg.activation_id, now, now,
        ActivationResponse.success({"ok": True}), duration=1)


def _frame(family, msgs) -> bytes:
    payload, frame = encode_batch(family, msgs)
    assert frame.activation_ids == [m.activation_id.asString for m in msgs]
    return payload


def _ack_fields(a) -> dict:
    """An ack as the serial wire spells it; `updated` is stamped at
    to_json() call time, so it is no field of the message."""
    j = a.to_json()
    if j["response"] is not None:
        j["response"].pop("updated")
    return j


#: a result as large as `shrink` leaves it: the cap's own default
RESULT_AT_THE_CAP = {"blob": "x" * (1024 * 1024 - 64)}


def _varied_activations(rng, n):
    """N messages over three identities and four actions that between
    them carry every optional column, unicode and empty arguments."""
    idents = [_ident(f"ns{k}") for k in range(3)]
    contents = [{}, None, {"x": 1}, {"\u00fc\u00f1\u00ee": "\u6f22\u5b57 \U0001f600"},
                {"nested": {"a": [1, 2.5, None, True]}}, {"": ""}]
    msgs = []
    for i in range(n):
        kw = {}
        if rng.random() < 0.3:
            kw["cause"] = ActivationId.generate()
        if rng.random() < 0.3:
            kw["trace_context"] = {"traceparent": f"00-{i}"}
        if rng.random() < 0.3:
            kw["init_args"] = {"k": i, "\u00e9": "\u00e8"}
        if rng.random() < 0.5:
            kw["fence_epoch"] = rng.choice([3, 3, 7])
            if rng.random() < 0.5:
                kw["fence_part"] = rng.randrange(8)
        m = _act_msg(idents[rng.randrange(3)], name=f"a{i % 4}", i=i, **kw)
        m.content = contents[rng.randrange(len(contents))]
        m.transid = TransactionId(f"tid_\u00e4_{i}",
                                  start_wallclock=rng.random() * 2e9)
        msgs.append(m)
    return msgs


def _varied_acks(rng, n):
    """N acks of all three kinds from three invokers (and none), some
    system errors, some traced, one result at `shrink`'s cap."""
    ident = _ident()
    invs = [InvokerInstanceId(k, user_memory=MB(512 * (k + 1)))
            for k in range(3)]
    acks = []
    for i in range(n):
        msg = _act_msg(ident, i=i)
        msg.transid = TransactionId(f"tid_{i}",
                                    start_wallclock=rng.random() * 2e9)
        inv = invs[rng.randrange(3)]
        kind = rng.randrange(3)
        if kind == 0:
            ack = CompletionMessage(msg.transid, msg.activation_id,
                                    rng.random() < 0.5, inv)
        else:
            act = _activation(ident, msg)
            if rng.random() < 0.2:
                act.response = ActivationResponse.whisk_error("boom")
            if i == 1:
                act.response = ActivationResponse.success(RESULT_AT_THE_CAP)
            ack = ResultMessage(msg.transid, act) if kind == 1 else \
                CombinedCompletionAndResultMessage(msg.transid, act, inv)
        if rng.random() < 0.3:
            ack.trace_context = {"traceparent": f"00-{i}"}
        acks.append(ack.shrink())
    return acks


class TestBatchWireRecords:
    @pytest.mark.parametrize("n", [1, 2, 8, 64])
    def test_activation_frame_equals_the_serial_parser(self, n):
        rng = random.Random(n)
        for _trial in range(4):
            msgs = _varied_activations(rng, n)
            raw = _frame(KIND_ACTIVATION, msgs)
            assert is_batch_payload(raw)
            kind, out = decode_batch(raw)
            assert kind == KIND_ACTIVATION and len(out) == n
            for m, b in zip(msgs, out):
                a = ActivationMessage.parse(m.serialize())
                assert a.to_json() == b.to_json() == m.to_json()
                assert (a.user, a.action, a.revision, a.activation_id,
                        a.blocking, a.content, a.init_args, a.cause,
                        a.trace_context, a.fence_epoch, a.fence_part) == \
                    (b.user, b.action, b.revision, b.activation_id,
                     b.blocking, b.content, b.init_args, b.cause,
                     b.trace_context, b.fence_epoch, b.fence_part)
                assert a.root_controller_index.name == \
                    b.root_controller_index.name
                assert a.transid.id == b.transid.id
                # bit for bit, not to a tolerance
                assert a.transid.start_wallclock.hex() == \
                    b.transid.start_wallclock.hex() == \
                    m.transid.start_wallclock.hex()

    @pytest.mark.parametrize("n", [1, 2, 8, 64])
    def test_ack_frame_equals_the_serial_parser(self, n):
        rng = random.Random(100 + n)
        acks = _varied_acks(rng, n)
        raw = _frame(KIND_ACK, acks)
        assert is_batch_payload(raw)
        kind, out = decode_batch(raw)
        assert kind == KIND_ACK and len(out) == n
        for m, b in zip(acks, out):
            a = parse_ack(m.serialize())
            # the frame defers the response: nothing is parsed yet
            assert b.activation is None or (
                isinstance(b.activation, LazyWhiskActivation)
                and not b.activation.materialized)
            assert (a.kind, a.activation_id, a.invoker, a.is_system_error,
                    a.is_slot_free, a.trace_context, a.transid.id) == \
                (b.kind, b.activation_id, b.invoker, b.is_system_error,
                 b.is_slot_free, b.trace_context, b.transid.id)
            if a.invoker is not None:
                assert a.invoker.to_json() == b.invoker.to_json()
            assert a.transid.start_wallclock.hex() == \
                b.transid.start_wallclock.hex()
            assert _ack_fields(a) == _ack_fields(b) == _ack_fields(m)
            if b.activation is not None:
                # the record's own bytes, as the invoker encoded them
                j = json.loads(b.activation.raw)
                j.pop("updated")
                assert j == _ack_fields(m)["response"]

    def test_an_id_that_is_not_32_hex_rides_the_sparse_column(self):
        """No ActivationId is such, but the wire round-trips what it is
        given: the string travels as it is and the decoder's constructor
        judges it, as the serial parser's does."""
        ident = _ident()
        odd = _act_msg(ident)
        odd.activation_id = ActivationId.of_hex("ABCDEF" + "0" * 26)
        fine = _act_msg(ident, i=1)
        _kind, out = parse_batch(_frame(KIND_ACTIVATION, [odd, fine]))
        # the constructor lowercases, exactly as ActivationMessage.parse
        assert out[0].activation_id == \
            ActivationMessage.parse(odd.serialize()).activation_id
        assert out[1].activation_id == fine.activation_id
        odd.activation_id = ActivationId.of_hex("not an id")
        with pytest.raises(ValueError):
            parse_batch(_frame(KIND_ACTIVATION, [odd]))
        with pytest.raises(ValueError):
            ActivationMessage.parse(odd.serialize())

    def test_plain_payloads_never_sniff_as_batch(self):
        ident = _ident()
        msg = _act_msg(ident)
        assert not is_batch_payload(msg.serialize())
        ack = CombinedCompletionAndResultMessage(
            msg.transid, _activation(ident, msg),
            InvokerInstanceId(0, user_memory=MB(512)))
        assert not is_batch_payload(ack.serialize())
        assert not is_batch_payload(PingMessage(
            InvokerInstanceId(0, user_memory=MB(512))).serialize())
        assert not is_batch_payload(msg.serialize().decode())
        # ... and a frame is never taken for JSON: no parser of the
        # serial wire reads past its first byte
        for raw in (_frame(KIND_ACTIVATION, [msg]), _frame(KIND_ACK, [ack])):
            assert raw[:1] not in b"{[ \t\r\n"
            assert is_batch_payload(raw)
            assert is_batch_payload(bytearray(raw))
            with pytest.raises(ValueError):
                json.loads(raw)
            with pytest.raises(ValueError):
                ActivationMessage.parse(raw)
            with pytest.raises(ValueError):
                parse_ack(raw)

    def test_batchable_family(self):
        ident = _ident()
        msg = _act_msg(ident)
        assert batchable_family(msg) == KIND_ACTIVATION
        assert batchable_family(
            ResultMessage(msg.transid, _activation(ident, msg))) == KIND_ACK
        assert batchable_family(PingMessage(
            InvokerInstanceId(0, user_memory=MB(512)))) is None
        with pytest.raises(ValueError):
            make_batch("ping", [msg])

    def test_dedup_tables_shrink_the_frame(self):
        """The frame's dedup must beat N serial encodes on a same-user
        batch — and a lone message must not pay for the framing."""
        ident = _ident()
        msgs = [_act_msg(ident, name=f"a{i % 2}", i=i) for i in range(16)]
        batch_bytes = len(_frame(KIND_ACTIVATION, msgs))
        serial_bytes = sum(len(m.serialize()) for m in msgs)
        assert batch_bytes < serial_bytes / 2
        assert len(_frame(KIND_ACTIVATION, msgs[:1])) < \
            len(msgs[0].serialize())


def _combined_ack(code: int):
    """A combined ack whose record has the status `code`."""
    ident = _ident()
    msg = _act_msg(ident)
    act = _activation(ident, msg)
    act.response = ActivationResponse(code, {"ok": True} if code == 0
                                      else {"error": f"status {code}"})
    return CombinedCompletionAndResultMessage(
        msg.transid, act, InvokerInstanceId(0, user_memory=MB(512)))


class TestRecordStatus:
    @pytest.mark.parametrize("code,http", [(0, 200), (1, 502), (2, 502),
                                           (3, 502)])
    def test_the_status_code_rides_ack_flag_bits_4_and_5(self, code, http):
        raw = _frame(KIND_ACK, [_combined_ack(code)])
        # a one-row ack frame of one invoker: the header, one blob length
        # and one wall clock lie before its flags byte
        flags = raw[columnar._HEADER.size + 4 + 8]
        assert (flags & 3, flags >> 4) == (2, code)
        _kind, (ack,) = parse_batch(raw)
        lazy = ack.activation
        assert lazy.status_code == code and not lazy.materialized
        resp, framed = _record_answer(lazy, result_only=False)
        assert (resp.status, framed, resp.body) == (http, 1, lazy.raw)
        assert (resp.content_type, resp.charset) == ("application/json",
                                                      "utf-8")
        assert not lazy.materialized
        # the parse, when somebody reads the record, agrees with the bits
        assert lazy.response.status_code == code

    def test_a_lazy_relay_passes_its_status_on_unparsed(self):
        codes = [0, 3, 1, 2, 0]
        raw = _frame(KIND_ACK, [_combined_ack(c) for c in codes])
        _kind, acks = parse_batch(raw)
        relayed = _frame(KIND_ACK, acks)
        assert not any(a.activation.materialized for a in acks)
        _kind, again = parse_batch(relayed)
        assert [a.activation.status_code for a in again] == codes
        assert [a.activation.raw for a in again] == \
            [a.activation.raw for a in acks]

    @pytest.mark.parametrize("code,http", [(0, 200), (2, 502)])
    @pytest.mark.parametrize("case", ["result", "serial_wire", "store",
                                      "parsed"])
    def test_any_other_record_is_answered_as_before(self, case, code, http):
        """`?result=true`, the serial wire's ack (`batchWire=false`), a
        record polled from the store and a framed record somebody already
        read: the entity's dump, as `json_response` made it before."""
        ack = _combined_ack(code)
        if case == "serial_wire":
            record = parse_ack(ack.serialize()).activation
        elif case == "store":
            record = WhiskActivation.from_json(ack.activation.to_json())
        else:
            _kind, (decoded,) = parse_batch(_frame(KIND_ACK, [ack]))
            record = decoded.activation
            if case == "parsed":
                assert record.activation_id == ack.activation_id
        result_only = case == "result"
        resp, framed = _record_answer(record, result_only)
        before = web.json_response(
            record.resulting_json() if result_only else record.to_json(),
            status=http)
        assert (resp.status, framed, resp.body) == (http, 0, before.body)
        assert resp.headers["Content-Type"] == before.headers["Content-Type"]


def _mutilations(raw: bytes):
    """(name, bytes) of frames that must not decode."""
    header = columnar._HEADER
    fields = list(header.unpack_from(raw, 0))

    def with_header(**kw):
        f = list(fields)
        for name, v in kw.items():
            f[("magic", "version", "family", "n", "t0", "t1", "t2",
               "sparse").index(name)] = v
        return header.pack(*f) + raw[header.size:]

    yield "cut inside the header", raw[:10]
    yield "cut inside the columns", raw[:header.size + 5]
    yield "cut inside a table blob", raw[:header.size + 60]
    yield "last byte gone", raw[:-1]
    yield "last row gone", raw[:-20]
    yield "a byte too many", raw + b"\x00"
    yield "the version before", with_header(version=columnar.WIRE_VERSION - 1)
    yield "a later version", with_header(version=columnar.WIRE_VERSION + 1)
    yield "an unknown family", with_header(family=9)
    yield "more rows than it holds", with_header(n=fields[3] + 1)
    yield "fewer rows than it holds", with_header(n=fields[3] - 1)
    yield "a table larger than it is", with_header(t0=fields[4] + 1)
    yield "a sparse section that is not there", with_header(sparse=7)
    # a row pointing past its table: the index column is the columns'
    # last; 0xFFFE is no row of any table (0xFFFF = an ack without invoker)
    idx_end = header.size + columnar._columns_struct(
        fields[4] + fields[5] + fields[6], fields[3],
        3 if fields[2] == 1 else 1).size
    yield "an index past its table", \
        raw[:idx_end - 2] + b"\xfe\xff" + raw[idx_end:]
    blob_at = raw.index(b'{"')   # the first table blob's JSON
    yield "a garbled table blob", \
        raw[:blob_at] + b"\x00\x01" + raw[blob_at + 2:]


class TestFrameEdges:
    @pytest.mark.parametrize("family", [KIND_ACTIVATION, KIND_ACK])
    def test_a_mutilated_frame_raises_what_the_handlers_catch(self, family):
        rng = random.Random(5)
        msgs = _varied_activations(rng, 3) if family == KIND_ACTIVATION \
            else [a for a in _varied_acks(rng, 4)
                  if a.invoker is not None][:2]
        raw = _frame(family, msgs)
        parse_batch(raw)
        seen = 0
        for name, bad in _mutilations(raw):
            # what `process_acknowledgement_frame` and the invoker's
            # `_process_batch` catch and log
            with pytest.raises((ValueError, KeyError, IndexError,
                                TypeError)):
                parse_batch(bad)
                pytest.fail(f"{name}: decoded")
            seen += 1
        assert seen == 15

    def test_a_corrupt_ack_frame_applies_none_of_its_acks(self):
        async def go():
            bal = _mk_balancer()
            msgs, inv, ident = TestBatchedAckPipeline()._setup_entries(bal, 3)
            acks = [CombinedCompletionAndResultMessage(
                m.transid, _activation(ident, m), inv) for m in msgs]
            raw = _frame(KIND_ACK, acks)
            before = bal.metrics.snapshot()["counters"]
            for _name, bad in _mutilations(raw):
                bal.process_acknowledgement_frame(bad)
            # an activation frame on the completion topic is refused too
            bal.process_acknowledgement_frame(_frame(KIND_ACTIVATION, msgs))
            assert bal.total_active_activations == 3
            assert len(bal.activation_slots) == 3
            assert bal.waterfall._finished == 0
            assert bal.metrics.snapshot()["counters"] == before
            bal.process_acknowledgement_frame(raw)
            assert bal.total_active_activations == 0
            await bal.close()

        asyncio.run(go())

    def test_a_corrupt_activation_frame_costs_the_invoker_one_unit(self):
        """The invoker's handler logs, hands its feed the one capacity
        unit back and runs nothing."""
        from openwhisk_tpu.invoker.reactive import InvokerReactive

        class _Feed:
            released = 0

            def processed(self):
                self.released += 1

            def consume_extra(self, n):
                raise AssertionError("a corrupt frame booked rows")

        class _Log:
            errors = []

            def error(self, _tid, text, *_a):
                self.errors.append(text)

        async def go():
            inv = object.__new__(InvokerReactive)
            inv.logger = _Log()
            feed = _Feed()
            raw = _frame(KIND_ACTIVATION,
                         _varied_activations(random.Random(2), 2))
            n = 0
            for _name, bad in _mutilations(raw):
                await inv._process(bad, feed)
                n += 1
            # an ack frame on an invoker's topic is no activation batch
            await inv._process(_frame(KIND_ACK, _varied_acks(
                random.Random(2), 2)), feed)
            assert feed.released == n + 1
            assert len(inv.logger.errors) == n + 1
            assert all("corrupt activation batch" in e
                       for e in inv.logger.errors)

        asyncio.run(go())

    def test_decoder_table_is_bounded_and_a_byte_is_a_new_object(self,
                                                                  monkeypatch):
        from openwhisk_tpu.core.entity.identity import UserLimits
        import dataclasses
        ident = _ident()
        # the same subject, namespace and authkey with other limits: one
        # digit of the blob differs
        tighter = dataclasses.replace(
            ident, limits=UserLimits(invocations_per_minute=60))
        looser = dataclasses.replace(
            ident, limits=UserLimits(invocations_per_minute=61))
        users = columnar._USERS
        users.objects.clear()
        a1, b1 = parse_batch(_frame(KIND_ACTIVATION, [
            _act_msg(tighter), _act_msg(looser, i=1)]))[1]
        assert a1.user == tighter and b1.user == looser
        assert a1.user.authkey == b1.user.authkey
        assert a1.user is not b1.user and len(users.objects) == 2
        # seen before: the very same objects, whatever frame names them
        a2, b2, c2 = parse_batch(_frame(KIND_ACTIVATION, [
            _act_msg(tighter), _act_msg(looser), _act_msg(tighter)]))[1]
        assert a2.user is a1.user is c2.user and b2.user is b1.user
        assert len(users.objects) == 2
        # bounded: a table at its bound is reset whole, never grown, and
        # what it held is parsed anew, equal and not the same
        monkeypatch.setattr(columnar, "INTERN_BOUND", 4)
        for k in range(9):
            parse_batch(_frame(KIND_ACTIVATION, [_act_msg(_ident(f"ns{k}"))]))
            assert len(users.objects) <= 4
        a3 = parse_batch(_frame(KIND_ACTIVATION, [_act_msg(tighter)]))[1][0]
        assert a3.user == a1.user and a3.user is not a1.user
        encoder = columnar._ACTION_BLOBS
        encoder.clear()
        for k in range(9):
            _frame(KIND_ACTIVATION, [_act_msg(ident, name=f"fresh{k}")])
            assert len(encoder) <= 4

    def test_encoder_keeps_a_blob_as_long_as_its_object(self):
        import gc
        ident = _ident()
        kept = columnar._BLOBS_BESIDE
        hits = columnar.WIRE_STATS["blob_hits"]
        raw1 = _frame(KIND_ACTIVATION, [_act_msg(ident)])
        assert id(ident) in kept
        blob = kept[id(ident)][1]
        assert json.loads(blob) == ident.to_json()
        # encoded once in its life: the second frame reuses the bytes
        hits = columnar.WIRE_STATS["blob_hits"]
        _frame(KIND_ACTIVATION, [_act_msg(ident), _act_msg(ident, i=1)])
        assert kept[id(ident)][1] is blob
        # user, action and controller of both rows; the first row's
        # action and controller may be new
        assert 4 <= columnar.WIRE_STATS["blob_hits"] - hits <= 6
        # an equal identity that is another object has a blob of its own
        twin = Identity.from_json(ident.to_json())
        assert twin == ident
        _frame(KIND_ACTIVATION, [_act_msg(twin)])
        assert kept[id(twin)][1] is not blob and kept[id(twin)][1] == blob
        key, key_twin = id(ident), id(twin)
        del ident, twin
        gc.collect()
        assert key not in kept and key_twin not in kept
        assert parse_batch(raw1)[0] == KIND_ACTIVATION

    def test_gauges_and_interned_count_what_they_say(self):
        from openwhisk_tpu.messaging.coalesce import export_coalesce_gauges
        from openwhisk_tpu.utils.logging import MetricEmitter

        def gauges():
            m = MetricEmitter()
            export_coalesce_gauges(m)
            return (
                m.gauge_value("bus_wire_frames", {"family": "activation"}),
                m.gauge_value("bus_wire_rows", {"family": "activation"}),
                m.gauge_value("bus_wire_frames",
                              {"family": "completion_ack"}),
                m.gauge_value("bus_wire_rows", {"family": "completion_ack"}),
                m.gauge_value("bus_wire_intern_lookups"),
                m.gauge_value("bus_wire_intern_hits"))

        ident = _ident("gauged")
        inv = InvokerInstanceId(7, user_memory=MB(512))
        msgs = [_act_msg(ident, name="g0", i=i) for i in range(3)]
        acks = [CombinedCompletionAndResultMessage(
            m.transid, _activation(ident, m), inv) for m in msgs[:2]]

        async def go():
            spy = _SpyProducer()
            prod = CoalescingProducer(spy, max_batch=64, batch_wire=True)
            await asyncio.gather(
                *[prod.send("invoker7", m) for m in msgs],
                prod.send("invoker8", _act_msg(ident, name="g1")),
                *[prod.send("completed0", a) for a in acks])
            await prod.flush()
            return [it for batch in spy.shipped for it in batch]

        g0 = gauges()
        items = asyncio.run(go())
        g1 = gauges()
        # three frames: 3 rows and 1 row of activations, 2 rows of acks;
        # encoding looks nothing up
        assert [b - a for a, b in zip(g0, g1)] == [2, 4, 1, 2, 0, 0]
        for _topic, payload, _m in items:
            parse_batch(payload)
        g2 = gauges()
        # one user + one action + one controller for each activation
        # frame, one invoker for the ack frame; the identity, the
        # controller and (second frame) nothing else were seen before
        assert g2[4] - g1[4] == 3 + 3 + 1
        first = g2[5] - g1[5]
        for _topic, payload, _m in items:
            parse_batch(payload)
        g3 = gauges()
        assert g3[4] - g2[4] == 7 and g3[5] - g2[5] == 7   # all seen
        assert 0 <= first <= 7 - 4   # a new user, 2 actions, an invoker

    def test_ack_decode_span_carries_interned(self, monkeypatch):
        """`ow_ack_decode` reads the hits of ITS frame: 0 for an invoker
        never seen, 1 from then on; `ow_produce` the blobs reused."""
        spans = _recorded_spans(monkeypatch)

        async def go():
            bal = _mk_balancer()
            msgs, _inv, ident = TestBatchedAckPipeline()._setup_entries(
                bal, 3)
            inv = InvokerInstanceId(4242, user_memory=MB(512))
            spy = _SpyProducer()
            prod = CoalescingProducer(spy, max_batch=64, batch_wire=True)
            for m in msgs:
                await prod.send("completed0",
                                CombinedCompletionAndResultMessage(
                                    m.transid, _activation(ident, m), inv))
                await prod.flush()
            for batch in spy.shipped:
                for _t, payload, _m in batch:
                    bal.process_acknowledgement_frame(payload)
            await bal.close()

        asyncio.run(go())
        decodes = [s.stats for s in spans if s.name == "ow_ack_decode"]
        assert [(d["acks"], d["interned"]) for d in decodes] == \
            [(1, 0), (1, 1), (1, 1)]
        assert all(d["bytes"] > 0 and "free" in d for d in decodes)
        produces = [s.stats for s in spans if s.name == "ow_produce"]
        assert [(p["n"], p["interned"]) for p in produces] == \
            [(1, 0), (1, 1), (1, 1)]

    def test_produce_span_carries_parked(self, monkeypatch):
        """ISSUE 38: `ow_produce`'s `parked` is 1 where the flush's wave
        found its drainer waiting (a lone flush after a park, a fresh
        drainer's first), 0 on the later flushes of a drainer kept busy;
        `bus_coalesce_parked_flushes` counts the ones."""
        from openwhisk_tpu.messaging.coalesce import export_coalesce_gauges
        from openwhisk_tpu.utils.logging import MetricEmitter
        spans = _recorded_spans(monkeypatch)

        def gauge():
            m = MetricEmitter()
            export_coalesce_gauges(m)
            return m.gauge_value("bus_coalesce_parked_flushes")

        ident = _ident("parked")

        async def go():
            prod = CoalescingProducer(_SpyProducer(), max_batch=2,
                                      batch_wire=True)
            await prod.send("invoker1", _act_msg(ident))        # fresh
            for _ in range(5):
                await asyncio.sleep(0)
            await prod.send("invoker1", _act_msg(ident, i=1))   # parked
            await asyncio.gather(*[                             # one wake,
                prod.send("invoker1", _act_msg(ident, i=i))     # 3 flushes
                for i in range(2, 7)])
            await prod.close()

        g0 = gauge()
        asyncio.run(go())
        produces = [s.stats for s in spans if s.name == "ow_produce"]
        assert [(p["n"], p["parked"]) for p in produces] == \
            [(1, 1), (1, 1), (2, 1), (2, 0), (1, 0)]
        assert gauge() - g0 == 3

    def test_the_tick_exports_how_the_bus_s_consumers_waited(self):
        """`bus_consumer_parks` counts the parks a message ended,
        `bus_consumer_poll_timeouts` those the peek's time-out ended; a
        peek that finds its queue filled parks not at all."""
        from openwhisk_tpu.messaging import MemoryMessagingProvider
        from openwhisk_tpu.messaging.coalesce import export_coalesce_gauges
        from openwhisk_tpu.utils.logging import MetricEmitter

        def gauges():
            m = MetricEmitter()
            export_coalesce_gauges(m)
            return (m.gauge_value("bus_consumer_parks"),
                    m.gauge_value("bus_consumer_poll_timeouts"))

        async def go():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            cons = prov.get_consumer("t", "g")
            assert await cons.peek(4, timeout=0.02) == []       # time-out
            assert await cons.peek(4, timeout=0.02) == []       # time-out
            for _ in range(3):                                  # 3 parks
                parked = asyncio.ensure_future(cons.peek(4, timeout=5.0))
                await asyncio.sleep(0)
                await prod.send("t", b"m")
                assert len(await parked) == 1
            await prod.send("t", b"there-already")
            assert len(await cons.peek(4, timeout=5.0)) == 1    # no park

        g0 = gauges()
        asyncio.run(go())
        g1 = gauges()
        assert (g1[0] - g0[0], g1[1] - g0[1]) == (3, 2)


def _recorded_spans(monkeypatch) -> list:
    """Stand a recorder in for `span` where the bus layer opens one;
    returns the list the spans land in (`.name`, `.stats`)."""
    from openwhisk_tpu.controller.loadbalancer import base
    from openwhisk_tpu.messaging import coalesce
    spans = []

    class _Span:
        def __init__(self, name, **stats):
            self.name, self.stats = name, stats
            spans.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def set_metadata(self, **kw):
            self.stats.update(kw)

    monkeypatch.setattr(base, "span", _Span)
    monkeypatch.setattr(coalesce, "span", _Span)
    return spans


class _SpyProducer:
    """Records send_many items; no transport."""

    def __init__(self):
        self.shipped = []

    async def send_many(self, items):
        self.shipped.append(list(items))

    async def send(self, topic, msg):
        await self.send_many([(topic, msg if isinstance(msg, bytes)
                               else msg.serialize(), msg)])

    async def close(self):
        pass

    @property
    def sent_count(self):
        return sum(len(b) for b in self.shipped)


class TestCoalescerBatchWire:
    def _drive(self, batch_wire: bool, msgs, topic="invoker0"):
        async def go():
            spy = _SpyProducer()
            prod = CoalescingProducer(spy, max_batch=64,
                                      batch_wire=batch_wire)
            await asyncio.gather(*[prod.send(topic, m) for m in msgs])
            await prod.flush()
            return spy.shipped

        return asyncio.run(go())

    def test_batch_wire_one_payload_per_topic(self):
        ident = _ident()
        msgs = [_act_msg(ident, i=i) for i in range(8)]
        shipped = self._drive(True, msgs)
        items = [it for batch in shipped for it in batch]
        assert len(items) == 1
        topic, payload, batch_msg = items[0]
        assert is_batch_payload(payload)
        _kind, out = parse_batch(payload)
        assert [m.activation_id.asString for m in out] == \
            [m.activation_id.asString for m in msgs]
        # the batch message exposes the ids for the produce stamp
        assert batch_msg.activation_ids == \
            [m.activation_id.asString for m in msgs]

    def test_off_switch_serial_payloads_byte_exact(self):
        ident = _ident()
        msgs = [_act_msg(ident, i=i) for i in range(4)]
        shipped = self._drive(False, msgs)
        items = [it for batch in shipped for it in batch]
        assert len(items) == 4
        for (topic, payload, m), orig in zip(items, msgs):
            assert payload == orig.serialize()

    def test_lone_message_is_a_one_row_frame(self):
        """No per-message JSON form behind the coalescing producer: a
        lone activation (and a lone ack) is its family's 1-row frame."""
        ident = _ident()
        msg = _act_msg(ident)
        ack = CompletionMessage(msg.transid, msg.activation_id, False,
                                InvokerInstanceId(0, user_memory=MB(512)))
        for lone, kind in ((msg, KIND_ACTIVATION), (ack, KIND_ACK)):
            shipped = self._drive(True, [lone])
            items = [it for batch in shipped for it in batch]
            assert len(items) == 1
            _topic, payload, frame = items[0]
            assert is_batch_payload(payload)
            got_kind, out = parse_batch(payload)
            assert got_kind == kind and len(out) == 1
            assert out[0].activation_id == lone.activation_id
            assert frame.activation_ids == [lone.activation_id.asString]

    def test_one_unserializable_message_fails_alone(self):
        """Deferring the encode to the flush must not widen one bad
        message's blast radius: its frame-mates ship, it alone fails."""
        ident = _ident()
        good = [_act_msg(ident, i=i) for i in range(3)]
        bad = _act_msg(ident, i=9)
        bad.content = {"unserializable": object()}

        async def go():
            spy = _SpyProducer()
            prod = CoalescingProducer(spy, max_batch=64, batch_wire=True)
            results = await asyncio.gather(
                prod.send("invoker0", good[0]), prod.send("invoker0", bad),
                prod.send("invoker0", good[1]), prod.send("invoker1", bad),
                prod.send("invoker0", good[2]), return_exceptions=True)
            await prod.flush()
            return results, [it for b in spy.shipped for it in b]

        results, items = asyncio.run(go())
        assert [type(r) for r in results] == \
            [type(None), TypeError, type(None), TypeError, type(None)]
        shipped = [m.activation_id for _t, payload, _m in items
                   for m in parse_batch(payload)[1]]
        assert shipped == [m.activation_id for m in good]

    def test_unbatchable_messages_pass_through(self):
        inv = InvokerInstanceId(0, user_memory=MB(512))
        shipped = self._drive(True, [PingMessage(inv), PingMessage(inv)],
                              topic="health")
        items = [it for batch in shipped for it in batch]
        assert len(items) == 2
        for _t, payload, m in items:
            assert not is_batch_payload(payload)

    def test_encode_exactly_once_byte_counted(self):
        """The satellite contract: with the batch wire on, a batched
        message is encoded exactly once — the serde serialize counter
        books exactly the batch payload's bytes, not N message encodes
        plus a re-frame."""
        from openwhisk_tpu.utils.hostprof import GLOBAL_HOST_OBSERVATORY

        ident = _ident()
        msgs = [_act_msg(ident, i=i) for i in range(6)]
        obs = GLOBAL_HOST_OBSERVATORY
        was_enabled = obs.enabled
        obs.enabled = True
        obs.reset()
        try:
            shipped = self._drive(True, msgs)
            items = [it for b in shipped for it in b]
            payload = items[0][1]
            snap = obs.snapshot()
            row = {(r["hop"], r["direction"]): r
                   for r in snap.get("serde", [])}
            ser = row.get(("activation", "serialize"))
            assert ser is not None
            assert ser["count"] == 1
            assert ser["bytes"] == len(payload)
        finally:
            obs.enabled = was_enabled
            obs.reset()

    def test_send_batch_resolves_per_item(self):
        """send_batch awaits one gather over futures; a flush failure
        still propagates to the caller."""
        ident = _ident()

        class _Boom(_SpyProducer):
            async def send_many(self, items):
                raise RuntimeError("bus down")

        async def go():
            prod = CoalescingProducer(_Boom(), max_batch=8,
                                      batch_wire=True)
            with pytest.raises(RuntimeError):
                await prod.send_batch("t", [_act_msg(ident, i=i)
                                            for i in range(3)])

        asyncio.run(go())


def _mk_balancer(monkeypatch=None, batched_ack=True):
    """A CommonLoadBalancer with stub planes, enough for ack processing."""
    from openwhisk_tpu.controller.loadbalancer.base import CommonLoadBalancer
    from openwhisk_tpu.utils.waterfall import ActivationWaterfall

    provider = MemoryMessagingProvider()
    bal = CommonLoadBalancer(provider, ControllerInstanceId("0"),
                             waterfall=ActivationWaterfall(
                                 WaterfallConfig(enabled=True)))
    bal.batched_ack = batched_ack
    return bal


class TestBatchedAckPipeline:
    def _setup_entries(self, bal, n, action=None):
        import bench
        ident = _ident()
        action = action or bench._bench_action("b0", memory=128)
        inv = InvokerInstanceId(0, user_memory=MB(512))
        msgs = []
        for i in range(n):
            m = _act_msg(ident, name="b0", i=i)
            bal.waterfall.begin(m.activation_id.asString)
            bal.waterfall.stamp(m.activation_id.asString,
                                STAGE_PUBLISH_ENQUEUE)
            bal.setup_activation(m, action, inv)
            msgs.append(m)
        return msgs, inv, ident

    def test_batch_ack_frame_completes_all(self):
        async def go():
            bal = _mk_balancer()
            msgs, inv, ident = self._setup_entries(bal, 5)
            acks = [CombinedCompletionAndResultMessage(
                m.transid, _activation(ident, m), inv) for m in msgs]
            raw = _frame(KIND_ACK, acks)
            bal.process_acknowledgement_frame(raw)
            assert bal.total_active_activations == 0
            assert not bal.activation_slots
            # every stage vector folded exactly once
            assert bal.waterfall._finished == 5
            assert bal.waterfall.active == 0
            assert bal.metrics.counter_value(
                "loadbalancer_completion_ack_regular") == 5
            await bal.close()

        asyncio.run(go())

    def test_batched_ack_off_replays_serially_bit_exact(self):
        """batchedAck=false: the frame decodes once but each ack walks
        process_completion — final books identical to the batched path."""
        async def go():
            out = {}
            for flag in (True, False):
                bal = _mk_balancer(batched_ack=flag)
                msgs, inv, ident = self._setup_entries(bal, 4)
                acks = [CombinedCompletionAndResultMessage(
                    m.transid, _activation(ident, m), inv) for m in msgs]
                bal.process_acknowledgement_frame(
                    _frame(KIND_ACK, acks))
                out[flag] = (bal.total_active_activations,
                             len(bal.activation_slots),
                             bal.waterfall._finished,
                             bal.metrics.counter_value(
                                 "loadbalancer_completion_ack_regular"))
                await bal.close()
            assert out[True] == out[False] == (0, 0, 4, 4)

        asyncio.run(go())

    def test_cross_dispatch_batch_acks_no_desync(self):
        """Out-of-order satellite: ONE completion frame acking
        activations from TWO different dispatch batches (interleaved,
        reversed order) — inflight gauge and waterfall must both land at
        zero with every vector folded."""
        async def go():
            bal = _mk_balancer()
            msgs_a, inv, ident = self._setup_entries(bal, 3)
            msgs_b, _, _ = self._setup_entries(bal, 3)
            assert bal.total_active_activations == 6
            mixed = [msgs_b[2], msgs_a[0], msgs_b[0], msgs_a[2],
                     msgs_b[1], msgs_a[1]]
            acks = [CombinedCompletionAndResultMessage(
                m.transid, _activation(ident, m), inv) for m in mixed]
            bal.process_acknowledgement_frame(
                _frame(KIND_ACK, acks))
            assert bal.total_active_activations == 0
            assert bal.waterfall._finished == 6
            assert bal.waterfall.active == 0
            await bal.close()

        asyncio.run(go())

    def test_partial_batch_with_evicted_entry(self):
        """Partial satellite: one ack in the frame targets an entry that
        was already completed (evicted) — it must count as
        regularAfterForced without touching the live entries' books, and
        the rest of the frame completes normally."""
        async def go():
            bal = _mk_balancer()
            msgs, inv, ident = self._setup_entries(bal, 3)
            # evict msgs[1] through the serial path first (a forced
            # timeout), so its later batch ack is a late duplicate
            bal.process_completion(msgs[1].activation_id, forced=True,
                                   is_system_error=False, invoker=inv)
            assert bal.total_active_activations == 2
            acks = [CombinedCompletionAndResultMessage(
                m.transid, _activation(ident, m), inv) for m in msgs]
            bal.process_acknowledgement_frame(
                _frame(KIND_ACK, acks))
            assert bal.total_active_activations == 0
            assert not bal.activation_slots
            assert bal.metrics.counter_value(
                "loadbalancer_completion_ack_regular") == 2
            assert bal.metrics.counter_value(
                "loadbalancer_completion_ack_regularAfterForced") == 1
            # the forced fold + the two batch folds: nothing leaked
            assert bal.waterfall.active == 0
            await bal.close()

        asyncio.run(go())

    def test_finish_many_equals_serial_finish(self):
        wf = ActivationWaterfall(WaterfallConfig(enabled=True))
        wf2 = ActivationWaterfall(WaterfallConfig(enabled=True))
        aids = [f"{i:032x}" for i in range(6)]
        t0 = time.monotonic_ns()
        for w in (wf, wf2):
            for i, aid in enumerate(aids):
                w.begin(aid, t0_ns=t0)
                w.stamp(aid, STAGE_PUBLISH_ENQUEUE, t0 + 1000 * (i + 1))
                w.stamp(aid, STAGE_COMPLETION_ACK, t0 + 2000 * (i + 1))
        for aid in aids:
            wf.finish(aid)
        assert wf2.finish_many(aids) == 6
        assert wf._hist == wf2._hist
        assert wf._sum_us == wf2._sum_us
        assert wf._finished == wf2._finished
        assert wf._total_hist == wf2._total_hist


class TestInvokerBatchPickup:
    def test_feed_consume_extra_backpressure(self):
        from openwhisk_tpu.messaging.connector import MessageFeed

        class _C:
            async def peek(self, n, timeout=0.5):
                return []

            def commit(self):
                pass

            async def close(self):
                pass

        feed = MessageFeed("t", _C(), 4, lambda p: None)
        assert feed.free_capacity == 4
        feed.consume_extra(6)
        assert feed.free_capacity == -2
        for _ in range(7):
            feed.processed()
        assert feed.free_capacity == 5

    def test_echo_fleet_roundtrip_over_batch_wire(self):
        """End-to-end over the memory bus: a coalesced dispatch ships ONE
        batch frame, the echo invoker decodes it once and acks in one
        ack frame, the balancer's batch ack path completes every
        promise. This covers bench's echo + the balancer feed wiring."""
        import bench

        async def go():
            from openwhisk_tpu.controller.loadbalancer import TpuBalancer
            from openwhisk_tpu.controller.loadbalancer.base import HEALTHY
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            feeds, stop = await bench._echo_fleet(provider, 2)
            for _ in range(80):
                health = await bal.invoker_health()
                if sum(h.status == HEALTHY for h in health) >= 2:
                    break
                await asyncio.sleep(0.25)
            ident = _ident()
            action = bench._bench_action("wire0", memory=128)
            msgs = [_act_msg(ident, name="wire0", i=i) for i in range(16)]
            promises = await asyncio.gather(*[
                bal.publish(action, m) for m in msgs])
            results = await asyncio.gather(*[
                asyncio.wait_for(p, 10) for p in promises])
            from openwhisk_tpu.messaging.coalesce import _STATS
            wire_batches = sum(_STATS["wire_frames"].values())
            await stop()
            await bal.close()
            for f in feeds:
                await f.stop()
            return results, wire_batches

        results, wire_batches = asyncio.run(go())
        assert len(results) == 16
        assert all(r.response.is_success for r in results)
        assert wire_batches > 0  # the batch wire actually carried frames


class TestFrontendSharding:
    def test_default_builds_nothing(self):
        p = LocalEntitlementProvider(None)
        assert p.frontend is None
        assert maybe_shard_frontend(p, FrontendConfig(shards=1)) is None

    def test_shard_of_deterministic_and_balanced(self):
        p = LocalEntitlementProvider(
            None, frontend_config=FrontendConfig(shards=4))
        try:
            plane = p.frontend
            assert isinstance(plane, FrontendShardPlane)
            shards = {plane.shard_of(f"ns-{i}") for i in range(64)}
            assert shards == {0, 1, 2, 3}
            assert plane.shard_of("ns-7") == plane.shard_of("ns-7")
        finally:
            plane.close()

    def test_parity_fuzz_vs_serial(self):
        """Per-namespace decision sequences through 3 shards equal the
        single-loop serial path's, including rejection texts."""
        async def drive(provider, idents, seq):
            out = []
            for i in seq:
                try:
                    await provider.check(
                        idents[i], ACTIVATE,
                        str(idents[i].namespace.name), throttle=True)
                    out.append((i, True, None))
                except ThrottleRejectRequest as e:
                    out.append((i, False, e.message))
            return out

        async def go():
            rng = random.Random(13)
            idents = [_ident(f"ns{k}") for k in range(10)]
            seq = [rng.randrange(10) for _ in range(300)]
            serial = LocalEntitlementProvider(None,
                                              invocations_per_minute=15)
            sharded = LocalEntitlementProvider(
                None, invocations_per_minute=15,
                frontend_config=FrontendConfig(shards=3))
            try:
                a = await drive(serial, idents, seq)
                b = await drive(sharded, idents, seq)
            finally:
                await sharded.close()
            from collections import defaultdict
            pa, pb = defaultdict(list), defaultdict(list)
            for i, ok, text in a:
                pa[i].append((ok, text))
            for i, ok, text in b:
                pb[i].append((ok, text))
            assert pa == pb
            assert sharded.frontend.routed == len(seq)

        asyncio.run(go())

    def test_concurrency_throttle_routes_through_shards(self):
        """The concurrency limit (backed by the balancer's counters)
        rejects through the shard plane with the serial message."""
        class _LB:
            def active_activations_for(self, ns):
                return 99

        async def go():
            p = LocalEntitlementProvider(
                _LB(), concurrent_invocations=10,
                frontend_config=FrontendConfig(shards=2))
            try:
                with pytest.raises(ThrottleRejectRequest) as ei:
                    await p.check(_ident(), ACTIVATE, "guest",
                                  throttle=True)
                assert "concurrent" in str(ei.value)
            finally:
                await p.close()

        asyncio.run(go())
