"""BEFS small-request fast-frame contract (fast1).

Mirrors the oob1 interop suite in test_rpc_transport.py: property-style
round-trip bit-identity against the legacy codec, transparent fallback
for anything a fast frame cannot carry (traces, spans, ndarrays,
oversize values), byte-identical legacy frames for a peer that never
declared fast1, magic dispatch non-collision, hit-rate stats, and
end-to-end negotiation over a real websocket server.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from bioengine_tpu.rpc import protocol
from bioengine_tpu.rpc.client import connect_to_server
from bioengine_tpu.rpc.protocol import (
    CALL,
    ERROR,
    RESULT,
    decode,
    decode_fast,
    encode,
    encode_fast,
    is_fast_frame,
    is_oob_frame,
)
from bioengine_tpu.rpc.server import RpcServer
from bioengine_tpu.rpc.transport import Codec, TransportConfig

pytestmark = [pytest.mark.integration, pytest.mark.anyio]


def call_msg(*args, **kwargs) -> dict:
    return {
        "t": CALL,
        "call_id": "0123456789abcdef",
        "service_id": "ws/client:svc",
        "method": "echo",
        "args": list(args),
        "kwargs": kwargs,
    }


def result_msg(value) -> dict:
    return {"t": RESULT, "call_id": "0123456789abcdef", "result": value}


def assert_identical(a, b) -> None:
    """Equality plus exact-type identity, recursively (1 == 1.0 == True
    under ==, but the wire must preserve which one it was)."""
    assert type(a) is type(b), (a, b)
    if isinstance(a, list):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert_identical(x, y)
    elif isinstance(a, dict):
        assert list(a) == list(b)  # key order preserved like msgpack
        for k in a:
            assert_identical(a[k], b[k])
    elif isinstance(a, float):
        assert a == b or (a != a and b != b)  # NaN-proof
    else:
        assert a == b


def both_roundtrips(msg: dict):
    """Decode msg through BEFS and through the legacy codec."""
    frame = encode_fast(msg)
    assert frame is not None, f"expected fast-eligible: {msg}"
    assert is_fast_frame(frame)
    return decode_fast(frame), decode(encode(msg))


SMALL_PAYLOADS = [
    (),
    (0,),
    (-1, 2**62, -(2**62), 1.5, -0.0),
    ("", "hello", "unié中"),
    (b"", b"\x00\xff" * 16),
    (None, True, False),
    ([1, "a", None], {"k": 1, "j": [2.5]}),
    # the replica_call envelope shape: [replica_id, method, [args], {kwargs}]
    ("rep-0", "forward", [1, "x"], {"scale": 2.0}),
    (float("nan"), float("inf"), -float("inf")),
]


class TestFastCodec:
    @pytest.mark.parametrize("args", SMALL_PAYLOADS, ids=str)
    def test_call_roundtrip_matches_legacy(self, args):
        msg = call_msg(*args, flag=True, n=3)
        fast, legacy = both_roundtrips(msg)
        assert_identical(fast, legacy)
        # and the legacy re-encode of both decodes is byte-identical
        assert encode(fast) == encode(legacy)

    @pytest.mark.parametrize(
        "value",
        [None, True, 0, -7, 3.25, "ok", b"\x01\x02", [1, [2, [3]]],
         {"a": {"b": 1}}, {"ok": True, "v": [1, 2, 3]}],
        ids=str,
    )
    def test_result_roundtrip_matches_legacy(self, value):
        fast, legacy = both_roundtrips(result_msg(value))
        assert_identical(fast, legacy)

    def test_property_random_small_payloads(self):
        rng = random.Random(1234)

        def gen_value(depth: int):
            kinds = ["none", "bool", "int", "float", "str", "bytes"]
            if depth < 3:
                kinds += ["list", "dict"]
            k = rng.choice(kinds)
            if k == "none":
                return None
            if k == "bool":
                return rng.random() < 0.5
            if k == "int":
                return rng.randint(-(2**63), 2**63 - 1)
            if k == "float":
                return rng.uniform(-1e9, 1e9)
            if k == "str":
                return "".join(
                    chr(rng.randint(32, 0x2FF))
                    for _ in range(rng.randint(0, 24))
                )
            if k == "bytes":
                return rng.randbytes(rng.randint(0, 32))
            if k == "list":
                return [gen_value(depth + 1) for _ in range(rng.randint(0, 4))]
            return {
                f"k{i}": gen_value(depth + 1)
                for i in range(rng.randint(0, 4))
            }

        for _ in range(300):
            args = [gen_value(0) for _ in range(rng.randint(0, 4))]
            kwargs = {f"kw{i}": gen_value(0) for i in range(rng.randint(0, 3))}
            msg = call_msg(*args, **kwargs)
            fast, legacy = both_roundtrips(msg)
            assert_identical(fast, legacy)

    def test_tuple_args_become_lists_like_msgpack(self):
        msg = call_msg((1, 2, "x"))
        fast, legacy = both_roundtrips(msg)
        assert_identical(fast, legacy)
        assert fast["args"][0] == [1, 2, "x"]

    @pytest.mark.parametrize(
        "msg",
        [
            call_msg(np.arange(4)),                       # ndarray arg
            call_msg(np.float32(1.5)),                    # np scalar
            call_msg("x" * 5000),                         # over threshold
            call_msg(2**70),                              # >64-bit int
            call_msg(memoryview(b"abc")),                 # non-bytes buffer
            {**call_msg(1), "trace": {"tid": "t", "sid": "s"}},
            {**result_msg(1), "spans": [{"n": "x"}]},
            {"t": ERROR, "call_id": "c", "error": "boom"},
            {"t": protocol.PING},
            result_msg(ValueError("boom")),               # exception result
            {"t": CALL, "call_id": "c", "service_id": "s",
             "method": "m", "args": [1], "kwargs": {1: "non-str key"}},
        ],
        ids=lambda m: str(m.get("t")) + ":" + str(len(str(m)))
        if isinstance(m, dict) else str(m),
    )
    def test_ineligible_messages_fall_back(self, msg):
        assert encode_fast(msg) is None

    def test_threshold_knob(self):
        msg = call_msg("y" * 1000)
        assert encode_fast(msg, limit=256) is None
        assert encode_fast(msg, limit=4096) is not None
        cfg = TransportConfig(fast_threshold=256)
        codec = Codec(config=cfg)
        codec.fast = True
        frames = codec.encode_frames(msg)
        assert not is_fast_frame(frames[0])
        assert codec.stats.fast_fallbacks == 1

    def test_magic_cannot_collide(self):
        legacy = encode(call_msg(1))
        oob = protocol.encode_oob(call_msg(1))
        fast = encode_fast(call_msg(1))
        assert not is_fast_frame(legacy)
        assert not is_fast_frame(oob)
        assert not is_oob_frame(fast)
        assert not protocol.is_chunk_frame(fast)
        assert is_fast_frame(fast)


class TestFastCodecTransport:
    def _pair(self):
        enc = Codec()
        enc.fast = True
        enc.oob = True
        dec = Codec()
        return enc, dec

    def test_codec_fast_path_and_stats(self):
        enc, dec = self._pair()
        msg = call_msg(1, "a", scale=2.0)
        frames = enc.encode_frames(msg)
        assert len(frames) == 1 and is_fast_frame(frames[0])
        out = dec.decode(frames[0])
        assert_identical(out, decode(encode(msg)))
        assert enc.stats.small_frames_out == 1
        assert dec.stats.small_frames_in == 1

    def test_transparent_fallback_keeps_payload_fidelity(self):
        enc, dec = self._pair()
        dec.oob = True
        arr = np.arange(1 << 12, dtype=np.float32)
        frames = enc.encode_frames(call_msg(arr))
        assert not is_fast_frame(frames[0])
        np.testing.assert_array_equal(dec.decode(frames[0])["args"][0], arr)
        assert enc.stats.fast_fallbacks == 1
        assert enc.stats.small_frames_out == 0
        d = enc.stats.as_dict()
        assert d["fast_frame_hit_rate"] == 0.0

    def test_hit_rate_accounting(self):
        enc, _ = self._pair()
        enc.encode_frames(call_msg(1))
        enc.encode_frames(call_msg(1))
        enc.encode_frames(call_msg(np.arange(8)))
        enc.encode_frames({"t": protocol.PING})  # not a hot envelope
        d = enc.stats.as_dict()
        assert enc.stats.small_frames_out == 2
        assert enc.stats.fast_fallbacks == 1
        assert d["fast_frame_hit_rate"] == round(2 / 3, 4)

    def test_legacy_peer_sees_byte_identical_legacy_frames(self):
        """A codec WITHOUT negotiated fast1 (or oob1) must emit exactly
        what a pre-fast1 build would — byte identity, not just value
        identity."""
        plain = Codec()
        assert plain.fast is False and plain.oob is False
        msg = call_msg(1, "a", k=2.5)
        assert plain.encode_frames(msg) == [encode(msg)]
        # a fast-enabled codec falling back on an ineligible message
        # emits the same full-codec bytes too
        fast_codec = Codec()
        fast_codec.fast = True
        ineligible = {**call_msg(2), "trace": {"tid": "t", "sid": "s"}}
        assert fast_codec.encode_frames(ineligible) == [encode(ineligible)]

    async def test_async_encode_skips_payload_walk(self):
        enc, dec = self._pair()
        frames = await enc.encode_frames_async(call_msg(1, 2, 3))
        assert is_fast_frame(frames[0])
        out = await dec.decode_async(frames[0])
        assert out["args"] == [1, 2, 3]


# ---------------------------------------------------------------------------
# end-to-end over a real websocket server
# ---------------------------------------------------------------------------


@pytest.fixture
async def echo_server():
    srv = RpcServer(shm_store=None)
    await srv.start()
    srv.register_local_service(
        {"id": "echo", "echo": lambda a: a, "add": lambda a, b: a + b}
    )
    yield srv
    await srv.stop()


class TestEndToEnd:
    async def test_fast1_negotiated_and_used(self, echo_server):
        conn = await connect_to_server(
            {
                "server_url": f"http://127.0.0.1:{echo_server.port}",
                "shm_store": None,
            }
        )
        try:
            assert conn.codec.fast is True
            assert protocol.PROTO_FAST1 in conn.peer_protocols
            out = await conn.call("bioengine/echo", "add", 2, 3)
            assert out == 5
            # request rode a fast frame, and so did the result
            assert conn.codec.stats.small_frames_out >= 1
            assert conn.codec.stats.small_frames_in >= 1
            assert conn.describe()["fast"] is True
            assert (
                conn.describe()["transport"]["fast_frame_hit_rate"] is not None
            )
        finally:
            await conn.disconnect()

    async def test_fast1_connection_falls_back_for_arrays(self, echo_server):
        conn = await connect_to_server(
            {
                "server_url": f"http://127.0.0.1:{echo_server.port}",
                "shm_store": None,
            }
        )
        try:
            arr = np.arange(1 << 14, dtype=np.float32)
            out = await conn.call("bioengine/echo", "echo", arr)
            np.testing.assert_array_equal(out, arr)
            assert conn.codec.stats.fast_fallbacks >= 1
            # and small calls still use fast frames on the same conn
            assert await conn.call("bioengine/echo", "add", 1, 1) == 2
            assert conn.codec.stats.small_frames_out >= 1
        finally:
            await conn.disconnect()

    async def test_no_fast1_peer_never_receives_befs(self, echo_server):
        conn = await connect_to_server(
            {
                "server_url": f"http://127.0.0.1:{echo_server.port}",
                "protocols": [protocol.PROTO_OOB1],  # pre-fast1 peer
                "shm_store": None,
            }
        )
        try:
            assert conn.codec.fast is False
            assert await conn.call("bioengine/echo", "add", 2, 2) == 4
            assert conn.codec.stats.small_frames_in == 0
            assert conn.codec.stats.small_frames_out == 0
        finally:
            await conn.disconnect()

    async def test_pure_legacy_peer_interop(self, echo_server):
        conn = await connect_to_server(
            {
                "server_url": f"http://127.0.0.1:{echo_server.port}",
                "protocols": [],       # pre-oob, pre-fast peer
                "shm_store": None,
            }
        )
        try:
            assert await conn.call("bioengine/echo", "add", 3, 4) == 7
            assert conn.codec.stats.legacy_msgs_out >= 1
            assert conn.codec.stats.small_frames_in == 0
        finally:
            await conn.disconnect()

    async def test_unix_socket_transport(self, tmp_path):
        sock = str(tmp_path / "rpc.sock")
        srv = RpcServer(shm_store=None, uds_path=sock)
        await srv.start()
        srv.register_local_service(
            {"id": "echo", "add": lambda a, b: a + b}
        )
        try:
            conn = await connect_to_server(
                {"server_url": f"unix://{sock}", "shm_store": None}
            )
            try:
                assert conn.codec.fast is True
                assert await conn.call("bioengine/echo", "add", 8, 9) == 17
                assert conn.codec.stats.small_frames_out >= 1
            finally:
                await conn.disconnect()
        finally:
            await srv.stop()
