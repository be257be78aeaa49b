"""The benchmark's own GetRateLimits codec (no import of the program).

Requests are built by tiling one hand-encoded ``RateLimitReq`` TLV and
overwriting only the key digits and the ``created_at`` varint, so a
1000-request call costs ~0.1 ms to build.  Responses are parsed with
message classes made here from the public field numbers of upstream's
``gubernator.proto`` (in a private descriptor pool, so they never meet
the program's generated classes).
"""
from __future__ import annotations

import numpy as np
from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

METHOD = "/pb.gubernator.V1/GetRateLimits"
KEY_DIGITS = 10  # hex digits: key ids < 2^40
BEHAVIOR_GLOBAL = 2


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def encode_request(name: str, unique_key: str, hits: int, limit: int,
                   duration: int, algorithm: int = 0, behavior: int = 0,
                   created_at: int = 0) -> bytes:
    """One ``requests`` TLV of GetRateLimitsReq (field 1), proto3: zero
    fields are left out, ``created_at`` is field 10."""
    p = bytearray()
    for tag, s in ((0x0A, name), (0x12, unique_key)):
        raw = s.encode()
        p += bytes([tag]) + _varint(len(raw)) + raw
    for tag, v in ((0x18, hits), (0x20, limit), (0x28, duration),
                   (0x30, algorithm), (0x38, behavior),
                   (0x50, created_at)):
        if v:
            p += bytes([tag]) + _varint(v)
    return b"\x0a" + _varint(len(p)) + bytes(p)


_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)
_SHIFTS = np.arange(KEY_DIGITS - 1, -1, -1, dtype=np.uint64) * np.uint64(4)


def key_digits(kids: np.ndarray) -> np.ndarray:
    """[n] key ids → [n, KEY_DIGITS] ASCII hex digits."""
    d = (np.asarray(kids, np.uint64)[:, None] >> _SHIFTS[None, :]) \
        & np.uint64(15)
    return _HEX[d.astype(np.int64)]


class RequestTemplate:
    """Vectorised builder of calls that differ only in key and stamp."""

    def __init__(self, name: str, hits: int, limit: int, duration: int,
                 behavior: int = 0):
        self.fields = dict(name=name, hits=hits, limit=limit,
                           duration=duration, behavior=behavior)
        tlv = encode_request(unique_key="#" * KEY_DIGITS,
                             created_at=1 << 41, **self.fields)
        self.key_off = tlv.index(b"#" * KEY_DIGITS)
        # created_at is the last field: tag 0x50 and a 6-byte varint,
        # which holds any epoch-ms stamp in [2^35, 2^42)
        self.ts_off = len(tlv) - 6
        if tlv[self.ts_off - 1] != 0x50:
            raise AssertionError("created_at is not the last field")
        self.tlv = np.frombuffer(tlv, np.uint8)

    @staticmethod
    def key_text(kid: int) -> str:
        return format(int(kid), f"0{KEY_DIGITS}x")

    def call(self, kids: np.ndarray, created_ms: int) -> bytes:
        if not (1 << 35) <= created_ms < (1 << 42):
            raise ValueError(f"stamp {created_ms} outside the 6-byte varint")
        m = np.tile(self.tlv, (len(kids), 1))
        m[:, self.key_off:self.key_off + KEY_DIGITS] = key_digits(kids)
        v = created_ms
        for i in range(6):
            m[:, self.ts_off + i] = (v & 0x7F) | (0x80 if i < 5 else 0)
            v >>= 7
        return m.tobytes()


def _response_class():
    f = descriptor_pb2.FileDescriptorProto(
        name="benchpb/resp.proto", package="benchpb", syntax="proto3")
    T = descriptor_pb2.FieldDescriptorProto
    item = f.message_type.add(name="Resp")
    for num, (fname, ftype) in enumerate(
            (("status", T.TYPE_INT32), ("limit", T.TYPE_INT64),
             ("remaining", T.TYPE_INT64), ("reset_time", T.TYPE_INT64),
             ("error", T.TYPE_STRING)), start=1):
        item.field.add(name=fname, number=num, type=ftype,
                       label=T.LABEL_OPTIONAL)
    outer = f.message_type.add(name="Resps")
    outer.field.add(name="responses", number=1, type=T.TYPE_MESSAGE,
                    type_name=".benchpb.Resp", label=T.LABEL_REPEATED)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchpb.Resps"))


_RESPS = None


def decode_responses(data: bytes) -> dict:
    """GetRateLimitsResp bytes → columns (status, limit, remaining,
    reset_time as int64 arrays; ``errors`` the count of non-empty error
    strings)."""
    global _RESPS
    if _RESPS is None:
        _RESPS = _response_class()
    rs = _RESPS.FromString(data).responses
    n = len(rs)
    return {
        "status": np.fromiter((r.status for r in rs), np.int64, n),
        "limit": np.fromiter((r.limit for r in rs), np.int64, n),
        "remaining": np.fromiter((r.remaining for r in rs), np.int64, n),
        "reset_time": np.fromiter((r.reset_time for r in rs), np.int64, n),
        "errors": sum(1 for r in rs if r.error),
    }
