"""The two protobuf messages the graph plane's client needs, by hand.

proto/prediction.proto: SeldonMessage{status=1, meta=2, data=3},
DefaultData{names=1, tensor=2}, Tensor{shape=1 packed int32, values=2 packed
double}, Status{code=1, info=2, reason=3, status=4}.  A request is built once
per payload and sent as bytes, so the generator spends nothing on encoding
inside the window; a reply is read with numpy straight from the packed doubles.
"""

from __future__ import annotations

import numpy as np


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(number: int, payload: bytes) -> bytes:
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def encode_tensor_message(array: np.ndarray) -> bytes:
    values = np.ascontiguousarray(array, dtype="<f8").tobytes()
    shape = b"".join(_varint(int(d)) for d in array.shape)
    tensor = _field(1, shape) + _field(2, values)
    return _field(3, _field(2, tensor))


def _read_varint(buf: bytes, pos: int):
    shift = value = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes) -> dict:
    """Length-delimited fields of one message (last wins); varint and fixed
    fields are skipped over."""
    out, pos = {}, 0
    while pos < len(buf):
        key, pos = _read_varint(buf, pos)
        number, kind = key >> 3, key & 7
        if kind == 0:
            _, pos = _read_varint(buf, pos)
        elif kind == 1:
            pos += 8
        elif kind == 5:
            pos += 4
        elif kind == 2:
            n, pos = _read_varint(buf, pos)
            out[number] = buf[pos:pos + n]
            pos += n
        else:
            raise ValueError(f"wire type {kind} in a SeldonMessage")
    return out


def decode_tensor_message(buf: bytes) -> np.ndarray:
    """The reply's data.tensor as an array; raises ValueError when the reply
    carries a failure status or no tensor."""
    message = _fields(buf)
    if 3 not in message:
        status = _fields(message.get(1, b""))
        raise ValueError("reply without data: "
                         + status.get(2, status.get(3, b"")).decode(errors="replace"))
    tensor = _fields(_fields(message[3]).get(2, b""))
    if 2 not in tensor:
        raise ValueError("reply data carries no tensor")
    shape, pos, raw = [], 0, tensor.get(1, b"")
    while pos < len(raw):
        d, pos = _read_varint(raw, pos)
        shape.append(d)
    return np.frombuffer(tensor[2], dtype="<f8").reshape(shape)
