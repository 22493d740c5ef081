"""RLP (Recursive Length Prefix) codec.

Behavioral twin of the geth ``rlp`` package the reference imports everywhere
(trie node encoding trie/committer.go, tx/header/receipt serialization
core/types/*, DeriveSha core/types/hashing.go).  Items are ``bytes`` or
(nested) lists of items; integers are encoded big-endian with no leading
zeros (the caller uses :func:`encode_uint`).
"""

from __future__ import annotations

from typing import Union

Item = Union[bytes, list]

_from_bytes = int.from_bytes  # bound once: decode_uint runs ~10x a transaction


def encode_uint(value: int) -> bytes:
    """Canonical integer -> byte-string payload (empty for zero)."""
    if value == 0:
        return b""
    length = (value.bit_length() + 7) // 8
    return value.to_bytes(length, "big")


def decode_uint(data: bytes) -> int:
    if data[:1] == b"\x00":
        raise ValueError("leading zero in canonical RLP integer")
    return _from_bytes(data, "big")


def _encode_length(length: int, offset: int) -> bytes:
    if length < 56:
        return bytes([offset + length])
    blen = encode_uint(length)
    return bytes([offset + 55 + len(blen)]) + blen


def encode(item: Item) -> bytes:
    if isinstance(item, (bytes, bytearray)):
        item = bytes(item)
        if len(item) == 1 and item[0] < 0x80:
            return item
        return _encode_length(len(item), 0x80) + item
    if isinstance(item, (list, tuple)):
        payload = b"".join(encode(x) for x in item)
        return _encode_length(len(payload), 0xC0) + payload
    if isinstance(item, int):
        return encode(encode_uint(item))
    raise TypeError(f"cannot RLP-encode {type(item)!r}")


def _decode_at(data: bytes, pos: int):
    """Decode one item at pos, return (item, next_pos)."""
    if pos >= len(data):
        raise ValueError("RLP input too short")
    b0 = data[pos]
    if b0 < 0x80:
        return bytes([b0]), pos + 1
    if b0 < 0xB8:  # short string
        length = b0 - 0x80
        end = pos + 1 + length
        s = data[pos + 1:end]
        if len(s) != length:
            raise ValueError("RLP string truncated")
        if length == 1 and s[0] < 0x80:
            raise ValueError("non-canonical single byte")
        return s, end
    if b0 < 0xC0:  # long string
        lenlen = b0 - 0xB7
        length = decode_uint(data[pos + 1:pos + 1 + lenlen])
        if length < 56:
            raise ValueError("non-canonical long string length")
        start = pos + 1 + lenlen
        end = start + length
        if end > len(data):
            raise ValueError("RLP string truncated")
        return data[start:end], end
    if b0 < 0xF8:  # short list
        length = b0 - 0xC0
        end = pos + 1 + length
        items = []
        cur = pos + 1
        while cur < end:
            item, cur = _decode_at(data, cur)
            items.append(item)
        if cur != end:
            raise ValueError("RLP list payload overrun")
        return items, end
    # long list
    lenlen = b0 - 0xF7
    length = decode_uint(data[pos + 1:pos + 1 + lenlen])
    if length < 56:
        raise ValueError("non-canonical long list length")
    start = pos + 1 + lenlen
    end = start + length
    if end > len(data):
        raise ValueError("RLP list truncated")
    items = []
    cur = start
    while cur < end:
        item, cur = _decode_at(data, cur)
        items.append(item)
    if cur != end:
        raise ValueError("RLP list payload overrun")
    return items, end


def payload_span(data: bytes, pos: int, limit: int):
    """(start, end) of the payload of the item whose prefix is at pos and
    which has to end by ``limit`` (<= len(data)); a list iff
    ``data[pos] >= 0xC0``.  The span form of :func:`_decode_at`: the same
    rejections, nothing sliced, nothing built."""
    if pos >= limit:
        raise ValueError("RLP input too short")
    b0 = data[pos]
    if b0 < 0x80:
        return pos, pos + 1
    # strings from 0x80 and lists from 0xC0 share the low six bits: a
    # payload length up to 55, or 55 + the width of a length that follows
    if b0 < 0xB8 or 0xC0 <= b0 < 0xF8:  # short string / short list
        start = pos + 1
        end = start + (b0 & 0x3F)
        if end > limit:
            raise ValueError("RLP item truncated")
        if b0 == 0x81 and data[start] < 0x80:
            raise ValueError("non-canonical single byte")
        return start, end
    start = pos + 1 + (b0 & 0x3F) - 0x37  # long string / long list
    length = decode_uint(data[pos + 1:start])
    if length < 56:
        raise ValueError("non-canonical long length")
    end = start + length
    if end > limit:
        raise ValueError("RLP item truncated")
    return start, end


def list_span(data: bytes, pos: int, limit: int):
    """:func:`payload_span` of an item that has to be a list."""
    span = payload_span(data, pos, limit)
    if data[pos] < 0xC0:
        raise ValueError("RLP list expected")
    return span


def span_items(data: bytes, pos: int, end: int, nested: int = -1) -> list:
    """The items laid end to end in ``data[pos:end]`` (a list's payload
    span), as :func:`decode` would return them: each string sliced
    straight from ``data``, and a list, which only item number
    ``nested`` may be, handed to :func:`_decode_at`."""
    items = []
    append = items.append
    while pos < end:
        b0 = data[pos]
        if b0 < 0x80:
            nxt = pos + 1
            append(data[pos:nxt])
        elif b0 < 0xB8:
            nxt = pos + b0 - 0x7F
            if nxt > end:
                raise ValueError("RLP string truncated")
            if b0 == 0x81 and data[pos + 1] < 0x80:
                raise ValueError("non-canonical single byte")
            append(data[pos + 1:nxt])
        elif b0 < 0xC0:
            start, nxt = payload_span(data, pos, end)
            append(data[start:nxt])
        elif len(items) != nested:
            raise ValueError("RLP string expected")
        elif b0 == 0xC0:
            nxt = pos + 1
            append([])
        else:
            item, nxt = _decode_at(data, pos)
            if nxt > end:
                raise ValueError("RLP list payload overrun")
            append(item)
        pos = nxt
    return items


def decode(data: bytes) -> Item:
    item, end = _decode_at(bytes(data), 0)
    if end != len(data):
        raise ValueError("trailing bytes after RLP item")
    return item
