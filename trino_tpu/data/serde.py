"""Columnar wire format: Page <-> bytes, with per-column compression.

Reference: ``core/trino-main/.../execution/buffer/PageSerializer.java:59`` /
``PageDeserializer`` and ``PagesSerdeFactory.java:53-59`` (per-block encodings
+ LZ4/ZSTD frame + optional AES). Here: a compact header + per-column blocks
(dtype tag, null bitmap, raw values, dictionary vocabulary for varchar),
compressed with zlib (the image has no lz4 module; the codec byte leaves room
to add one). Used by the DCN streaming shuffle tier, the spooled exchange,
and the spooled result segments (SURVEY.md §2.6) — intra-slice repartition
never serializes (it rides ICI inside the compiled program).

**The codec follows the destination** (the reference compresses a pipelined
exchange only when asked: ``exchange.compression-codec``, default ``NONE``).
A frame that goes only into a task's ``OutputBuffer``, to be pulled by the
next task, is written ``CODEC_NONE``: zlib level 1 runs at some 75 MB/s,
slower than any link the frame could cross, and the consumer has to undo
it (0.5 s out and 0.17 s in a q3 statement at SF 10). A frame that goes to
disk (the FTE spool, a result segment) keeps ``CODEC_ZLIB``, block by
block where zlib shrinks the block. The caller knows where its bytes go
and passes the codec; no property chooses it.

Version 3 compresses each COLUMN block independently and stores a block
RAW when zlib does not shrink it (the reference's
``PageSerializer`` marker-byte contract: an incompressible block skips
the codec). Float/int entropy columns — exactly the shape of a big
result export — previously paid compress+inflate both ways for nothing;
now they pay neither, and the per-codec byte counters
(``trino_tpu_serde_bytes_total{direction,codec}``) make the realized
compression ratio observable. Version 4 (written now) is version 3 with a
varchar column's vocabulary as ONE block: the entries' byte lengths as one
``u32`` array, then all of them as one UTF-8 run, written with one join and
one encode and read with one decode and slices (a string at a time, 1.5 M
customer names took 0.73 s to write and 0.46 s to read). Version 2
payloads (whole-body zlib) and version 3 payloads (a length before each
entry) still deserialize — spool files written by an older process stay
readable.

Format (little-endian):
  magic u32 | version u8 | codec u8 | num_columns u16 | num_rows u32
  then per column: block_codec u8 | block_len u32 | block bytes
  (block_codec = CODEC_ZLIB when compressed, CODEC_NONE when stored raw)
  where each block decodes to:
    type_name: u16 len + utf8
    has_nulls: u8; if 1: packed bitmap ceil(n/8) bytes
    dtype_code: u8 (PHYSICAL dtype — may be narrower than the logical type)
    values: n * itemsize bytes
    if varchar: dict_len u32, dict_len x u32 byte lengths, then the
      entries' utf8 end to end (versions 2, 3: dict_len x (u32 len + utf8))
"""
from __future__ import annotations

import struct
import zlib
from typing import List

import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.data.dictionary import Dictionary
from trino_tpu.data.page import Column, Page
from trino_tpu.obs.devprofiler import host_read

MAGIC = 0x7E51_00D5
VERSION = 4  # written; 2 and 3 are still read
CODEC_NONE = 0
CODEC_ZLIB = 1

_CODEC_NAMES = {CODEC_NONE: "none", CODEC_ZLIB: "zlib"}

# Physical dtype tags: a column may ride a narrower dtype than its logical
# type's (data/page.py Column), so the wire format carries the actual one.
_DTYPE_CODES = {
    np.dtype(np.bool_): 0, np.dtype(np.int8): 1, np.dtype(np.int16): 2,
    np.dtype(np.int32): 3, np.dtype(np.int64): 4,
    np.dtype(np.float32): 5, np.dtype(np.float64): 6,
}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}


# a page whose varchar vocabulary is larger than this AND than its row count
# ships only the entries its rows reference: the chunks of one output page
# share the page's dictionary (Page.slice_rows), and each wrote all of it
# (five chunks of customer at SF 10 wrote 1.5 M names five times, and the
# consumer decoded them five times: most of a Q18 statement's 10 s)
VOCAB_PRUNE_MIN = 4096


def _referenced_vocabulary(codes: np.ndarray, vocab):
    """(codes, vocabulary) cut to the entries ``codes`` reference, in
    vocabulary order, so that code order stays string order; negative
    codes (NULL) stay as they are."""
    live = codes >= 0
    used = np.unique(codes[live])
    out = codes.copy()
    out[live] = np.searchsorted(used, codes[live]).astype(codes.dtype)
    return out, [vocab[i] for i in used.tolist()]


def _serialize_vocabulary(vocab, parts: List[bytes]) -> None:
    """``dict_len u32``, the entries' byte lengths as one ``u32`` array,
    then the entries end to end as one UTF-8 run: one join and one encode
    whatever the count."""
    text = "".join(vocab)
    blob = text.encode()
    if len(blob) == len(text):  # ASCII: a string's length is its bytes'
        lengths = np.fromiter(map(len, vocab), np.uint32, len(vocab))
    else:
        lengths = np.fromiter((len(s.encode()) for s in vocab), np.uint32,
                              len(vocab))
    parts.append(struct.pack("<I", len(vocab)))
    parts.append(lengths.astype("<u4", copy=False).tobytes())
    parts.append(blob)


def _deserialize_vocabulary(body: bytes, off: int, dlen: int):
    """(entries, end offset) of a version 4 vocabulary block at ``off``,
    just after its ``dict_len``: one decode, then slices at the entries'
    CHARACTER offsets (the byte offsets, where the run is ASCII; else the
    count of UTF-8 lead bytes before each)."""
    if not dlen:
        return [], off
    lengths = np.frombuffer(body, dtype="<u4", count=dlen, offset=off)
    off += 4 * dlen
    ends = np.cumsum(lengths, dtype=np.int64)
    total = int(ends[-1])
    text = body[off:off + total].decode()
    if len(text) != total:
        raw = np.frombuffer(body, dtype=np.uint8, count=total, offset=off)
        chars = np.concatenate(
            [np.zeros(1, np.int64), np.cumsum((raw & 0xC0) != 0x80)])
        ends = chars[ends]
    ends = ends.tolist()
    return ([text[a:b] for a, b in zip([0] + ends[:-1], ends)],
            off + total)


def _serialize_column(col: Column, n: int, parts: List[bytes]) -> None:
    name = str(col.type).encode()
    parts.append(struct.pack("<H", len(name)))
    parts.append(name)
    if col.nulls is not None:
        parts.append(b"\x01")
        parts.append(np.packbits(host_read(col.nulls, "serialize")).tobytes())
    else:
        parts.append(b"\x00")
    vals_np = np.ascontiguousarray(host_read(col.values, "serialize"))
    vocab = None
    if col.type.is_varchar:
        assert col.dictionary is not None
        vocab = col.dictionary.values
        if len(vocab) > max(VOCAB_PRUNE_MIN, n):
            vals_np, vocab = _referenced_vocabulary(vals_np, vocab)
    dtype_code = _DTYPE_CODES[vals_np.dtype]
    if col.hi is not None:
        # long-decimal two-limb column: flag bit 7 on the dtype code, hi
        # limb block follows the low words (reference: Int128 flat storage)
        parts.append(struct.pack("<B", dtype_code | 0x80))
        parts.append(vals_np.tobytes())
        parts.append(
            np.ascontiguousarray(host_read(col.hi, "serialize")).tobytes())
    else:
        parts.append(struct.pack("<B", dtype_code))
        parts.append(vals_np.tobytes())
    if vocab is not None:
        _serialize_vocabulary(vocab, parts)
    if col.type.is_nested:
        # children: u32 flat row count, then the child column recursively
        # (reference: ArrayBlockEncoding/MapBlockEncoding nest the element
        # block encodings the same way)
        for child in col.children:
            parts.append(struct.pack("<I", len(child)))
            _serialize_column(child, len(child), parts)


def serialize_page(page: Page, codec: int = CODEC_ZLIB) -> bytes:
    from trino_tpu.obs import metrics as M

    n = page.num_rows
    out: List[bytes] = [
        struct.pack("<IBBHI", MAGIC, VERSION, codec, page.channel_count, n)]
    logical = 0
    wire_by_codec = {CODEC_NONE: 0, CODEC_ZLIB: 0}
    for col in page.columns:
        parts: List[bytes] = []
        _serialize_column(col, n, parts)
        body = b"".join(parts)
        logical += len(body)
        block_codec, block = CODEC_NONE, body
        if codec == CODEC_ZLIB:
            comp = zlib.compress(body, level=1)
            if len(comp) < len(body):
                # incompressible-column fast path: only blocks zlib
                # actually SHRANK ship compressed — entropy data (float
                # measures, high-cardinality ints) stores raw and skips
                # the inflate on the read side too
                block_codec, block = CODEC_ZLIB, comp
        wire_by_codec[block_codec] += len(block)
        out.append(struct.pack("<BI", block_codec, len(block)))
        out.append(block)
    for bc, nbytes in wire_by_codec.items():
        if nbytes:
            M.SERDE_BYTES.inc(nbytes, "encode", _CODEC_NAMES[bc])
    if logical:
        M.SERDE_BYTES.inc(logical, "encode", "logical")
    return b"".join(out)


def deserialize_page(data: bytes) -> Page:
    from trino_tpu.obs import metrics as M

    magic, version, codec, ncols, nrows = struct.unpack_from("<IBBHI", data, 0)
    if magic != MAGIC:
        raise ValueError("bad page magic")
    columns: List[Column] = []
    if version == 2:
        # legacy whole-body frame (pre-incompressible-fast-path spool
        # files): one zlib pass over every column block together
        body = data[12:]
        if codec == CODEC_ZLIB:
            body = zlib.decompress(body)
        off = 0
        for _ in range(ncols):
            col, off = _deserialize_column(body, off, nrows, version)
            columns.append(col)
        return Page(columns)
    if version not in (3, VERSION):
        raise ValueError(
            f"unsupported page format version {version} "
            f"(expected 2, 3 or {VERSION})")
    off = 12
    logical = 0
    wire_by_codec = {CODEC_NONE: 0, CODEC_ZLIB: 0}
    for _ in range(ncols):
        block_codec, block_len = struct.unpack_from("<BI", data, off)
        off += 5
        block = data[off:off + block_len]
        off += block_len
        wire_by_codec[block_codec] = (
            wire_by_codec.get(block_codec, 0) + block_len)
        if block_codec == CODEC_ZLIB:
            block = zlib.decompress(block)
        elif block_codec != CODEC_NONE:
            raise ValueError(f"unknown column block codec {block_codec}")
        logical += len(block)
        col, _end = _deserialize_column(block, 0, nrows, version)
        columns.append(col)
    for bc, nbytes in wire_by_codec.items():
        if nbytes:
            M.SERDE_BYTES.inc(nbytes, "decode", _CODEC_NAMES[bc])
    if logical:
        M.SERDE_BYTES.inc(logical, "decode", "logical")
    return Page(columns)


def _deserialize_column(body: bytes, off: int, nrows: int, version: int):
    (name_len,) = struct.unpack_from("<H", body, off)
    off += 2
    typ = T.parse_type(body[off : off + name_len].decode())
    off += name_len
    has_nulls = body[off]
    off += 1
    nulls = None
    if has_nulls:
        nbytes = (nrows + 7) // 8
        bits = np.unpackbits(
            np.frombuffer(body, dtype=np.uint8, count=nbytes, offset=off)
        )[:nrows].astype(np.bool_)
        nulls = jnp.asarray(bits)
        off += nbytes
    code = body[off]
    has_hi = bool(code & 0x80)
    dt = _CODE_DTYPES[code & 0x7F]
    off += 1
    vals = np.frombuffer(body, dtype=dt, count=nrows, offset=off)
    off += nrows * dt.itemsize
    hi = None
    if has_hi:
        hi = np.frombuffer(body, dtype=np.int64, count=nrows, offset=off)
        off += nrows * 8
    dictionary = None
    if typ.is_varchar:
        (dlen,) = struct.unpack_from("<I", body, off)
        off += 4
        if version >= 4:
            vocab, off = _deserialize_vocabulary(body, off, dlen)
        else:  # a length before each entry
            vocab = []
            for _ in range(dlen):
                (slen,) = struct.unpack_from("<I", body, off)
                off += 4
                vocab.append(body[off : off + slen].decode())
                off += slen
        dictionary = Dictionary(vocab)
    children = None
    if typ.is_nested:
        children = []
        for _ in T.type_children(typ):
            (crows,) = struct.unpack_from("<I", body, off)
            off += 4
            child, off = _deserialize_column(body, off, crows, version)
            children.append(child)
    return (
        Column(
            typ, jnp.asarray(vals), nulls, dictionary, children=children,
            hi=jnp.asarray(hi) if hi is not None else None,
        ),
        off,
    )
