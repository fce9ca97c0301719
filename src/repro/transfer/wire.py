"""Wire format v3 — what a FlatParams payload looks like as BYTES.

Until now the cross-pod payloads (full flat buffers, or the compress_flat
top-k + int8 deltas of core/compression.py) only ever existed as device
arrays, and "bytes on the wire" was a number the simulator made up
(``SimConfig.param_bytes``).  This module makes the bytes real: every
payload is encoded into a self-describing, versioned, checksummed frame
that an actual transport (transfer/transport.py) can carry, and whose
length IS the transfer size.

Frame layout (little-endian, fixed 68-byte header + body; version 3
frames append one ``weight f32`` field before the crc — 72 bytes)::

    magic    4s   b"VCWF"
    version  u16  wire format version (this module speaks 3; a frame is
                  EMITTED at the oldest version that can express it, so
                  dense/sparse/shard frames stay version 2 byte-for-byte)
    kind     u8   0 = DENSE (raw flat buffer), 1 = SPARSE (top-k + int8),
                  2 = SHARD (one contiguous ShardedTreeSpec segment of the
                  server bus — the DOWNLOAD/redistribution leg),
                  3 = AGG (v3 only: ONE merged, already-assimilated frame
                  from an edge aggregator — dense body + summed client
                  weight in the v3 ``weight`` header field)
    dtype    u8   dense/shard payload dtype code (0=f32, 1=bf16, 2=f16)
    n        u64  logical element count of the (padded) flat buffer
                  (shard: element count of THIS segment, == shard_len)
    k        u64  surviving elements (dense: == n; shard: shard index)
    block    u32  int8 quantization block (sparse; shard: n_shards;
                  dense: 0)
    density  f32  sparse density budget (dense/shard: 1.0)
    round    u32  error-feedback round counter (bookkeeping)
    res_norm f32  l2 norm of the residual carried AFTER this payload
                  (error-feedback bookkeeping: the receiver can monitor
                  how much update mass is still in flight client-side)
    len_val  u64  byte length of the values section
    len_scl  u64  byte length of the scales section
    len_idx  u64  byte length of the indices section
    weight   f32  (v3 headers ONLY) summed client mass of an aggregate
                  frame: 1 - prod(per-assimilation retention) over the
                  results the aggregator folded; 0 <= weight <= 1
    crc      u32  crc32 over header-sans-crc || body — a bit flip ANYWHERE
                  in the frame (including the n/k/density header fields)
                  fails the checksum, not just body corruption

Versioning rules: the magic/version pair is checked FIRST; a decoder
rejects frames with a version newer than it speaks (no silent best-effort
parsing), and any field may only be reinterpreted by bumping the version
— v2 did exactly that: it added kind 2 and reinterpreted the ``k`` /
``block`` header fields for that kind only, and v3 adds kind 3 plus the
``weight`` header field (v1/v2 frames decode unchanged, and the old
kinds are still EMITTED as version-2 frames so their byte counts never
move).  Truncated, oversized, or bit-flipped frames fail the length/crc
checks and raise ``WireError`` — a torn transfer is never assimilated
(the paper's fault-tolerance requirement: dropping a payload is always
safe, applying a corrupt one never is).
"""
from __future__ import annotations

import struct
import zlib
from typing import NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressedDelta

MAGIC = b"VCWF"
WIRE_VERSION = 3

KIND_DENSE = 0
KIND_SPARSE = 1
KIND_SHARD = 2                 # one contiguous segment of the server bus
KIND_AGG = 3                   # merged pre-assimilated frame (v3 only)

# emission rule: a frame is written at the OLDEST version that can express
# it, so dense/sparse/shard frames keep the v2 68-byte header (every
# pinned byte count stays exact) and only aggregate frames pay for v3's
# extra ``weight f32``
_EMIT_VERSION = 2
_HDR = struct.Struct("<4sHBBQQIfIfQQQ")      # v1/v2 header minus the crc
_HDR3 = struct.Struct("<4sHBBQQIfIfQQQf")    # v3: + weight f32
_CRC = struct.Struct("<I")
_PEEK = struct.Struct("<4sH")                # magic/version, checked FIRST
HEADER_BYTES = _HDR.size + _CRC.size
HEADER_BYTES_V3 = _HDR3.size + _CRC.size


def _frame(header_wo_crc: bytes, body: bytes) -> bytes:
    """Assemble a frame: crc covers header-sans-crc || body, so a flip in
    ANY field (not just the payload) fails validation."""
    return (header_wo_crc
            + _CRC.pack(zlib.crc32(body, zlib.crc32(header_wo_crc)))
            + body)

_DTYPE_CODES = {"float32": 0, "bfloat16": 1, "float16": 2}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}


class WireError(ValueError):
    """Frame failed validation (magic/version/length/crc) — do NOT
    assimilate anything from it."""


class WireMessage(NamedTuple):
    kind: int                     # KIND_DENSE|KIND_SPARSE|KIND_SHARD|KIND_AGG
    payload: Union[np.ndarray, CompressedDelta]
    round: int                    # error-feedback round counter
    residual_norm: float          # client-side residual mass after sending
    shard: int = 0                # KIND_SHARD: segment index on the bus
    n_shards: int = 1             # KIND_SHARD: total segments of the bus
    weight: float = 1.0           # KIND_AGG: summed client mass (v3 header)


class AggregatePayload(NamedTuple):
    """What an edge aggregator submits upstream: its merged (already
    assimilated) fold state plus the summed client mass it represents.
    Travels as a ``KIND_AGG`` v3 frame; the hub folds it with
    ``ServerScheme.assimilate_aggregate`` instead of the per-result path
    (no scheme encode, no residual ledger — both ran at the edge)."""

    buf: np.ndarray               # merged flat buffer (padded bus layout)
    weight: float                 # 1 - prod(retention) over folded results


def dense_frame_bytes(n: int, dtype: str = "float32") -> int:
    """Exact frame length of a dense buffer payload."""
    itemsize = 2 if dtype in ("bfloat16", "float16") else 4
    return HEADER_BYTES + n * itemsize


def agg_frame_bytes(n: int, dtype: str = "float32") -> int:
    """Exact frame length of one merged aggregate frame (v3 header)."""
    itemsize = 2 if dtype in ("bfloat16", "float16") else 4
    return HEADER_BYTES_V3 + n * itemsize


def shard_frame_bytes(shard_len: int, dtype: str = "float32") -> int:
    """Exact frame length of one handout segment (same body as dense)."""
    return dense_frame_bytes(shard_len, dtype)


def sparse_frame_bytes(k: int, block: int = 256) -> int:
    """Exact frame length of a top-k + int8 payload: k int8 values,
    ceil(k/block) f32 scales, k int32 indices."""
    return HEADER_BYTES + k + (-(-k // block)) * 4 + k * 4


def _host(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


def _dense_bytes(buf: np.ndarray):
    name = str(buf.dtype)
    if name == "bfloat16":
        return _DTYPE_CODES[name], buf.view(np.uint16).tobytes()
    if name not in _DTYPE_CODES:
        raise WireError(f"unsupported dense wire dtype {name}")
    return _DTYPE_CODES[name], buf.tobytes()


def encode_dense(buf, *, round: int = 0, residual_norm: float = 0.0) -> bytes:
    """Encode a full flat buffer (the uncompressed payload kind)."""
    arr = _host(buf).reshape(-1)
    code, raw = _dense_bytes(arr)
    header = _HDR.pack(MAGIC, _EMIT_VERSION, KIND_DENSE, code,
                       arr.size, arr.size, 0, 1.0,
                       int(round), float(residual_norm),
                       len(raw), 0, 0)
    return _frame(header, raw)


def encode_aggregate(buf, *, weight: float, round: int = 0,
                     residual_norm: float = 0.0) -> bytes:
    """Encode an edge aggregator's merged upstream frame (KIND_AGG): the
    dense fold-state body plus the summed client mass in the v3 header's
    ``weight`` field.  The weight is the only thing distinguishing the
    body from a dense payload — it tells the hub how much of its own
    pre-window mass the merge already retains (see
    ``ServerScheme.assimilate_aggregate``)."""
    w = float(weight)
    if not 0.0 <= w <= 1.0:
        raise WireError(f"aggregate weight {w} outside [0, 1]")
    arr = _host(buf).reshape(-1)
    code, raw = _dense_bytes(arr)
    header = _HDR3.pack(MAGIC, 3, KIND_AGG, code,
                        arr.size, arr.size, 0, 1.0,
                        int(round), float(residual_norm),
                        len(raw), 0, 0, w)
    return _frame(header, raw)


def encode_shard(seg, *, shard: int, n_shards: int, round: int = 0) -> bytes:
    """Encode one contiguous handout segment of the server bus (the
    DOWNLOAD leg): shard ``shard`` of ``n_shards``, laid out by the bus's
    ShardedTreeSpec shard table.  ``k`` carries the shard index and
    ``block`` the shard count (v2 reinterpretation, KIND_SHARD only)."""
    if not 0 <= shard < n_shards:
        raise WireError(f"shard {shard} out of range 0..{n_shards - 1}")
    arr = _host(seg).reshape(-1)
    code, raw = _dense_bytes(arr)
    header = _HDR.pack(MAGIC, _EMIT_VERSION, KIND_SHARD, code,
                       arr.size, int(shard), int(n_shards), 1.0,
                       int(round), 0.0,
                       len(raw), 0, 0)
    return _frame(header, raw)


# fused encode leg: the body (values || scales || indices bytes) is packed
# into ONE device buffer (kernels/ref.py::pack_body — bitcast+concat, zero
# arithmetic, so the bytes are exactly the payload arrays' bytes) and
# crosses the device->host boundary in ONE transfer, vs the three array
# transfers + Python concat the old encoder paid per frame.  jit caches by (k, ng)
# shape, so steady-state rounds reuse the compiled pack.
_pack_body_dev = None


def _packed_sparse_body(p: CompressedDelta) -> bytes:
    global _pack_body_dev
    if _pack_body_dev is None:
        from repro.kernels import ref as _kref
        _pack_body_dev = jax.jit(_kref.pack_body)
    return _host(_pack_body_dev(p.values, p.scales, p.indices)).tobytes()


def encode_sparse(p: CompressedDelta, *, round: int = 0,
                  residual_norm: float = 0.0) -> bytes:
    """Encode a compress_flat payload (global top-k + int8).  The body is
    device-packed and crosses to the host as one buffer; frame bytes are
    identical to the three-section ``tobytes`` concat they replace."""
    k = int(p.values.size)
    ng = int(p.scales.size)
    n = 1
    for s in p.shape:
        n *= int(s)
    body = _packed_sparse_body(p)
    header = _HDR.pack(MAGIC, _EMIT_VERSION, KIND_SPARSE, 0,
                       n, k, int(p.block), float(p.density),
                       int(round), float(residual_norm),
                       k, 4 * ng, 4 * k)
    return _frame(header, body)


def encode(payload, *, round: int = 0, residual_norm: float = 0.0) -> bytes:
    """Dispatch on payload type: buffers go dense, CompressedDelta sparse,
    AggregatePayload rides the v3 aggregate frame."""
    if isinstance(payload, CompressedDelta):
        return encode_sparse(payload, round=round, residual_norm=residual_norm)
    if isinstance(payload, AggregatePayload):
        return encode_aggregate(payload.buf, weight=payload.weight,
                                round=round, residual_norm=residual_norm)
    return encode_dense(payload, round=round, residual_norm=residual_norm)


def decode(frame: bytes) -> WireMessage:
    """Validate and decode one frame.  Raises WireError on ANY structural
    problem — short frame, bad magic, unknown version, length mismatch,
    crc mismatch — so a torn transfer can never be assimilated."""
    if len(frame) < _PEEK.size:
        raise WireError(f"frame too short: {len(frame)} < {_PEEK.size}")
    magic, version = _PEEK.unpack_from(frame)
    if magic != MAGIC:
        raise WireError(f"bad magic {magic!r}")
    if version > WIRE_VERSION:
        raise WireError(f"wire version {version} newer than spoken "
                        f"{WIRE_VERSION}")
    # the header struct is selected by the (already validated) version:
    # v1/v2 = 68 bytes, v3 = 72 (trailing weight f32); the crc always
    # covers the whole header-sans-crc, so the weight field is protected
    hdr = _HDR3 if version >= 3 else _HDR
    hdr_bytes = hdr.size + _CRC.size
    if len(frame) < hdr_bytes:
        raise WireError(f"frame too short: {len(frame)} < {hdr_bytes}")
    fields = hdr.unpack_from(frame)
    (_, _, kind, dcode, n, k, block, density, rnd, res_norm,
     len_v, len_s, len_i) = fields[:13]
    weight = fields[13] if version >= 3 else 1.0
    (crc,) = _CRC.unpack_from(frame, hdr.size)
    body = frame[hdr_bytes:]
    if len(body) != len_v + len_s + len_i:
        raise WireError(f"torn frame: body {len(body)}B != declared "
                        f"{len_v + len_s + len_i}B")
    if zlib.crc32(body, zlib.crc32(frame[:hdr.size])) != crc:
        raise WireError("crc mismatch (corrupt frame)")
    if kind == KIND_AGG and version < 3:
        raise WireError(f"kind {KIND_AGG} (aggregate) requires wire v3, "
                        f"got v{version}")
    if kind in (KIND_DENSE, KIND_SHARD, KIND_AGG):
        dtype = _CODE_DTYPES.get(dcode)
        if dtype is None:
            raise WireError(f"unknown dense dtype code {dcode}")
        if dtype == "bfloat16":
            arr = np.frombuffer(body, np.uint16).view(jnp.bfloat16)
        else:
            arr = np.frombuffer(body, np.dtype(dtype))
        if arr.size != n:
            raise WireError(f"dense payload {arr.size} elements != "
                            f"declared n={n}")
        if kind == KIND_SHARD:
            # v2: k = shard index, block = n_shards
            if not (block > 0 and 0 <= k < block):
                raise WireError(f"shard index {k} out of range for "
                                f"{block} shards")
            return WireMessage(KIND_SHARD, arr, rnd, res_norm,
                               shard=int(k), n_shards=int(block))
        if kind == KIND_AGG:
            if not 0.0 <= weight <= 1.0:
                raise WireError(f"aggregate weight {weight} outside [0, 1]")
            return WireMessage(KIND_AGG, arr, rnd, res_norm,
                               weight=float(weight))
        return WireMessage(KIND_DENSE, arr, rnd, res_norm)
    if kind == KIND_SPARSE:
        vals = np.frombuffer(body[:len_v], np.int8)
        scls = np.frombuffer(body[len_v:len_v + len_s], np.float32)
        idxs = np.frombuffer(body[len_v + len_s:], np.int32)
        if vals.size != k or idxs.size != k:
            raise WireError(f"sparse sections disagree with k={k}: "
                            f"{vals.size} values / {idxs.size} indices")
        if block <= 0 or scls.size != -(-k // block):
            raise WireError(f"scale count {scls.size} != ceil({k}/{block})")
        if k > n:
            raise WireError(f"k={k} exceeds buffer length n={n}")
        payload = CompressedDelta(values=jnp.asarray(vals),
                                  scales=jnp.asarray(scls),
                                  indices=jnp.asarray(idxs),
                                  shape=(int(n),), density=float(density),
                                  block=int(block))
        return WireMessage(KIND_SPARSE, payload, rnd, res_norm)
    raise WireError(f"unknown frame kind {kind}")
