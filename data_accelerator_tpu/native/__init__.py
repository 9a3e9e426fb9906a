"""The native codec (``native/decoder.cpp``, one library): JSON lines and
Kafka record batches into columns at ingest, a result batch's columns
into the sinks' NDJSON at egress. ``decoder.py`` builds, loads and binds
it."""

from .decoder import (
    KAFKA_CODEC_NAMES,
    NativeBuildError,
    NativeDecoder,
    NdjsonBuffer,
    PackedBufferPool,
    encode_ndjson,
    load_library,
    native_available,
    native_crc32c,
    packed_shard_bytes,
    scan_lines,
)

__all__ = [
    "KAFKA_CODEC_NAMES",
    "NativeBuildError",
    "NativeDecoder",
    "NdjsonBuffer",
    "PackedBufferPool",
    "encode_ndjson",
    "load_library",
    "native_available",
    "native_crc32c",
    "packed_shard_bytes",
    "scan_lines",
]
