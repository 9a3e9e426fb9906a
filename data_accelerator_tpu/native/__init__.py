from .decoder import (
    KAFKA_CODEC_NAMES,
    NativeBuildError,
    NativeDecoder,
    PackedBufferPool,
    load_library,
    native_available,
    native_crc32c,
    scan_lines,
)

__all__ = [
    "KAFKA_CODEC_NAMES",
    "NativeBuildError",
    "NativeDecoder",
    "PackedBufferPool",
    "load_library",
    "native_available",
    "native_crc32c",
    "scan_lines",
]
