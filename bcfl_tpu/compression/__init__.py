"""Communication compression for update exchange (COMPRESSION.md).

In-graph, jit-compatible codecs for client update deltas — int8 per-chunk
quantization with stochastic rounding, top-k sparsification, and their
composition — with error-feedback residuals carried in the engine round
state, payload fingerprinting for the ledger, and bytes-on-wire accounting.
"""

from bcfl_tpu.compression.codecs import (
    KERNEL_IMPLS,
    KINDS,
    CompressionConfig,
    codec_key,
    corrupt_payload,
    decode_tree,
    encode_tree,
    kernel_plan,
    payload_nbytes,
    roundtrip,
    wire_format,
    zero_residual,
)

__all__ = [
    "KERNEL_IMPLS",
    "KINDS",
    "CompressionConfig",
    "codec_key",
    "corrupt_payload",
    "decode_tree",
    "encode_tree",
    "kernel_plan",
    "payload_nbytes",
    "roundtrip",
    "wire_format",
    "zero_residual",
]
