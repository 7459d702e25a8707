"""Compressed delta transport for the DiLoCo outer round.

DiLoCo's premise is that outer synchronization is rare enough to tolerate
slow links; this package makes each synchronization cheap too. Streaming
DiLoCo (Douillard et al., 2025, PAPERS.md) shows outer pseudo-gradients
survive 4-8x quantization *when the quantization error is fed back*: each
end accumulates the error it introduced into the next round's payload, so
the compressed trajectory provably tracks the uncompressed one (the
residual never compounds — it is re-shipped, not dropped).

Pieces:

  * :mod:`quant`    — chunkwise int8 / packed-int4 quantization with
    per-chunk max-abs f32 scales. Native C++ kernel
    (native/hypha_quant.cpp) with a numpy fallback that is BIT-EXACT
    against it (parity pinned by tests, like the CBOR codec pair).
  * :mod:`frame`    — the self-describing HQD1 wire container: magic +
    CBOR header (codec, chunk, tensor table) + packed payload. A receiver
    needs no out-of-band schema; plain SafeTensors files pass through
    :func:`read_delta` untouched, so codecs interoperate per job. It also
    holds the plain f32 wire's two copy-free ends: :func:`write_delta`
    writes a tree whose every leaf is ``float32`` on codec "none" from the
    leaves' own memory (``frame_f32``'s head and views; a leaf that is not
    C-contiguous copied first, it alone; over a file the caller hands it,
    where it hands one), and :func:`read_delta_into` reads such a file
    into buffers the caller keeps. Every other tree (bf16, int8/int4, a
    leaf of another dtype) is encoded and saved through a copy of all of
    it, as it always was.
  * :mod:`feedback` — the :class:`ErrorFeedback` residual accumulator used
    on BOTH ends: the worker folds its quantization error into the next
    round's delta, the parameter server folds broadcast quantization error
    into the next outer update.

Codec selection is per job via ``JobSpec.delta_codec``
(none | bf16 | int8 | int4), superseding the older ``delta_dtype`` field
(which maps onto the bf16 codec for back-compat).
"""

from __future__ import annotations

from .feedback import ErrorFeedback
from .frame import (
    MAGIC,
    ReadStats,
    frame_tag,
    is_frame,
    read_delta,
    read_delta_into,
    read_frame,
    write_delta,
    write_frame,
)
from .quant import DEFAULT_CHUNK, dequantize, quantize

__all__ = [
    "CODECS",
    "QUANT_CODECS",
    "DEFAULT_CHUNK",
    "MAGIC",
    "ErrorFeedback",
    "effective_codec",
    "codec_for_bandwidth",
    "quantize",
    "dequantize",
    "write_frame",
    "read_frame",
    "read_delta",
    "read_delta_into",
    "ReadStats",
    "write_delta",
    "is_frame",
    "frame_tag",
]

# Every per-job wire codec. "none" ships f32 SafeTensors (the seed format),
# "bf16" casts to bfloat16 SafeTensors (the old delta_dtype behavior), and
# the quantized pair ship HQD1 frames.
CODECS = ("none", "bf16", "int8", "int4")

# Codecs that quantize (and therefore want error feedback).
QUANT_CODECS = ("int8", "int4")


def effective_codec(delta_codec: str, delta_dtype: str = "float32") -> str:
    """Resolve the job's wire codec, honoring the legacy ``delta_dtype``.

    ``delta_codec`` wins when set to anything but "none"; otherwise
    ``delta_dtype="bfloat16"`` keeps selecting the bf16 wire format so
    pre-codec job specs behave exactly as before.
    """
    if delta_codec not in CODECS:
        raise ValueError(
            f"delta_codec must be one of {'|'.join(CODECS)}, got {delta_codec!r}"
        )
    if delta_codec == "none" and delta_dtype == "bfloat16":
        return "bf16"
    return delta_codec


# How many bits each codec ships per f32 parameter — the degradation order
# codec_for_bandwidth walks (never "upgrades" past the job's base codec).
_CODEC_BITS = {"none": 32, "bf16": 16, "int8": 8, "int4": 4}


def codec_for_bandwidth(
    bps: float, base: str, hi_bps: float, lo_bps: float
) -> str:
    """Per-link codec ladder for a measured bandwidth (ft.adaptive).

    ``bps >= hi_bps`` keeps the job's base codec; below it the link
    degrades to int8; below ``lo_bps`` to int4. A link never ships MORE
    bits than the base codec asks for (a job already on int4 stays int4),
    and every quantized choice keeps its per-peer error-feedback residual
    on both transport ends, so degraded links stay unbiased.
    """
    if base not in CODECS:
        raise ValueError(f"base codec must be one of {'|'.join(CODECS)}, got {base!r}")
    if bps >= hi_bps:
        return base
    pick = "int8" if bps >= lo_bps else "int4"
    if _CODEC_BITS[pick] >= _CODEC_BITS[base]:
        return base
    return pick
