"""Native JP2 decode subsystem — the inference-path mirror of the
encoder, and the self-contained round-trip oracle (no OpenJPEG in the
loop):

- ``parser``   Tier-2: JP2 boxes, markers, packet headers (host)
- ``index``    code-block-addressable stream index (random access)
- ``t1_dec``   MQ + EBCOT context-modeling pass decode (host; native
               ``t1_decode_blocks``, Python reference and fallback)
- ``device``   dequantize + inverse DWT + inverse RCT/ICT (jitted)
- ``decoder``  orchestration, partial decode (``reduce`` / ``layers``),
               windowed region decode (``region`` / ``index``)

Public API: :func:`decode`, :func:`build_index`, :class:`StreamIndex`,
:class:`DecodeError`, :func:`set_metrics_sink`.
"""
from .decoder import decode, set_metrics_sink
from .errors import DecodeError, InvalidParam
from .index import StreamIndex, build_index
from .parser import probe

__all__ = ["decode", "probe", "build_index", "StreamIndex",
           "DecodeError", "InvalidParam", "set_metrics_sink"]
