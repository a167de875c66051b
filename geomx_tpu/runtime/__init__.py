"""Native (C++) host runtime bindings.

The reference's transport core is native C++ (ps-lite); here the
host-side pieces that benefit from native code — the priority send queue
and the TSEngine scheduler state machine — are C++ (native/
geops_runtime.cpp) behind ctypes, built from source on first use, with
pure-Python fallbacks (geomx_tpu.transport) when no toolchain exists.
"""

from geomx_tpu.runtime.native import (NativePriorityQueue,
                                      NativeRecordIOReader,
                                      NativeRecordIOWriter, NativeTSEngine,
                                      build_native, load_native,
                                      native_available)

__all__ = ["NativePriorityQueue", "NativeRecordIOReader",
           "NativeRecordIOWriter", "NativeTSEngine", "build_native",
           "load_native", "native_available"]
