"""Host runtime: Python bindings to the native tango-semantics layer.

The native library (firedancer_tpu/native/) provides the shared-memory
workspace, frag rings, flow control, cnc and tcache (reference semantics:
src/tango/). This package wraps it with ctypes for tile orchestration and
the TPU bridge; hot paths (publish, gather) stay in C++.
"""
from .tango import (  # noqa: F401
    Workspace, Ring, Fseq, Cnc, Store, Tcache, TraceRing,
    lib, CNC_BOOT, CNC_RUN, CNC_HALT, CNC_FAIL, FSEQ_STALE,
    TRACE_LINK_NONE,
)
