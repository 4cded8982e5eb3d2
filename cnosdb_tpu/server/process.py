"""What a server process asks of its runtime, once, at start.

A query over a few million rows allocates and frees some hundred MB of
host buffers (decoded columns, the device lanes' staging and pulls, the
mesh lane's shard-major operands). Left to its defaults the process
serves such a request at one of two speeds, for tens of requests at a
stretch: glibc hands a freed buffer back to the kernel (it unmaps a
thread arena's heap once the heap is empty, trims the main heap past a
threshold, and unmaps every chunk above the mmap threshold), or keeps it
because some small live object still sits in that heap — and which of
the two holds changes whenever a full garbage collection moves the small
objects. A request that gets its buffers back from the kernel pays for
mapping and faulting them in again: +40 ms of 383 in the four-chip
fleet group-by, +45 to +75 of 600 on one chip (PERF.md, PR 29). And a full
collection walks every object the imports left behind (JAX alone makes
about a million): 45-100 ms, every tenth such request and every 0.6 s
of a bulk load.

So the process keeps what it frees, and takes the start-up's objects
out of the collector's sight. Both are the process's own business: no
option, no environment variable; an operator who set glibc's own
variables keeps them.
"""
from __future__ import annotations

import ctypes
import gc
import os

# <malloc.h>
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3
# glibc's ceiling for the mmap threshold (HEAP_MAX_SIZE / 2 on 64 bit);
# a larger value is refused. Setting it also ends glibc's own moving of
# the threshold, the third source of the two speeds.
_MMAP_THRESHOLD = 32 << 20
# a free top above this is returned to the kernel: never, in effect
# (mallopt takes an int)
_TRIM_THRESHOLD = (1 << 31) - 1
# above twice a thread arena's heap (64 MB) no empty heap is unmapped;
# it is address space asked for ahead of need, not memory touched
_TOP_PAD = 256 << 20
_OPERATOR_SET = ("GLIBC_TUNABLES", "MALLOC_TRIM_THRESHOLD_",
                 "MALLOC_TOP_PAD_", "MALLOC_MMAP_THRESHOLD_")


def keep_freed_memory() -> bool:
    """Tell glibc's allocator to keep freed memory for the next request
    → whether it was told. False where the operator tuned the allocator
    through its environment variables, or the C library is not glibc."""
    if any(os.environ.get(k) for k in _OPERATOR_SET):
        return False
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes, mallopt.restype = (ctypes.c_int, ctypes.c_int), \
        ctypes.c_int
    return all([mallopt(_M_MMAP_THRESHOLD, _MMAP_THRESHOLD),
                mallopt(_M_TRIM_THRESHOLD, _TRIM_THRESHOLD),
                mallopt(_M_TOP_PAD, _TOP_PAD)])


def freeze_startup_objects() -> int:
    """Move everything alive now (modules, JAX's tables, the opened
    engine) to the collector's permanent generation, after one full
    collection → how many objects that is. A later full collection then
    walks what requests left behind, not the imports."""
    gc.collect()
    gc.freeze()
    return gc.get_freeze_count()
