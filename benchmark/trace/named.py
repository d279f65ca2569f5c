"""What the per-layer metrics of a routed layer read from a trace, beside
``program.py``: device time under a scope of the program's, and the calls
of the kernels whose name starts with a prefix. Functions over
``program.Program``, so that tests drive them on a fixture."""

from __future__ import annotations

from typing import Optional

from benchmark.trace import program

GMM_PREFIX = "bps_gmm"


def scope_ms(trace: program.Program, scope: str) -> Optional[float]:
    """Device ms a step of the operations whose scope path holds
    ``scope`` (all phases); None where the traced program has no such
    scope, as a program from before the scope was opened has not."""
    if not trace.steps:
        return None
    ns = [end - start for _, path, start, end in trace.ops if scope in path]
    return trace.ms_per_step(sum(ns)) if ns else None


def kernel_of(name: str, prefix: str) -> Optional[str]:
    """``program.kernel`` for another family of kernels: the kernel an
    ``XLA Ops`` event is a call of (``%bps_gmm_dx.3 = ...``), or None."""
    lhs = name.partition(" = ")[0].lstrip("%")
    if not lhs.startswith(prefix):
        return None
    head, _, tail = lhs.rpartition(".")
    return head if head and tail.isdigit() else lhs


def ns_by_kernel(trace: program.Program, prefix: str) -> dict:
    """``{kernel: (ns, calls)}`` of the events of the kernels called
    ``prefix*``, as ``program.ns_by_kernel`` gives the flash kernels'."""
    out: dict = {}
    for name, _, start, end in trace.ops:
        k = kernel_of(name, prefix)
        if k is not None:
            ns, calls = out.get(k, (0.0, 0))
            out[k] = (ns + end - start, calls + 1)
    return out
