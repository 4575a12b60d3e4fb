"""Runtime companions to the static-analysis suite (tools/analysis).

The static ``retrace`` pass is a lexical heuristic; ``trace_guard`` is
its runtime backstop — it watches the actual jit compile caches while a
workload runs and asserts they stop growing once warm.  The invariant
auditor (``audit_controller`` / ``audit_boundary``) is the data-structure
counterpart: pool/stash/lane consistency checks the serving engine runs
at boundary ticks under its ``debug_invariants`` flag.  ``spans`` reads
the serving path's ``repro:`` profiler spans back out of a capture.
"""
from .invariants import InvariantViolation, audit_boundary, audit_controller
from .runtime import RetraceError, TraceReport, trace_guard

__all__ = ["InvariantViolation", "RetraceError", "TraceReport",
           "audit_boundary", "audit_controller", "trace_guard"]
