"""Production mesh factories.

Single pod: 256 TPU v5e chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16); the pod axis
carries pure data parallelism (gradient all-reduce over DCI) while params
are FSDP-sharded over ('pod','data') and tensor-sharded over 'model'.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's
xla_force_host_platform_device_count trick.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU tests/benches (same axis names as single-pod)."""
    return jax.make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants (roofline denominators; EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
