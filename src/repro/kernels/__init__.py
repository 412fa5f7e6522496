"""Pallas TPU kernels for the model side's compute hot-spots.

The planner runs none of them: its device programs are the vmapped
simplex and replay of :mod:`repro.engine`.

Each kernel ships three layers:
  <name>.py  — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target);
  ops.py     — jit'd public wrapper (layout munging, block-size selection,
               interpret=True auto-fallback off-TPU);
  ref.py     — pure-jnp oracle, the allclose target for the test sweeps.

Model kernels: flash_attention (prefill), decode_attention (split-KV
flash decoding), ssd_scan (Mamba-2 SSD chunked scan), rmsnorm (fused
norm).
"""

from . import ops, ref

__all__ = ["ops", "ref"]
