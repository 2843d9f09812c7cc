"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

Each kernel ships three files: kernel.py (pl.pallas_call + explicit
BlockSpec VMEM tiling), ops.py (jit'd public wrapper with pallas/oracle
dispatch), ref.py (pure-jnp oracle).  ``interpret=None`` (the default
everywhere) follows the platform — see ``_platform.resolve_interpret``:
the CPU test suite validates in the Pallas interpreter, a TPU compiles
through Mosaic.
"""
from . import (decode_attention, flash_attention, gla_chunk,  # noqa: F401
               tensor_alu, vta_gemm)
