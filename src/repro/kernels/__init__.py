"""Pallas TPU kernels for the compute hot-spots.

* ``stage_merge``     — CheckFree's recovery merge (HBM-bandwidth-bound axpy
                        over whole stages; the paper's core operation).
* ``flash_attention`` — block-tiled causal/sliding-window attention (dense
                        archs' dominant FLOPs; enables long-context shapes).
* ``ssd_scan``        — Mamba2 chunked SSD scan (SSM/hybrid archs).

Each kernel has a pure-jnp oracle in ``ref.py`` and a jit'd dispatch wrapper
in ``ops.py``.  Kernels are written against TPU BlockSpec/VMEM semantics,
compiled on a TPU and validated on the CPU with ``interpret=True``; every
caller passes ``interpret`` (``ops.interpret_default()`` picks it from the
platform).
"""
