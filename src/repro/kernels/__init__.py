"""Pallas TPU kernels for the MoR hot paths (+ ops.py wrappers, ref.py
oracles).  Compiled by Mosaic on TPU; interpret mode everywhere else."""
