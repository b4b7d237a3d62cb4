"""Pallas TPU kernels for the compute hot spots.

Each kernel package ships three files:
  kernel.py — pl.pallas_call body with explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrapper (padding, interpret fallback on CPU)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Paper hot spots (section IV): lcs (phase iii similarity DP), shingle
(phase ii, the O(N*L^3) hash), minhash (the Spark-builtin baseline).
"""
