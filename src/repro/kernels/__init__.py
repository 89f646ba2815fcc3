"""Pallas TPU kernels for NeedleTail-JAX hot spots.

Paper kernels: density_combine (⊕ over predicate maps, single and [Q, γ]
batched forms), window_scan (prefix sums for TWO-PRONG), theta_stats
(θ-bisection THRESHOLD).  Framework kernels: flash_attention, ssd_chunk
(Mamba2).  Public API in :mod:`repro.kernels.ops`; jnp oracles in
:mod:`repro.kernels.ref`.
"""
