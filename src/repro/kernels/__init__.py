"""Pallas TPU kernels for the paper's compute hot-spot.

``approx_channel.py`` — fused PHY pipeline (bitcast -> interleave -> Gray-QAM
-> Rayleigh/AWGN via counter RNG -> closed-form ML demod -> bit clamp) with
explicit BlockSpec VMEM tiling; ``ops.py`` jit'd wrappers; ``ref.py`` the
pure-jnp oracle (bit-exact, shared tile math). Compiled by Mosaic on TPU;
run by the Pallas interpreter on CPU for tests.
"""

from repro.kernels.ops import (
    approx_channel,
    approx_channel_batch,
    approx_channel_transmit,
    approx_channel_transmit_batch,
)
