"""The Pallas TPU names every kernel module takes from one place.

``ANY`` is the memory space of an input the kernel leaves in HBM and stages
itself with explicit DMAs (the page pools and the contiguous latent cache).
"""

from __future__ import annotations

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams
ANY = pl.ANY
