"""Engine registry: ONE owner for histogram- and serving-engine
selection (registry.py). The {fused, pallas, xla-einsum} x mbatch x block
size x layout knob space resolves behind ``registry.resolve``, a pure
function of the config, the dataset's shape and the platform.

Module level stays jax-free (like ``obs``): ``scripts/tpulint``'s
stub-package trick imports pieces of this package before a backend
exists; everything that needs jax imports it lazily.
"""
from . import registry  # noqa: F401  (jax-free)
