"""poseidon_tpu — a TPU-native distributed CNN training framework.

Brand-new implementation of the capabilities of petuum/poseidon (PMLS-Caffe):
prototxt-defined CNN training, Caffe-exact solvers, distributed data
parallelism with DWBP-style communication/compute overlap, sufficient-factor
broadcasting for FC gradients, and bounded-staleness synchronization — built
on JAX/XLA/pjit for TPU meshes. See ARCHITECTURE.md for the design map.
"""

__version__ = "0.1.0"

from . import config  # noqa: F401
# the process-wide span recorder's start-up phase opens here, at the
# package's first import (stdlib only; runtime/__init__.py is lazy)
from .runtime import spans  # noqa: F401
