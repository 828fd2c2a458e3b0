import os
import sys

# The benchmark's own tests run on the CPU, at tiny sizes, with the Pallas
# kernels interpreted; the measurement path itself needs a TPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from repro.core.backend import PallasBackend, register_backend  # noqa: E402

register_backend("pallas", PallasBackend(interpret=True))
