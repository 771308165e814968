import os
import sys

# The benchmark's own tests run on the CPU (Pallas kernels interpreted),
# against the program in ``src/``.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if _SRC not in sys.path:
    sys.path.append(_SRC)
