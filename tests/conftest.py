"""Run the suite on the CPU.

Set before any test module imports JAX, so that neither the pytest
process nor the subprocess checks it starts (which inherit the
environment) take a TPU that the machine may hold.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
