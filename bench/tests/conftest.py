"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# programs compiled here are not worth keeping, and must not land in the
# checkout's own cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
