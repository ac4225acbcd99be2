"""benchmark/tests run by hand and by rehearse.py, on the CPU only:
    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
They are not part of tier-1 (tests/), and no test prints a metrics line."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
