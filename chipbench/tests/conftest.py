"""The benchmark's own tests run on the CPU, apart from the repo's suite:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests

from the root of the checkout (the program is imported from ``src``)."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
