"""The benchmark's own tests: by hand, from the root of the repo,

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

No chip and no topology call.  The platform is pinned before jax loads, so a
test run beside a serving process cannot take its device.
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
