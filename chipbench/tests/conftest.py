"""chipbench's own tests run on the CPU, by hand:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

(tier-1 runs ``tests/`` only.) They use the tiny presets in
``chipbench/rehearse/``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
