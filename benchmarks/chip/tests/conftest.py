"""Tests of the benchmark's own yardstick. Run by hand and in the CPU
rehearsal, never by the repository's tier-1 command (which runs tests/):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q -p no:cacheprovider
"""

import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(CHIP, "tests"), CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)
