"""The benchmark's own tests: CPU only, quick. The repository's root goes on
the path so that ``chipbench`` (and not this directory) is what imports find."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
