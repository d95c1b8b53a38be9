"""The benchmark's CPU tests import ``bench`` from the checkout's root and
``tiny`` from this directory."""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE.parents[1]), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
