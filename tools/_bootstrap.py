"""Put the repo root on sys.path so ``python tools/<script>.py`` can
import the package and __graft_entry__ (script dir, not cwd, is
sys.path[0]).  Every tools/ script starts with ``import _bootstrap``.
"""

import os
import sys

_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _root not in sys.path:
    sys.path.insert(0, _root)
