"""The harness's tests: CPU only, small sizes, no chip."""

import os
import sys

_ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
for path in (os.path.join(_ROOT, "src"), _ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
