import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"),
           os.path.join(_BENCH, "configs"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)
