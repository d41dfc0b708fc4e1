import os
import sys

# The benchmark's own tests run on the CPU: the harness's look for a chip
# is skipped and the program's kernels run in Pallas interpret mode.
os.environ["JAX_PLATFORMS"] = "cpu"
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
