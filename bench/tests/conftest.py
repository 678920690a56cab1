"""The benchmark's tests: ``python -m pytest bench/tests`` (outside the
repository's tier-1 ``testpaths``).  They run on the CPU at tiny sizes."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
for p in (str(HERE), str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
