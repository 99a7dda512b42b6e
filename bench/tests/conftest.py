"""The benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They sit outside the repo's tier-1 ``testpaths``.  ``bench/`` and
``src/`` go on the path as ``bench/run.py`` puts them there.
"""

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
