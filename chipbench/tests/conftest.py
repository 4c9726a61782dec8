"""The benchmark's own tests: they run on the CPU, apart from the
repository's test suite (``pytest chipbench/tests``)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def cells():
    """The cells ``BENCHMARK.json`` names."""
    import json

    return [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
