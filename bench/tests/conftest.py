"""Put the checkout root (for ``bench``) and ``src`` (for the program) on
the path; the tests run on the CPU."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
