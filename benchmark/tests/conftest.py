"""The benchmark's own tests: CPU only, toy sizes, no chip. Run with
``python -m pytest benchmark/tests -q`` from the root of the repo.
They are not part of tier-1 (``tests/``)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)
