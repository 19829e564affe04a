"""`pytest bench/tests` — the benchmark's own tests: CPU only, not part of
tier 1.  They import the harness's modules the way its programs do."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
