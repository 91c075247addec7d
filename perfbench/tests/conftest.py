"""The benchmark's own tests: `python -m pytest perfbench/tests -q` from the
root of the repo, on the CPU. They are not part of the repo's tier-1 run."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
