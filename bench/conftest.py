import sys
from pathlib import Path

# the program under test, as bench/run.py puts it on the path
ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
