import pathlib
import sys

# the benchmark package lives at the repository root, beside src/
_ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (_ROOT, _ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
