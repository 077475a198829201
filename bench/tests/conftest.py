"""CPU tests of the benchmark itself: python3 -m pytest bench/tests"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent))
import smoke  # noqa: E402,F401  (puts bench/ and src/ on sys.path)
