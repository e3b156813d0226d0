"""The benchmark's own modules (``benchmarks/chip``) import by plain name."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))
