"""Put the benchmark's directory and the program's ``src`` on the path."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
CHECKOUT = CHIP.parents[1]
for p in (str(CHECKOUT / "src"), str(CHIP)):
    if p not in sys.path:
        sys.path.insert(0, p)
