import sys
from pathlib import Path

# the program under test, for runs without PYTHONPATH=src
SRC = str(Path(__file__).resolve().parents[2] / "src")
if SRC not in sys.path:
    sys.path.append(SRC)
