"""CPU rehearsal of the chip benchmark: ``python -m pytest benchmarks/chip/tests``
from the checkout root.  The benchmark's modules import each other by bare
name, as ``run.py`` arranges for itself."""
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("REPRO_QMM_IMPL", "interpret")
