"""The benchmark's CPU tests import it as the package ``bench`` from the
repository root."""
import pathlib
import sys

ROOT = str(pathlib.Path(__file__).resolve().parents[2])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
