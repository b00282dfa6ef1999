"""The benchmark's own tests: they import `bench` from the repository
root and drive it on the CPU at sizes a test run can hold."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
