"""The benchmark's own tests run by path, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
