import os
import sys

# the benchmark's tests run here, on the CPU, by path:
#   python -m pytest chipbench/tests -q
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
