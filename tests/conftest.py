import os
import sys

# The suite runs on the CPU backend, Pallas kernels in interpret mode; the
# program runs on a TPU through `chip_smoke.py`. Tests run on the single
# host device (multi-device cases force N host devices in their own
# subprocess, or are `distributed`-marked).
os.environ["JAX_PLATFORMS"] = "cpu"
_HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(_HERE, "..", "src"))
# Repo root too, so tests can import the `benchmarks` package (e.g. the
# stylized-facts smoke reuses benchmarks.emergent_dynamics.stylized_facts).
sys.path.insert(0, os.path.join(_HERE, ".."))
