"""Device idle share (%, device trace) of the traced part of the window:
100 * (1 - busy / window), busy being the union of the device's
operations, averaged over the chips."""
from bench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "_idle.py").read
