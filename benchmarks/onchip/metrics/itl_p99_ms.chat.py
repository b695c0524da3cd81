"""``itl_p99_ms``'s reading (the 99th percentile of every gap between
consecutive tokens of a request inside the window) as a per-layer metric,
for the chat cell, whose tail is too unsteady to hold to a bound: about 15
chunk steps a window set the top 1% of some 2,300 gaps, and how many rows
decode beside them moves it by tens of percent (PERF.md, section 2)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "metric_itl_p99_ms",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "itl_p99_ms.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
read = _mod.read
