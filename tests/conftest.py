import os
import sys

# Tests run against the single default CPU device; tests/test_multidevice.py
# asks for more host devices in subprocesses of its own.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:
    from hypothesis import settings
except ImportError:  # container without hypothesis: deterministic stub
    from _hypothesis_stub import install

    install()
    from hypothesis import settings

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")
