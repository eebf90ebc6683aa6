import os
import sys

# allow plain `pytest tests/` without PYTHONPATH=src
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# make `from _propshim import ...` work regardless of pytest import mode
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# NOTE: deliberately NOT setting XLA_FLAGS here — the tests must see 1
# device; multi-device tests spawn subprocesses with their own XLA_FLAGS.
