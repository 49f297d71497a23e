import os
import sys

# Tests see the real (single) CPU device. Distributed-parity tests spawn
# subprocesses with their own XLA_FLAGS instead.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
