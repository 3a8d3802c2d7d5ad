import os
import sys

# tests run on the CPU with one device per process; a test that needs
# several devices starts a subprocess with forced host devices (XLA_FLAGS).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
