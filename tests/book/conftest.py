import os
import sys

# Standalone-safe: when pytest is invoked from INSIDE tests/book, the parent
# tests/conftest.py is outside the confcut and never loads — without this,
# the first Executor.run would initialize the host's default platform
# instead of the virtual CPU mesh.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import cpu_mesh  # noqa: F401,E402  (must precede any jax-using import)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
