# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device; only launch/dryrun.py (its own process) forces 512
# placeholder devices.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Fall back to the vendored hypothesis shim only when the real package is
# missing (this container has no index; requirements-dev.txt declares it).
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "_shims"))
