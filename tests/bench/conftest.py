import os
import sys

# The benchmark is imported as the package ``chipbench`` from the root of
# the checkout.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
