"""The harness's own tests: CPU, tiny sizes, not part of the repo's tier-1.

    JAX_PLATFORMS=cpu MLT_ATTN_INTERPRET=1 python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MLT_ATTN_INTERPRET", "1")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
