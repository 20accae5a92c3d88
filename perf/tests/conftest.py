"""perf/tests run by hand on the CPU: ``python -m pytest perf/tests -q``
(not part of tier-1). The repo's root conftest.py pins the CPU platform
and eight virtual devices for every pytest session under the root."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
