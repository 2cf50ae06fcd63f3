"""Puts the repository root and ``src`` on the path for the harness tests.

Run from the repository root: ``python -m pytest chipbench/tests``.  The
tests run on the CPU at tiny sizes; nothing here times anything.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
