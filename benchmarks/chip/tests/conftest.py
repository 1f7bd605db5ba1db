"""The harness modules live one directory up, beside ``cell.py``."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)
