import os
import sys

# the benchmark package lives at the repository root, beside src/
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
