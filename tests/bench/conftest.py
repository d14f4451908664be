import os
import sys

# the benchmark's package lives at the root of the checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
