import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
# ``tiny`` beside the tests, and ``benchmark`` at the root of the checkout
for path in (os.path.dirname(os.path.dirname(HERE)), HERE):
    if path not in sys.path:
        sys.path.insert(0, path)
