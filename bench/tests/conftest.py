import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.join(HERE, ".."), os.path.join(HERE, "..", "..", "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
