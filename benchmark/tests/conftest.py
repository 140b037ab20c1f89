import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# CPU programs written to the checkout's persistent cache are tied to this
# host's CPU features and break other processes that load them later.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
