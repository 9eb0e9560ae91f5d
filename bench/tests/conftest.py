import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

tiny.paths()

# compiled programs of test runs go to a cache of their own, never into
# the checkout's, which the benchmark's chip runs read
import os  # noqa: E402
import tempfile  # noqa: E402

os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="bench-tests-jax-cache-")
