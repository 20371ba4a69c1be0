import os
import sys

# four host devices for the sharded cell's self-test: the flag must be set
# before jax initializes; a caller's own XLA_FLAGS keep their device count
_DEVICES_FLAG = "--xla_force_host_platform_device_count"
if _DEVICES_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        [os.environ.get("XLA_FLAGS", ""), f"{_DEVICES_FLAG}=4"]).strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH, os.path.dirname(__file__)):
    if p not in sys.path:
        sys.path.insert(0, p)
