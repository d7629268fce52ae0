"""The rehearsal tests run the cells on the CPU's virtual devices: the same
device count as the repository's own tests, set before JAX starts."""
import os
import sys

os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_force_host_platform_device_count=8 "
    "--xla_disable_hlo_passes=all-reduce-promotion",
)
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
