import os
import sys

# The tests run on the CPU, with 8 virtual host devices for the sharding
# tests; the chip is driven by chip_smoke.py and bench.py. Set before jax is
# imported anywhere, overriding the environment.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soak configurations (chaos convergence sweeps); "
        "excluded from the tier-1 run (-m 'not slow')",
    )
