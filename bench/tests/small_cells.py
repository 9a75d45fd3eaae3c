"""The cells cut to a size the CPU runs in seconds, for the tests.

``CELLS`` run on one device. ``FOUR_CHIP_CELLS`` need a 2x2 mesh: four host
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``, set
before JAX starts), as ``bench/tests/test_bench_sharded.py`` gives them."""

SILO = {"config": {"tenants": [
    {"name": "silo", "count": 1, "kind": "dense", "dim": 256, "clients": 4,
     "rows_per_client": 256, "gamma": 0.5, "noise_std": 0.1,
     "sigmas": [0.01, 1.0, 100.0], "admit": "wire"}]},
    "traffic": {"knee_per_s": 200.0}}
FLEET = {"config": {"tenants": [
    {"name": "dense", "count": 3, "kind": "dense", "dim": 64, "clients": 3,
     "rows_per_client": 64, "gamma": 0.5, "noise_std": 0.1,
     "sigmas": [0.1, 1.0], "admit": "pool"},
    {"name": "rff", "count": 3, "kind": "rff", "dim": 64, "d_orig": 16,
     "clients": 3, "rows_per_client": 64, "gamma": 0.5, "noise_std": 0.1,
     "sigmas": [0.1, 1.0], "admit": "pool"}]},
    "traffic": {"knee_per_s": 60.0, "popularity": {"reshuffle_s": 0.5}}}
CELLS = {"silo_d4096.stream": SILO, "fleet_d2048.burst": FLEET}
SILO_SHARDED = {"config": {"tenants": [
    {"name": "silo", "count": 1, "kind": "dense", "placement": "sharded",
     "dim": 256, "clients": 4, "rows_per_client": 256, "gamma": 0.5,
     "noise_std": 0.1, "sigmas": [0.01, 1.0, 100.0], "admit": "pool"}]},
    "traffic": {"knee_per_s": 40.0}}
FOUR_CHIP_CELLS = {"silo_d16384.read": SILO_SHARDED}
