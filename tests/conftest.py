"""Test-session setup: make `src/` importable and force host devices.

The tier-1 command runs with PYTHONPATH=src (also set via pytest.ini
``pythonpath``); the sys.path insert below keeps direct `pytest tests/...`
invocations working from any cwd.

The XLA_FLAGS guard forces 4 simulated host devices for the whole test
session (jax reads the flag at first backend init, so it must be set
before any test imports jax): the mesh equivalence suite
(test_mesh_snn.py) needs a 4-way mesh, and running the *entire* tier-1
suite under forced multi-device is itself part of the contract — every
single-device path must be oblivious to how many devices exist. An
explicit user-set XLA_FLAGS is respected.
"""
import os
import sys
from pathlib import Path

if "XLA_FLAGS" not in os.environ and "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

SRC = str(Path(__file__).resolve().parents[1] / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
