"""The benchmark harness's fault-handling cases, counted in tier-1: the
file under ``chipbench/tests/`` is loaded by path, so there is no second
copy to keep alike. No JAX; its stub servers bind port 0."""

import importlib.util
import os
import sys

_CHIPBENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chipbench")
sys.path.insert(0, _CHIPBENCH)  # the cases import client, run, launcher

_spec = importlib.util.spec_from_file_location(
    "chipbench_test_client_faults",
    os.path.join(_CHIPBENCH, "tests", "test_client_faults.py"))
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)
globals().update(
    {k: v for k, v in vars(_cases).items() if not k.startswith("__")})
