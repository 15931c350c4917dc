"""Test-wide environment: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective code is
validated on host-platform virtual devices (the analogue of the reference's
fake-backend trick — distill_worker.py:34-42 `_NOP_PREDICT_TEST` — which runs
the whole multiprocess pipeline with zero network/GPUs).

The platform and device count are set both in the environment (what
subprocesses and `force_platform_from_env` read) and through jax.config (in
case a pytest plugin imported jax first) — either works as long as no backend
has been initialized yet.
"""

import os

os.environ.setdefault("EDL_TPU_TEST_DEVICES", "8")

# -- lockgraph plugin (EDL_TPU_LOCKGRAPH=1) ---------------------------------
# Install the lock-order recorder BEFORE any edl_tpu module is imported so
# module-level locks are created through the patched factories. The whole
# run then doubles as a deadlock audit: pytest_sessionfinish (below)
# analyzes the global lock-order graph and FAILS the session on any cycle
# (potential ABBA deadlock), with both acquisition stacks in the report.
# See edl_tpu/analysis/lockgraph.py and doc/design_analysis.md.
_LOCKGRAPH = None
if os.environ.get("EDL_TPU_LOCKGRAPH", "") == "1":
    from edl_tpu.analysis import lockgraph as _lockgraph_mod
    _LOCKGRAPH = _lockgraph_mod.install()

# Keep the ambient env consistent with the config below: in-process code
# that applies the env contract (parallel/distributed.py
# force_platform_from_env, e.g. examples run inside tests) must re-apply
# the SAME platform and device count.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = os.environ["EDL_TPU_TEST_DEVICES"]

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices",
                  int(os.environ["EDL_TPU_TEST_DEVICES"]))


# -- test tiers ------------------------------------------------------------
# `pytest -q` = the fast tier (minutes on one core); the multi-process
# integration suites are @pytest.mark.slow and run with `--runslow`
# (CI runs both tiers — .github/workflows/ci.yml).

def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked slow (multi-process "
                          "integration; ~15 extra minutes on one core)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process integration tests excluded from "
                   "the default run (enable with --runslow)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow tier: pass --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


def pytest_sessionfinish(session, exitstatus):
    if _LOCKGRAPH is None:
        return
    rep = _lockgraph_mod.write_report(_LOCKGRAPH,
                                      _lockgraph_mod.default_report_path())
    print(f"\nlockgraph: {rep['locks_tracked']} lock sites, "
          f"{rep['edges']} order edges, {len(rep['cycles'])} cycle(s), "
          f"{len(rep['hazards'])} hazard(s) -> "
          f"{_lockgraph_mod.default_report_path()}")
    if not rep["ok"]:
        print(_lockgraph_mod.render_failure(rep))
        session.exitstatus = 1
