"""Pytest bootstrap: dump the flight-recorder black box on the first test
failure (CI uploads ``results/blackbox/`` as the ``tier1-blackbox``
artifact).
"""

import os

import pytest


_BLACKBOX_DUMPED = False


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On the first test failure, dump the process-global flight recorder:
    the newest spans/instants/metric snapshots any enabled Tracer fed before
    the assertion — the suite's black box, loadable in Perfetto."""
    outcome = yield
    rep = outcome.get_result()
    global _BLACKBOX_DUMPED
    if rep.when != "call" or not rep.failed or _BLACKBOX_DUMPED:
        return
    _BLACKBOX_DUMPED = True
    try:
        from repro.obs.trace import FLIGHT_RECORDER

        if len(FLIGHT_RECORDER) == 0:
            return
        out = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "results", "blackbox",
        )
        os.makedirs(out, exist_ok=True)
        safe = item.nodeid.replace("/", "_").replace("::", "-")
        FLIGHT_RECORDER.dump(os.path.join(out, f"{safe}.json"))
    except Exception:
        pass  # the black box must never mask the real test failure
