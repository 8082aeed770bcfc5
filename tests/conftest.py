"""Suite-wide pytest config: the `slow` marker for the >10s model/train
tests; `scripts/run_tests.sh` deselects them by default (run with ``-m ""``
or ``--all`` for the full suite)."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: tests taking >10s (model-family train loops); "
        "deselect with -m 'not slow'")
