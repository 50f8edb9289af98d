"""A traced dry drive turns telemetry on for its process, as the runner
does for its own; the test process is shared, so turn it off again."""
import pytest


@pytest.fixture(autouse=True)
def _telemetry_off_again():
    yield
    from mxnet_tpu import telemetry
    telemetry.disable()
