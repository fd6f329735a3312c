"""``device_idle_share.oneshot``'s reading, in the tutorial cell: see that reader."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_device_idle_share", Path(__file__).with_name("device_idle_share.oneshot.py"))
_oneshot = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_oneshot)

read = _oneshot.read
