"""``wide_rung_share.oneshot``'s reading, in the tutorial cell: see that reader."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_wide_rung_share", Path(__file__).with_name("wide_rung_share.oneshot.py"))
_oneshot = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_oneshot)

read = _oneshot.read
