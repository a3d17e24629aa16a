"""A per-layer reader of ``metrics/``, loaded as a module.

The reader files are named by their metrics, dots included, so
``import`` cannot find them.  A reader that reads the same quantity as
another in other cells, or shares its count of the work, loads that
reader here rather than copying it.
"""

from __future__ import annotations

import importlib.util
import re
from pathlib import Path

METRICS = Path(__file__).resolve().parent / "metrics"


def load(metric: str):
    """The module of ``metrics/<metric>.py``."""
    spec = importlib.util.spec_from_file_location(
        "metric_" + re.sub(r"\W", "_", metric), METRICS / f"{metric}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
