"""Median job latency, due time to Result (see latency.py)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import latency  # noqa: E402


def read(ctx):
    return latency.quantile(ctx, 0.50)
