"""``early_dispatch_share``: the share of the window's launch records
that a step dispatched after its completion, read under both names the
benchmark gives it, and None from records that carry no ``early`` field
(a program that never refills the window)."""
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
import harness  # noqa: E402

from repro.serving.telemetry import LaunchRecord  # noqa: E402

NAMES = ["early_dispatch_share.column", "early_dispatch_share.open"]


@pytest.mark.parametrize("metric", NAMES)
def test_share_of_early_launch_records(metric):
    recs = [LaunchRecord(index=i, ts_start=float(i), batch=1, width=1,
                         early=i % 4 != 0) for i in range(8)]
    read = harness.load_reader(ROOT, metric)
    assert read(SimpleNamespace(launches=recs)) == pytest.approx(75.0)
    for r in recs:
        r.early = False
    assert read(SimpleNamespace(launches=recs)) == 0.0


@pytest.mark.parametrize("metric", NAMES)
def test_none_without_the_field_or_records(metric):
    read = harness.load_reader(ROOT, metric)
    bare = [SimpleNamespace(index=i, ts_start=float(i), batch=1)
            for i in range(3)]
    assert read(SimpleNamespace(launches=bare)) is None
    assert read(SimpleNamespace(launches=[])) is None
