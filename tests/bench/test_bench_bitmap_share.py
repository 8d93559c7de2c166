"""``planner.bitmap_share`` reads the served results' bitmap flag, and
reads nothing from results that lack it (a program without key bitmaps)."""
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest

from bench import cell


@pytest.mark.parametrize("results,expect", [
    ([SimpleNamespace(planned_by_bitmap=True), None,
      SimpleNamespace(planned_by_bitmap=False),
      SimpleNamespace(planned_by_bitmap=True),
      SimpleNamespace(planned_by_bitmap=True)], 0.75),
    ([SimpleNamespace(n_pulled=3)], None),
    ([None], None),
])
def test_bitmap_share_reader(results, expect):
    read = cell.metric_reader("planner.bitmap_share")
    run = cell.RunRecord(window=SimpleNamespace(results=results), trace=None)
    assert read(run) == expect
