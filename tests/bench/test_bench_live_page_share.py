"""The reader of the paged kernel's live-page share
(bench/metrics/kernel.paged_live_page_share) on recorded `decode_step`
spans: with the two arguments the engine adds, and without them, as a
program that lacks them records its steps."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402
from bench.records import Run  # noqa: E402

CELLS = {
    "mistral-7b.chat-steady": "kernel.paged_live_page_share",
    "qwen2-7b.chat-closed": "kernel.paged_live_page_share--closed",
    "mistral-7b.longprompt-closed": "kernel.paged_live_page_share--closed",
}


def step(t, **args):
    return {"name": "decode_step", "ph": "X", "tid": 0,
            "ts": int(t * 1e6), "dur": 50000,
            "args": dict(occupancy=2, slots=4, queue_depth=0, **args)}


def read(cell_name, spans):
    cell = cells.resolve(cell_name, ROOT)
    run = Run(cell=cell, hf={}, peak={}, t0=10.0, t1=20.0, requests=[],
              spans=spans, device=None)
    return cell.reader(CELLS[cell_name]).read(run)


@pytest.mark.parametrize("cell_name", list(CELLS))
def test_live_page_share_is_the_mean_over_the_windows_steps(cell_name):
    spans = [step(5.0, live_pages=128, grid_pages=128),  # before the window
             step(11.0, live_pages=16, grid_pages=128),
             step(12.0, live_pages=32, grid_pages=128),
             step(13.0, live_pages=0, grid_pages=128),
             step(25.0, live_pages=128, grid_pages=128)]  # after it
    assert read(cell_name, spans) == pytest.approx(100 * (16 + 32 + 0) / 384)


@pytest.mark.parametrize("cell_name", list(CELLS))
def test_live_page_share_is_none_without_the_arguments(cell_name):
    assert read(cell_name, [step(11.0), step(12.0)]) is None
    assert read(cell_name, []) is None
    # a dense engine's steps carry neither; one paged step among them reads
    assert read(cell_name, [step(11.0), step(12.0, live_pages=8,
                                             grid_pages=32)]) == 25.0
