"""``dispatch_ahead_pct.sat`` (PR 35): the reader on hand-written scrapes,
its entry in ``BENCHMARK.json``, and the counter it reads on a rehearsed
cell of each kind (a causal family dispatches ahead, a block family does
not)."""

import time

import pytest

import run as runner
from lib import spec
from tiny import tiny_cell

NAME = "dispatch_ahead_pct.sat"
P = "pygrid_serving_dispatches_total"


def _ctx(w0: dict, w1: dict) -> dict:
    return {"kind": "serve", "seconds": 50.0, "snap_w0": w0, "snap_w1": w1}


def test_the_reader_on_hand_written_scrapes():
    read = spec.load_reader(NAME)
    w0 = {f'{P}{{ahead="yes"}}': 10.0, f'{P}{{ahead="no"}}': 5.0}
    w1 = {f'{P}{{ahead="yes"}}': 310.0, f'{P}{{ahead="no"}}': 105.0}
    assert read(_ctx(w0, w1)) == pytest.approx(100 * 300 / 400, rel=1e-12)
    # a series born inside the window counts from nought
    assert read(_ctx({}, w1)) == pytest.approx(100 * 310 / 415, rel=1e-12)
    # a block family: every dispatch waits for the one before it
    only_no = {f'{P}{{ahead="no"}}': 105.0}
    assert read(_ctx({}, only_no)) == 0.0
    # the parent of PR 35 has no such counter; a training cell no engine;
    # a window without a dispatch nothing to divide by
    other = {'pygrid_serving_tokens_total{model="bench"}': 1e6}
    assert read(_ctx(other, other)) is None
    assert read({"kind": "train"}) is None
    assert read(_ctx(w1, w1)) is None


def test_the_entry_lists_the_three_saturated_cells():
    bench = spec.load_benchmark()
    assert spec.check_rules(bench) == []
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert bench["per_layer"][-1] == entry  # appended, nothing moved
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "gen_tokens_per_s",
        "workloads": ["batch-saturate", "reason-saturate", "blockdiff-saturate"],
    }
    for w in entry["workloads"]:
        mine = {e["name"] for e in spec.metrics_for(bench, "end_to_end", w)}
        assert entry["moves"] in mine


@pytest.mark.parametrize(
    "cell, ahead",
    [("batch-saturate", True), ("blockdiff-saturate", False)],
)
def test_a_rehearsed_cell_reads_it(cell, ahead):
    """The closed loop at the tiny size keeps more callers than slots: a
    causal family's steps go out ahead of the tokens read, a block
    family's never do."""
    runner.T_START = time.time()
    run = runner.run_cell(tiny_cell(cell), 2**31 + 35, 2.0, False, False)
    assert run["verdict"]["correct"] is True and run["failed"] == 0
    got = spec.load_reader(NAME)(run["ctx"])
    assert (got > 50.0) if ahead else (got == 0.0)
