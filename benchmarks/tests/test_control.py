"""The control: the reference at the next precision below the one each
configuration states, put in the program's place, comes out not correct
under the configuration's own limits, while the exact reference passes.
Small sizes (a test run's); PERF.md has the readings at the cells' own."""

import pytest

import control

ROWS, QUERIES = 16384, 128


@pytest.mark.parametrize("name", ["sift-flat-l2", "cohere-bq-cosine"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_next_lower_precision_fails(name, seed):
    cfg = control.load_config(name)
    sound = control.verdict(cfg, seed, "float32", ROWS, QUERIES)
    assert sound["correct"], sound["numbers"]
    low = control.verdict(cfg, seed, cfg["precision"]["next_lower"], ROWS,
                          QUERIES)
    assert not low["correct"], low["numbers"]
    # the number the lower precision has to fail, with room: the control's
    # reading is over three times the limit's
    err = low["numbers"]["distance_error_max"]
    assert err["value"] > 3 * err["limit"]
