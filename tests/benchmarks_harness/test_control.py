"""The control of each configuration: its plain reference computed in the
nearest precision below the one the configuration states (bfloat16 for
float32), put in the program's place.  The comparison has to reject it,
by the limits the configuration's file states, while the reference itself
passes clean.  (On the chip the same was read at the cells' own sizes:
PERF.md, section 2.)"""

import pytest

from benchmarks import compare
from bench_tiny import SEEDS, tiny_cell

N_QUERIES = 48


def _numbers(cell, seed, precision):
    cfg = cell.cfg
    data = cell.kind.generate(cfg, seed)
    queries = cell.kind.queries(cfg, data, seed)[:N_QUERIES]
    exact = cell.reference.Reference(cfg, data)
    served = cell.reference.Reference(cfg, data, precision)
    rows = [[(str(i), s) for i, s in r] for r in served.topk_many(queries)]
    numbers = compare.compare(exact, queries, rows, cfg["k"])
    numbers.update(failed=0, device_faults=0)
    return numbers, cfg["limits"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ["msmarco_closed", "sift_paced"])
def test_the_control_is_rejected_and_the_reference_is_not(name, seed):
    cell = tiny_cell(name)
    numbers, limits = _numbers(cell, seed, "float64")
    correct, _ = compare.verdict(numbers, limits)
    assert correct, numbers
    numbers, limits = _numbers(cell, seed, "bfloat16")
    correct, lines = compare.verdict(numbers, limits)
    assert not correct, lines
    # by a wide margin, on the number that reads the arithmetic
    assert numbers["score_err"] > 20 * limits["score_err"]


def test_judge_is_tie_aware():
    import numpy as np

    ref = np.array([3.0, 2.0, 2.0])
    # the doc left out ties the worst one kept: no gap
    assert compare.judge_rows([("5", 3.0), ("9", 2.0), ("4", 2.0)], ref,
                              2.0, 50, 3) == (0, 0.0, 0.0)
    # a better doc was left out
    bad = compare.judge_rows([("5", 3.0), ("9", 2.0), ("4", 2.0)], ref,
                             2.5, 50, 3)
    assert bad[0] == 0 and bad[2] == pytest.approx(0.25)
    # too few hits, a repeated id, an unknown id, hits out of order
    assert compare.judge_rows([("5", 3.0)], ref[:1], 2.0, 50, 3)[0] == 1
    assert compare.judge_rows([("5", 3.0), ("5", 3.0), ("4", 2.0)], ref,
                              0.0, 50, 3)[0] == 1
    assert compare.judge_rows([("x", 3.0), ("9", 2.0), ("4", 2.0)], ref,
                              0.0, 50, 3)[0] == 1
    assert compare.judge_rows([("9", 2.0), ("5", 3.0), ("4", 2.0)],
                              ref[[1, 0, 2]], 0.0, 50, 3)[0] == 1
    # fewer docs match than k: that many hits are right
    assert compare.judge_rows([("5", 3.0), ("9", 2.0)], ref[:2], 0.0, 2,
                              3) == (0, 0.0, 0.0)
