"""The lower-precision control comes out as not correct, at a size a test
run holds: the float8 reference put in the program's place reads gaps far
above the program's own, and above the cell's limit."""
from bench import control


def test_float8_control_fails_where_the_program_passes(tiny_cell):
    tiny_cell["correct"]["min_tokens"] = 200
    seeds = [2 ** 33 + 1, 2, 3]
    rs = control.readings(tiny_cell, seeds, set(seeds), 3.0,
                          log=lambda *a: None)
    limit = tiny_cell["correct"]["widest_gap_limit"]
    lower = max(r["widest_gap"] for r in rs)
    upper = min(r["control_gap.fp8"] for r in rs)
    assert all(r["tokens"] >= 200 for r in rs)
    assert lower <= limit < upper
    assert upper >= 3 * lower
