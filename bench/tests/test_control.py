"""The control comes out not correct where sound runs come out correct,
at a size a test run holds: the int8 reference in place of a served
model."""
from bench import harness, ref_lm, weights
from bench.tests.conftest import quiet

SEEDS = (11, 2 ** 33 + 5, 2 ** 40 + 9)


def test_int8_reference_control_fails_the_gap_limit(tiny_root):
    cell = harness.find_cell("tiny-chat", tiny_root)
    drv = harness.driver_module(cell)
    limit = cell.cell["check"]["limits"]["served_gap_max"]
    S = drv.Served(cell, SEEDS[0], quiet)
    for seed in SEEDS:
        if seed != SEEDS[0]:
            S.server.params = S.make_weights(seed)
        S.reset()
        rec = drv.measure(S, cell.mix, seed, 2.0, lead=0.5, drain=20,
                          log=quiet)
        pairs = drv.sample(rec["finished"], 4, seed)
        params = weights.make(cell.config, S.wseed, S.dtype)
        prog = max(ref_lm.served_gaps(cell.config, params, p, o).max()
                   for p, o in pairs)
        ctrl = max(ref_lm.control_gaps(cell.config, params, p, o).max()
                   for p, o in pairs)
        assert prog <= limit < ctrl, (seed, prog, ctrl)

