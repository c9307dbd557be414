"""The control: the plain reference one precision below the
configuration's, put in the program's place, reads incorrect where the
program reads correct, on three seeds, at the rehearsal size
(``control.py`` runs it at the cells' own size on the chip host)."""
import pytest

import control
import run

SEEDS = (3, 17, 2**31 + 11)


def _run(cell, seed, make_system=None):
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      "0.3", "--rehearse"])
    return run.run(args, make_system=make_system)


@pytest.mark.parametrize("cell", ["fig3-13.5M.iters", "poisson-200k.solve"])
def test_control_fails_program_passes(cell, no_compile_cache):
    for seed in SEEDS:
        ctl = _run(cell, seed, control.control_system)
        assert ctl["correct"] is False, (seed, ctl["checks"])
        prog = _run(cell, seed)
        assert prog["correct"] is True, (seed, prog["checks"])
        # the number that separates them reads at least 10x apart
        name = next(iter(prog["checks"]))
        assert ctl["checks"][name]["value"] > 10 * prog["checks"][name][
            "value"]
