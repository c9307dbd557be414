"""Host seconds of placing the operator on the cell's devices, up to the
end of the transfer: the program's span ``solver.place``
(``repro.util.place_operands``), in set-up."""
from program_spans import seconds


def read(ctx):
    return seconds("solver.place")
