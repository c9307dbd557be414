"""Host seconds of the shard format's pack inside the plan build: the
program's span ``plan.pack`` (``repro.core.build_spmv_plan``), in set-up."""
from program_spans import seconds


def read(ctx):
    return seconds("plan.pack")
