"""Host dispatch: median host ms of one ``TrainerRunner.step`` call in the
traced window, from the program's ``repro.step`` spans (bench/spans.py)."""
from bench import spans


def read(ctx):
    return spans.step_ms(spans.of_cell(ctx.cell["name"]))
