"""Host time per task spent choosing each task's policy from its probe
scores (Fisher potentials and budgeted selection): the program's
``adapt_many.select`` spans."""
import program_spans


def read(r):
    return program_spans.ms_per_task(r, ("adapt_many.select",))
