"""Host time per task building the results of each fine-tune group (one
adaptation object per task with its evaluator): the program's
``adapt_many.finish`` spans."""
import program_spans


def read(r):
    return program_spans.ms_per_task(r, ("adapt_many.finish",))
