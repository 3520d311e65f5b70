"""Time per task from the dispatch of each group's fine-tune scans to the
device's completion, as the host waits for it: the program's
``adapt_many.finetune.run`` spans."""
import program_spans


def read(r):
    return program_spans.ms_per_task(r, ("adapt_many.finetune.run",))
