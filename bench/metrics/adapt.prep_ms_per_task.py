"""Host time per task spent preparing device inputs inside ``adapt_many``:
bucketing the episodes, then stacking (and placing) each probe group's and
each fine-tune group's inputs; the program's spans ``adapt_many.bucket``,
``.probe.stack`` and ``.finetune.stack``."""
import program_spans


def read(r):
    return program_spans.ms_per_task(r, ("adapt_many.bucket",
                                         "adapt_many.probe.stack",
                                         "adapt_many.finetune.stack"))
