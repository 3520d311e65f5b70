"""Host time per task copying results to the host once the device is done:
the probe's scores and the fine-tune's deltas, losses and skip flags; the
program's ``adapt_many.probe.fetch`` and ``.finetune.fetch`` spans."""
import program_spans


def read(r):
    return program_spans.ms_per_task(r, ("adapt_many.probe.fetch",
                                         "adapt_many.finetune.fetch"))
