"""Fisher probe time per task: the program's own ``Adaptation.fisher_seconds``
(host time of the batched probe dispatch up to the fetch of its scores,
shared out over the tasks of its group), summed over the window's tasks."""


def read(r):
    if not r.get("tasks"):
        return None
    return 1e3 * r["fisher_seconds"] / r["tasks"]
