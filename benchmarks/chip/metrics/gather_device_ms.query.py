"""Read path: device busy ms a query round spends in the gather and ⊕-fold
program (``XLA Modules`` events of ``jit_gather_merge``)."""


def read(run):
    reader = getattr(run.summary, "module_busy", None)
    if reader is None:
        return None
    s = reader("query", "jit_gather_merge")
    return None if s is None else s * 1e3
