"""Read path: device busy ms a query round spends in the batched finalize
program (``XLA Modules`` events of ``jit_finalize_batch``)."""


def read(run):
    reader = getattr(run.summary, "module_busy", None)
    if reader is None:
        return None
    s = reader("query", "jit_finalize_batch")
    return None if s is None else s * 1e3
