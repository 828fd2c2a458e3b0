"""Read path: host ms a query round spends in ``jax.device_get`` of the
answers: waiting for the device and the device→host copy (span
``repro.query.fetch``)."""


def read(run):
    reader = getattr(run.summary, "program_span_mean", None)
    if reader is None:
        return None
    s = reader("query", "query.fetch")
    return None if s is None else s * 1e3
