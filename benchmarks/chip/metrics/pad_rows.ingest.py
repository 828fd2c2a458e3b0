"""Tenant sharding: padding rows an ingest round adds to the batch so that
each chip gets as many rows as the fullest (arg ``pad_rows`` of span
``repro.ingest.stack``), mean a round.  Not in ``BENCHMARK.json`` until
the harness reduces traces with `chipbench.program_spans`."""
from chipbench import program_spans


def read(run):
    s = run.summary
    if not isinstance(s, program_spans.ProgramSummary):
        return None
    rounds = s.of_kind("ingest")
    pads = [span.args.get("pad_rows") for r in rounds
            for span in s._program_in(r, "repro.ingest.stack")]
    if not pads or None in pads:
        return None
    return sum(pads) / len(rounds)
