"""One module per plan statistic, found by its ``kind`` (see `chipbench.spec`).

Each module gives:

- ``declare(session, params)``: defer the statistic on a `FrameSession`;
  returns the name its answers carry;
- ``window(params)``: the rows one of its windows spans;
- ``stat_floats(params, d)``: floats of its slot in one tenant's state;
- ``flops(params, d, rows)``: the least operations for a chunk of ``rows``;
- ``reference(x, params)``: the plain float64 answer over a series ``x``;
- ``control(x, params)``: the same answer in the next lower precision;
- ``numbers(pairs, params)``: the gaps compared with their limits, over
  the sampled hosts' (answer, reference) pairs;
- optionally ``exact(answers, rows, params)``: exact checks over every host.

The references import nothing of the program under test.
"""
