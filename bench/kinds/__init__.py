"""Kinds of cell.  A deployment's ``kind`` names its module here,
``bench/kinds/<kind>.py``, which the harness finds by that name.  A kind
module defines

* ``RATE``: the end-to-end rate its calls feed, a metric of
  ``BENCHMARK.json``;
* ``Cell(config, traffic, seed)``, with ``warmup()`` (one call of the
  cell's own shapes, in set-up), ``inputs(index)`` (what window call
  ``index`` takes, made from the seed, untimed), ``run(inputs)`` (the
  timed call), ``record(inputs, out)`` (keeps what the check needs and
  returns the call's work in the rate's units), ``counters()`` (the
  program's counters, cumulative) and ``check(limits)`` (``(checks,
  failed)`` once the window has closed; ``limits`` is
  ``bench/limits/<kind>.json``);
* ``Control``: a ``Cell`` with the kind's control in the program's
  place, which the check must find not correct.
"""
