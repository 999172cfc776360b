"""On-chip benchmark of the graph-streaming service (see ``run.py``).

Everything a cell needs lives here and is found by name: a deployment in
``configs/<config>.json``, a traffic mix in ``traffic/<mix>.json`` (which
names its generator module in ``traffic/``), and one reader per metric in
``metrics/<metric>.py``.  The program under test is imported only by
``harness.py`` and ``traffic/``; generators, the reference and the trace
reduction import nothing of it.
"""
