"""The chip benchmark of the ICQ vector-search system (``BENCHMARK.json``).

Everything that measures lives here, apart from the program: data
generation, exact ground truth and recall, the plain reference that
decides ``correct``, trace reduction, the peaks table and the work
counts.  ``run.py`` runs one cell once; see ``PERF.md`` at the root.
"""
