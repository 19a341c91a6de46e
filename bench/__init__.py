"""The chip benchmark of the coordination store.

``run_cell.py`` runs one cell of ``BENCHMARK.json`` once.  Each cell is a
configuration (``configs/<name>.json``, whose ``driver`` names
``drivers/<driver>.py``), a traffic mix (``traffic/<name>.json``) and its
offered rate (``cells/<cell>.json``, in ops per tick).  Per-layer metrics are read by
``readers/<metric>.py`` from the run's trace and counts.  ``oracle.py``
decides ``correct``; ``control.py`` is the control it must refuse, and
``faults.py`` the faults planted under the timed path that it must catch;
``sweep.py`` finds a cell's knee.  Tests: ``python -m pytest bench/tests``.
"""
