"""What a traffic driver is, and the pieces drivers share.

A traffic file's ``driver`` key names a file ``bench/drivers/<name>.py``;
the rest of the traffic file is that driver's parameters, so adding a mix
is adding a file, and adding a way of offering load is adding a driver
file. The file defines ``Driver``, a class that ``run.py`` builds as
``Driver(cell, seed, seconds)`` (``harness.registry.Cell``: configuration,
traffic, regime and space) and then calls in this order:

  ``setup()``   data from the seed (``harness.data.Deployment``), the
                program's state, any index build and a warm-up of exactly
                the window's shapes;
  ``window()``  the measured traffic for ``seconds`` (nothing compiles
                here), recorded as ``calls``, a list of ``Call``;
  ``release()`` frees the program's state once ``memory_peak_bytes`` is
                read, so the reference does not set the peak;
  ``check()``   the plain reference (``harness.reference``) over a sample
                drawn from the seed, against every answer due in the
                window; returns a ``Tally``.

The benchmark's parts are files under ``bench/``: ``configs/``,
``traffic/``, ``metrics/``, ``regimes/``, ``spaces/`` and ``drivers/``;
``harness/registry.py`` says what each file defines.

A driver may also set ``build_s`` (the index build's seconds) and ``jcfg``
(the program's join configuration) for the metric readers.
``tools/control.py`` puts the control in a driver's place through
``make_data()``, ``X``, ``dep`` and ``theta``. Drivers reach the program
only through its entry points, with inputs generated here.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Call:
    """One timed call of the program."""
    t0: float
    t1: float
    n_queries: int
    stats: object          # the program's JoinStats of the call
    pairs: np.ndarray
